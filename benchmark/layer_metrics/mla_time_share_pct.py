"""Share of the device's busy time spent on latent attention: operations
that read or write a tensor of a latent ring's shape `[slots or 1, ring
or key chunk, 576 or 512]`, of a decode step's scores of all heads over
it `[slots, 64, ring or key chunk]`, or of the expanded prefill's score
blocks of 256 queries (opcount/longcat_flash.py `is_mla_op`). The
low-rank projections, the absorbed products with `W_kvb`, the norms and
the rotary under the program's `mla_absorb` / `mla_expand` scopes are
plain XLA fusions that a TPU trace cannot tell from any other
(lib/program_time.py), so this is the rings' and the scores' share, a
lower bound of the two scopes'. Nothing where the configuration's
opcount has no such test."""
import os

from benchmark.lib import common


def read(ctx):
    cell, tr = ctx["cell"], ctx["trace"]
    busy = tr.busy_ns()
    oc = common.load_module(os.path.join(cell.dir, "opcount",
                                         cell.cfg["opcount"] + ".py"))
    match = getattr(oc, "is_mla_op", None)
    if not busy or match is None:
        return None
    return 100.0 * tr.time_by(lambda n, x: match(x, cell.cfg)) / busy

"""Share of the device's busy time spent on full-length attention:
operations that read or write a tensor of the full ring's shape `[slots
or 1, 8, cache_len, 128]`, of a decode step's scores over it, or of a
full layer's prefill score blocks (opcount/k_exaone.py
`is_full_attn_op`). The projections, norms and the rest under the
program's `attn_full` scope are plain XLA fusions that a TPU trace
cannot tell from any other (lib/program_time.py), so this is the ring's
and the scores' share, a lower bound of the scope's. Nothing where the
configuration's opcount has no such test."""
import os

from benchmark.lib import common


def read(ctx, which="is_full_attn_op"):
    cell, tr = ctx["cell"], ctx["trace"]
    busy = tr.busy_ns()
    oc = common.load_module(os.path.join(cell.dir, "opcount",
                                         cell.cfg["opcount"] + ".py"))
    match = getattr(oc, which, None)
    if not busy or match is None:
        return None
    return 100.0 * tr.time_by(lambda n, x: match(x, cell.cfg)) / busy

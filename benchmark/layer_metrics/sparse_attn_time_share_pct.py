"""Share of the device's busy time spent on block-sparse attention: the
selection (scores against the pooled keys, the block scores, the top-k),
the pooled ring's update and the block attention, prompt and decode
alike (opcount/minicpm_sala.py `is_sparse_select_op` /
`is_sparse_attend_op`, by operand shape: a TPU trace carries no scope
names, lib/program_time.py). The layers' projections, norms and gate
under the program's `sparse_attn` scope are plain fusions that a trace
cannot tell from any other, so this is a lower bound of the scope's.
Nothing where the configuration's opcount has no such predicates."""
import os

from benchmark.lib import common


def read(ctx):
    cell, tr = ctx["cell"], ctx["trace"]
    busy = tr.busy_ns()
    if not busy:
        return None
    oc = common.load_module(os.path.join(cell.dir, "opcount",
                                         cell.cfg["opcount"] + ".py"))
    if not hasattr(oc, "is_sparse_select_op"):
        return None
    return 100.0 * tr.time_by(
        lambda n, x: oc.is_sparse_select_op(x, cell.cfg)
        or oc.is_sparse_attend_op(x, cell.cfg)) / busy

"""Share of the device's busy time spent on the Lightning layers'
recurrence: operations on float32 tensors of the state's shape `[slots
or 1, 32, 128, 128]` (the decode step's pass over every slot's state,
the admission's write of one) or of the chunked prefill's chunk shapes
(opcount/minicpm_sala.py `is_state_op`). The mixers' projections,
rotation, norm and gate under the program's `lightning` scope are plain
fusions that a TPU trace cannot tell from any other
(lib/program_time.py), so this is the recurrence's share, a lower bound
of the scope's. Nothing where the configuration's opcount has no
`is_state_op`."""
import os

from benchmark.lib import common


def read(ctx):
    cell, tr = ctx["cell"], ctx["trace"]
    busy = tr.busy_ns()
    if not busy:
        return None
    oc = common.load_module(os.path.join(cell.dir, "opcount",
                                         cell.cfg["opcount"] + ".py"))
    if not hasattr(oc, "is_state_op"):
        return None
    return 100.0 * tr.time_by(
        lambda n, x: oc.is_state_op(x, cell.cfg)) / busy

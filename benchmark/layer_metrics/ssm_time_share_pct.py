"""Share of the device's busy time spent on the state-space layers'
recurrence: operations that read or write a float32 tensor of the
state's shape `[slots or 1, 128, 64, 128]` (the decode step's pass over
every slot's state, the admission's write of one, a prompt's chunk
borders) or of the chunked scan's chunk x chunk shapes
(opcount/nemotron_h.py `is_state_op`). The mixers' projections,
convolution, gate and norm under the program's `ssm` scope are plain
XLA fusions that a TPU trace cannot tell from any other
(lib/program_time.py), so this is the recurrence's share, a lower bound
of the scope's."""
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

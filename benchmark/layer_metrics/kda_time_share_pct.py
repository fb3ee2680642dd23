"""Share of the device's busy time spent on the linear-attention layers'
recurrence: operations that read or write a tensor of the state's shape
`[slots or 1, heads, 128, 128]` (the decode step's passes over the
state, the admission's write of it) or of the chunked prefill's chunk
shapes (opcount/solar_open2.py `is_state_op`). The mixers' projections,
convolution and gates under the program's `kda` scope are plain XLA
fusions that a TPU trace cannot tell from any other
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
    return 100.0 * tr.time_by(
        lambda n, x: oc.is_state_op(x, cell.cfg)) / busy

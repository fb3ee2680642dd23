"""Share of the device's busy time spent in the routed experts' grouped
matrix products: the `ragged-dot` Mosaic kernels XLA:TPU makes of
`jax.lax.ragged_dot` (three a layer: gate, up, down), their group
metadata kernel, and the fusions that take a kernel's result in
(opcount/solar_open2.py `is_expert_op`). The router, the sort, the
shared expert and the combine under the program's `moe_experts` scope
are plain XLA fusions that a TPU trace cannot tell from any other
(lib/program_time.py), so this is the grouped products' share, a lower
bound of the scope's."""
import os

from benchmark.lib import common


def read(ctx):
    cell, tr = ctx["cell"], ctx["trace"]
    busy = tr.busy_ns()
    if not busy:
        return None
    oc = common.load_module(os.path.join(cell.dir, "opcount",
                                         cell.cfg["opcount"] + ".py"))
    return 100.0 * tr.time_by(oc.is_expert_op) / busy

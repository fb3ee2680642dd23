"""Rows the non-gated experts' kernel multiplied over the rows it had to:
the program's `moe::tile_rows` samples in the window (a decode step's
work items times the row tile, one value an expert layer:
paddle_tpu/ops/pallas/grouped_relu2.py `work_items`) over its
`moe::pairs_here` samples (the token-expert pairs that landed on held
experts), the expert layers summed, mean over mean of the window's
decode steps. 1.0 is the least; what lies over it is padding of the
matrix unit's rows - a group of 4-5 pairs takes a 16-row tile, and a
tile two groups share is multiplied once for each - which costs nothing
while the kernel is bound by the weights it streams and is the first
number to look at when it is not. Nothing where the program has no such
counter (`jax.lax.ragged_dot` ran: the parent, the gated families, off
the chip)."""
import os

from benchmark.lib import common, program_time


def read(ctx):
    cell = ctx["cell"]
    tl = common.load_module(os.path.join(cell.dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    window = tl.window_ns(ctx)
    rows, pairs = (
        [sum(v) for v in program_time.counter_values(name, *window)]
        for name in ("moe::tile_rows", "moe::pairs_here"))
    if not rows or not pairs or not sum(pairs):
        return None
    return (sum(rows) / len(rows)) / (sum(pairs) / len(pairs))

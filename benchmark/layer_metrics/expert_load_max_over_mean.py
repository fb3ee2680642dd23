"""The straggler among the held experts: token-expert pairs the busiest
held expert was given over the mean of the held experts, from the sum of
the program's `moe::expert_load` samples in the window (prompts and
decode steps alike). 1.0 is an even load."""
import os

from benchmark.lib import common, program_time


def read(ctx):
    cell = ctx["cell"]
    tl = common.load_module(os.path.join(cell.dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    loads = program_time.counter_values("moe::expert_load", *tl.window_ns(ctx))
    if not loads:
        return None
    total = [sum(col) for col in zip(*loads)]
    mean = sum(total) / len(total)
    return max(total) / mean if mean else None

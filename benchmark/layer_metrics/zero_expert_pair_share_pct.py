"""Of the token-expert pairs of a decode step, the share that chose a
zero-compute expert (no weights read, no row of the grouped products:
the token times its weight): the mean over the window's steps and the
expert layers of the program's `moe::zero_pairs` samples, over `moe_topk`
pairs a slot (a decode step computes every slot). 256 of 768 router
outputs are zero-compute, so an even router reads 33. Nothing where the
program has no such counter."""
import os

from benchmark.lib import common, program_time


def read(ctx):
    cell = ctx["cell"]
    tl = common.load_module(os.path.join(cell.dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    zero = program_time.counter_values("moe::zero_pairs", *tl.window_ns(ctx))
    if not zero or "moe_topk" not in cell.cfg:
        return None
    mean = sum(sum(z) / len(z) for z in zero) / len(zero)
    return 100.0 * mean / (cell.cfg["moe_topk"] * ctx["res"]["slots"])

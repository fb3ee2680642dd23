"""Of the routed experts this chip holds, the share that got at least
one token in a decode step: the mean over the window's steps and over
the expert layers of the program's `moe::experts_hit` samples, over the
experts held."""
import os

from benchmark.lib import common, program_time


def read(ctx):
    cell = ctx["cell"]
    tl = common.load_module(os.path.join(cell.dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    hits = program_time.counter_values("moe::experts_hit", *tl.window_ns(ctx))
    if not hits:
        return None
    mean = sum(sum(h) / len(h) for h in hits) / len(hits)
    return 100.0 * mean / cell.cfg["experts_held"][1]

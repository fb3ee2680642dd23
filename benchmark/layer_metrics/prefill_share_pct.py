"""Share of the scheduler loop's working time that goes to admissions:
`generation::prefill` + `generation::prefill_fetch` over the window less
the loop thread's `serving::idle_wait`, all clipped to the window. Small
as a share, yet each admission puts a prefill program between two decode
steps: that is the p95 gap between tokens."""
import os

from benchmark.lib import common


def read(ctx):
    tl = common.load_module(os.path.join(ctx["cell"].dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    w0, w1 = tl.window_ns(ctx)

    def inside(*names):
        return sum(max(min(e, w1) - max(s, w0), 0.0)
                   for s, e, _ in tl.named(ctx, *names))

    if not tl.named(ctx, "generation::prefill_fetch"):
        return None
    working = (w1 - w0) - inside("serving::idle_wait")
    if working <= 0:
        return None
    return 100.0 * inside("generation::prefill",
                          "generation::prefill_fetch") / working

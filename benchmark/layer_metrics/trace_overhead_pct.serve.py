"""What the device trace costs the loop it watches: median start-to-start
of consecutive `generation::decode` spans inside the device-traced
interval over the same outside it (both inside the window), minus 1. The
program's host spans are on all through a traced run; the jax profiler
and its python tracer only for the traced interval: one run holds both
sides."""
import os
import statistics

from benchmark.lib import common


def read(ctx):
    tl = common.load_module(os.path.join(ctx["cell"].dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    w0, w1 = tl.window_ns(ctx)
    t0, t1 = tl.traced_ns(ctx)
    starts = [s for s, _, _ in tl.named(ctx, "generation::decode")
              if w0 <= s <= w1]
    traced, plain = [], []
    for a, b in zip(starts, starts[1:]):
        if a >= t0 and b <= t1:
            traced.append(b - a)
        elif b <= t0 or a >= t1:
            plain.append(b - a)
    if not traced or not plain:
        return None
    return 100.0 * (statistics.median(traced) / statistics.median(plain) - 1)

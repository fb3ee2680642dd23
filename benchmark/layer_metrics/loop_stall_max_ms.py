"""The longest single phase of the scheduler's plain loop: of the phases
of its loop thread that began inside the window, `serving::idle_wait`
excluded, and those at the two edges of the device-traced interval left
out. A run in which the whole server stands still for seconds (PERF.md,
Open questions) shows here, with the phase that held it: a `*_fetch` is
the device's side, anything else the host's. The phase's name and start
go on a line of their own, before the result line.

The edges are the trace's own doing, not the loop's. While `stop_trace`
collects it held the interpreter for 0.13-0.16 s, beginning 0.2 s after
the last device event; `start_trace` left a fetch of 85-86 ms that began
0.12 s before the first (PERF.md, section 5, PR 24). The harness leaves no
mark of when either call ran, hence the fixed allowance on both sides."""
import os

from benchmark.lib import common

PHASES = ("serving::pick", "generation::prefill",
          "generation::prefill_fetch", "serving::install",
          "generation::decode", "generation::decode_fetch",
          "serving::deliver")
EDGE_NS = 0.5e9


def read(ctx):
    tl = common.load_module(os.path.join(ctx["cell"].dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    w0, w1 = tl.window_ns(ctx)
    t0, t1 = tl.traced_ns(ctx)
    spans = [(e - s, s, n) for s, e, n in tl.named(ctx, *PHASES)
             if w0 <= s <= w1 and not (s <= t0 and e >= t0 - EDGE_NS)
             and not (s <= t1 + EDGE_NS and e >= t1)]
    if not any(n.startswith("serving::") for _, _, n in spans):
        return None  # a program without the loop's phase spans
    dur, start, name = max(spans)
    print(f"loop_stall_max_ms: {name} {dur / 1e6:.3f} ms, "
          f"{(start - w0) / 1e9:.3f} s into the window", flush=True)
    return dur / 1e6

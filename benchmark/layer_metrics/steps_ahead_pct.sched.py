"""Share of the window in which the scheduler's loop ran one decode step
ahead of what it had delivered: the time-weighted mean over the window of
its `serving::steps_ahead` samples (one per loop iteration, beside
`serving::slots_busy`; 1 when the iteration enqueues its step before it
fetches the step in flight, 0 when it drains for an admission, starts
again from the host's tokens, or waits idle; each holds until the next).
Where it reads high the device has its next program before the host has
seen this one's tokens, and the host's work an iteration costs no device
time. None for a program whose loop takes no such samples."""
import os

from benchmark.lib import common


def read(ctx):
    here = os.path.join(ctx["cell"].dir, "layer_metrics")
    tl = common.load_module(os.path.join(here, "host_gap_ms.serve.py"))
    sb = common.load_module(os.path.join(here, "slots_busy_pct.sched.py"))
    ahead = sb.counter_mean("serving::steps_ahead", *tl.window_ns(ctx))
    if ahead is None:
        return None
    return 100.0 * ahead

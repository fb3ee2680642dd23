"""Share of the decode slots that held a request, as the scheduler itself
counts them: the time-weighted mean over the window of its
`serving::slots_busy` samples (one per loop iteration, after admission and
before the step; each holds until the next) over the slots. The
scheduler's side of `slot_occupancy_pct`, which rebuilds the same from
the client's token times.

Also `counter_mean`, which `kv_live_pct` loads from here."""
import os

from benchmark.lib import common


def counter_mean(name, t0_ns, t1_ns):
    """Time-weighted mean of the program's counter samples of this name
    over [t0_ns, t1_ns] on perf_counter_ns, sample and hold; None where
    the program has no such samples (or no counter samples at all)."""
    from paddle_tpu import profiler

    samples = getattr(profiler, "counter_samples", None)
    if samples is None:
        return None
    pts = sorted((ev["ts"] * 1e3, ev["args"]["value"]) for ev in samples()
                 if ev["name"] == name)
    total = held = 0.0
    for (t, v), (t_next, _) in zip(pts, pts[1:] + [(t1_ns, None)]):
        a, b = max(t, t0_ns), min(t_next, t1_ns)
        if b > a:
            total += v * (b - a)
            held += b - a
    return total / held if held else None


def read(ctx):
    tl = common.load_module(os.path.join(ctx["cell"].dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    busy = counter_mean("serving::slots_busy", *tl.window_ns(ctx))
    if busy is None:
        return None
    return 100.0 * busy / ctx["res"]["slots"]

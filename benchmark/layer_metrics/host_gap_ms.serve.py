"""The time the device has nothing queued because of the host, per decode
iteration: from the end of one `generation::decode_fetch` (the step's
tokens are on the host) to the end of the next `generation::decode` (the
next step is enqueued), less any `generation::prefill` / `::prefill_fetch`
between (an admission is device work). Median over the iterations of the
window that lie outside the device-traced interval, where the profiler's
python tracer does not slow the loop.

Also the helpers the other readers of the program's host timeline load
from here: `window_ns`, `traced_ns`, `named`."""
import statistics
import time


def window_ns(ctx):
    """The measured window on perf_counter_ns. A serving kind gives it on
    time.monotonic: brought over by the offset between the two clocks,
    read here (both run at the same rate)."""
    offset = time.perf_counter() - time.monotonic()
    w0, w1 = ctx["res"]["window"]
    return (w0 + offset) * 1e9, (w1 + offset) * 1e9


def traced_ns(ctx):
    """The device-traced interval on perf_counter_ns (first to last
    device event, by the one-mark clock offset)."""
    tr = ctx["trace"]
    return tr.t0 + ctx["clock_offset_ns"], tr.t1 + ctx["clock_offset_ns"]


def named(ctx, *names):
    """The host spans of these names as (start_ns, end_ns, name), by
    start."""
    return sorted((s, e, n) for n, s, e in ctx["spans"].host if n in names)


def read(ctx):
    w0, w1 = window_ns(ctx)
    t0, t1 = traced_ns(ctx)
    evs = named(ctx, "generation::decode", "generation::decode_fetch",
                "generation::prefill", "generation::prefill_fetch")
    gaps, fetch_end, admitted = [], None, 0.0
    for s, e, n in evs:
        if n == "generation::decode_fetch":
            fetch_end, admitted = e, 0.0
        elif n.startswith("generation::prefill"):
            admitted += e - s
        elif fetch_end is not None:  # the next generation::decode
            if fetch_end >= w0 and e <= w1 and (e <= t0 or fetch_end >= t1):
                gaps.append(e - fetch_end - admitted)
            fetch_end = None
    return statistics.median(gaps) / 1e6 if gaps else None

"""Profiler.

Reference parity: paddle/fluid/platform/profiler.h (RAII RecordEvent :126,
EnableProfiler/DisableProfiler :208, chrome-trace export via
device_tracer.cc + profiler.proto) and python/paddle/fluid/profiler.py
context managers.

TPU-native: host-side RAII events feed a chrome-trace JSON directly;
device timelines come from jax.profiler (XPlane/perfetto) started and
stopped by the same switch — start_profiler/stop_profiler wrap both so
one API yields the merged picture the reference's CUPTI tracer gave.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time

__all__ = [
    "RecordEvent",
    "record_event",
    "enabled",
    "add_span",
    "record_counter",
    "counter_samples",
    "start_profiler",
    "stop_profiler",
    "profiler",
    "reset_profiler",
    "export_chrome_tracing",
    "bump_counter",
    "counters",
    "reset_counters",
    "device_trace_dir",
    "host_events",
]

_state = threading.local()
_events = []
# timestamped counter samples (chrome ph "C"), on the spans' clock but in
# a list of their own: host_events() readers index ev["dur"] on every
# event, and a sample has none
_samples = []
_events_lock = threading.Lock()
_enabled = [False]
_device_trace_dir = [None]
# survives stop_profiler so monitor.export_merged_chrome_trace can find
# the device-side files the run just wrote
_last_device_trace_dir = [None]


def device_trace_dir():
    """Directory of the most recent jax device trace (None if the run
    never started one — e.g. state='CPU' profiling)."""
    return _last_device_trace_dir[0]

# -- dispatch counters --------------------------------------------------------
# Always-on monotonic counters (unlike timed events, which only record while
# the profiler is enabled): the executor's plan-cache hit/miss, jit-cache
# hit/miss, and donation accounting are cheap integer bumps that tests and
# the benchmark read directly — the role of the reference's STAT_* registry
# (platform/monitor.h) rather than the timeline.
_counters: dict[str, int] = {}
_counters_lock = threading.Lock()


def bump_counter(name: str, n: int = 1) -> None:
    """Increment a named monotonic counter."""
    with _counters_lock:
        _counters[name] = _counters.get(name, 0) + n


def counters() -> dict:
    """Snapshot of all counters."""
    with _counters_lock:
        return dict(_counters)


def reset_counters() -> None:
    with _counters_lock:
        _counters.clear()


def _now_us():
    return time.perf_counter_ns() / 1e3


def _emit_span(name, ts_us, dur_us):
    ev = {
        "name": name,
        "ph": "X",
        "ts": ts_us,
        "dur": dur_us,
        "pid": os.getpid(),
        "tid": threading.get_ident() % 100000,
    }
    with _events_lock:
        _events.append(ev)


class RecordEvent:
    """RAII named range (platform/profiler.h:126). Usable as context
    manager or begin()/end() pair."""

    def __init__(self, name):
        self.name = name
        self._begin = None
        self._began_enabled = False

    def begin(self):
        # capture enabled-state NOW: the span's fate is decided here, so
        # (a) a span in flight when stop_profiler() lands (the executor's
        # last dispatch, a dataloader wait) is still recorded — losing
        # boundary spans silently skews stop-adjacent aggregates — and
        # (b) the disabled path never touches the clock: spans ride every
        # dispatch hot path always-on, so the off cost must be a boolean
        self._began_enabled = _enabled[0]
        if self._began_enabled:
            self._begin = _now_us()
        return self

    def end(self):
        if not self._began_enabled or self._begin is None:
            return
        _emit_span(self.name, self._begin, _now_us() - self._begin)
        self._begin = None

    def __enter__(self):
        return self.begin()

    def __exit__(self, *exc):
        self.end()
        return False


@contextlib.contextmanager
def record_event(name):
    with RecordEvent(name):
        yield


def enabled() -> bool:
    """Whether spans and counter samples are being kept: lets a caller
    skip computing a value it would only hand to :func:`record_counter`."""
    return _enabled[0]


def add_span(name, t0_ns, t1_ns):
    """Record a span from two ``time.perf_counter_ns()`` reads the caller
    took anyway (a loop that keeps its own phase split reads the clock
    once per boundary and hands each pair here). Same event list and
    clock as :class:`RecordEvent`; one boolean when the profiler is off."""
    if _enabled[0]:
        _emit_span(name, t0_ns / 1e3, (t1_ns - t0_ns) / 1e3)


class timed_span:
    """One phase of a call, timed whatever the profiler's state: two
    ``perf_counter_ns`` reads that make the span (while the profiler is
    on) and an entry ``(name, start_ns, ns)`` of ``into``, the caller's
    always-on list of its call's innermost phases (where it keeps one:
    the stall records of ``monitor/flight_recorder.py``)."""

    __slots__ = ("name", "into", "t0")

    def __init__(self, name, into=None):
        self.name, self.into = name, into

    def __enter__(self):
        self.t0 = time.perf_counter_ns()

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        add_span(self.name, self.t0, t1)
        if self.into is not None:
            self.into.append((self.name, self.t0, t1 - self.t0))
        return False


def record_counter(name, value):
    """Append one timestamped sample of a quantity the caller owns (live
    slots, queue depth) to the timeline: a chrome counter event
    (``ph: "C"``) on the spans' clock. One boolean when the profiler is
    off. Not to be confused with :func:`bump_counter`'s always-on
    monotonic counts, which carry no time."""
    if not _enabled[0]:
        return
    ev = {
        "name": name,
        "ph": "C",
        "ts": _now_us(),
        "pid": os.getpid(),
        "tid": threading.get_ident() % 100000,
        "args": {"value": value},
    }
    with _events_lock:
        _samples.append(ev)


def counter_samples():
    """Snapshot of the :func:`record_counter` samples, in time order per
    thread. Kept out of :func:`host_events` (a sample has no ``dur``)."""
    with _events_lock:
        return list(_samples)


def _note_double_start(**fields):
    bump_counter("profiler::double_start")
    try:
        from ..monitor import flight_recorder as _flight

        _flight.record_event("profiler_double_start", **fields)
    except Exception:
        pass


def start_profiler(state="All", tracer_option="Default", trace_dir=None):
    """EnableProfiler equivalent. state: CPU | GPU | All (accepted for
    compat; device tracing starts whenever state != CPU).

    Idempotent under a live trace: a second start used to let
    ``jax.profiler.start_trace`` raise out of the training loop (and the
    blanket except then wiped the live dir, orphaning the first trace so
    ``stop_profiler`` could never close it). Now a double start is a
    no-op flagged with a ``profiler_double_start`` flight event +
    ``profiler::double_start`` counter, and the original trace keeps its
    owner."""
    _enabled[0] = True
    if state == "CPU":
        return
    import jax

    if _device_trace_dir[0] is not None:
        _note_double_start(trace_dir=_device_trace_dir[0])
        return
    d = trace_dir or "/tmp/paddle_tpu_trace"
    os.makedirs(d, exist_ok=True)
    try:
        jax.profiler.start_trace(d)
        _device_trace_dir[0] = d
        _last_device_trace_dir[0] = d
    except RuntimeError:
        # a trace this module does not own is live (e.g. opprof's replay
        # trace, or user code driving jax.profiler directly): same no-op
        # contract, and never raise out of the training loop
        _note_double_start(trace_dir=d, owner="external")
    except Exception:
        _device_trace_dir[0] = None  # device tracing unsupported


def stop_profiler(sorted_key=None, profile_path=None, file=None):
    """DisableProfiler equivalent; writes chrome trace to profile_path.

    When ``sorted_key`` is given, prints the per-event aggregate table the
    reference's DisableProfiler emits (platform/profiler.h:208 /
    python/paddle/fluid/profiler.py) — Calls / Total / Min / Max / Ave /
    Ratio per event name, sorted by the requested key.
    """
    _enabled[0] = False
    if _device_trace_dir[0] is not None:
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        _device_trace_dir[0] = None
    if profile_path:
        export_chrome_tracing(profile_path)
    if sorted_key is not None:
        print_summary(sorted_key=sorted_key, file=file)


def summary_records():
    """Aggregate collected events: name -> dict(calls,total,min,max,ave) in ms."""
    with _events_lock:
        evs = list(_events)
    agg = {}
    for ev in evs:
        rec = agg.setdefault(
            ev["name"], {"calls": 0, "total": 0.0, "min": float("inf"), "max": 0.0}
        )
        dur_ms = ev["dur"] / 1e3
        rec["calls"] += 1
        rec["total"] += dur_ms
        rec["min"] = min(rec["min"], dur_ms)
        rec["max"] = max(rec["max"], dur_ms)
    for rec in agg.values():
        rec["ave"] = rec["total"] / rec["calls"]
    return agg


_SORT_KEYS = {
    "default": None,
    "calls": "calls",
    "total": "total",
    "max": "max",
    "min": "min",
    "ave": "ave",
}


def print_summary(sorted_key="total", file=None):
    """Reference-style event summary table (profiler.py print_profiler)."""
    if sorted_key not in _SORT_KEYS:
        raise ValueError(
            f"sorted_key must be one of {sorted(_SORT_KEYS)}, got {sorted_key!r}"
        )
    agg = summary_records()
    if not agg:
        print("No profiler events recorded.", file=file)
        # counters are always-on (no start_profiler needed): still show them
        _print_counters(file)
        return
    grand_total = sum(r["total"] for r in agg.values()) or 1.0
    key = _SORT_KEYS[sorted_key]
    # "min" sorts ascending (reference profiler.py: the cheapest events
    # lead); every other key leads with the most expensive/most called
    ascending = key == "min"
    items = sorted(
        agg.items(), key=(lambda kv: kv[1][key]) if key else (lambda kv: kv[0]),
        reverse=key is not None and not ascending,
    )
    name_w = max(10, min(50, max(len(n) for n in agg)))
    header = (
        f"{'Event':<{name_w}}  {'Calls':>8}  {'Total(ms)':>12}  "
        f"{'Min(ms)':>10}  {'Max(ms)':>10}  {'Ave(ms)':>10}  {'Ratio':>7}"
    )
    bar = "-" * len(header)
    print("\n------------------------->     Profiling Report     "
          "<-------------------------\n", file=file)
    order = "ascending" if ascending else "descending"
    print(f"Sorted by {sorted_key} in {order} order"
          if key else "Sorted by event name", file=file)
    print(bar, file=file)
    print(header, file=file)
    print(bar, file=file)
    for name, r in items:
        print(
            f"{name[:name_w]:<{name_w}}  {r['calls']:>8}  {r['total']:>12.4f}  "
            f"{r['min']:>10.4f}  {r['max']:>10.4f}  {r['ave']:>10.4f}  "
            f"{r['total'] / grand_total:>7.4f}",
            file=file,
        )
    print(bar, file=file)
    _print_counters(file, name_w, footer_bar=bar)


def _print_counters(file=None, name_w=40, footer_bar=None):
    snap = counters()
    if not snap:
        return
    print("Counters:", file=file)
    for name in sorted(snap):
        print(f"  {name:<{name_w}}  {snap[name]:>10}", file=file)
    if footer_bar:
        print(footer_bar, file=file)


def host_events():
    """Snapshot of the collected host spans (chrome-trace dict events)."""
    with _events_lock:
        return list(_events)


def reset_profiler():
    with _events_lock:
        _events.clear()
        _samples.clear()


def export_chrome_tracing(path):
    """Write collected host events and counter samples as a
    chrome://tracing JSON file."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with _events_lock:
        trace = {"traceEvents": list(_events) + list(_samples)}
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=None):
    """fluid.profiler.profiler context manager."""
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)

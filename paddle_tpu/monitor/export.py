"""Telemetry exporters: Prometheus text dump + merged chrome trace.

The reference exposed its StatRegistry through VLOG lines and its
profiler through a chrome trace built from profiler.proto
(device_tracer.cc GenProfile); the two never met in one artifact. Here
both exporters walk the same registry/profiler state:

- :func:`export_prometheus` — text exposition format (the de-facto
  fleet-metrics wire format) over every registered counter/gauge/
  histogram plus the profiler's always-on dispatch counters — including
  the utilization-accounting series (``monitor/<name>/mfu``,
  ``monitor/<name>/hbm_bw_util``, ``cost/<label>/*`` program cost
  gauges, ``cost/executed_*`` ledgers) the cost model feeds.
- :func:`export_merged_chrome_trace` — ONE chrome-trace JSON holding the
  host-side RecordEvent spans and the jax device trace (the
  ``*.trace.json.gz`` files jax.profiler writes), so host dispatch gaps
  line up against device kernel occupancy in the same timeline view.
"""
from __future__ import annotations

import glob
import gzip
import json
import math
import os
import re
import time

from .. import profiler
from . import registry as _reg

__all__ = ["export_prometheus", "export_merged_chrome_trace",
           "prometheus_text", "PROMETHEUS_CONTENT_TYPE"]

# the exposition format's registered media type — scrapers key parsing
# off it, so every HTTP surface serving prometheus_text() (the debug
# server's /metrics) must send exactly this
PROMETHEUS_CONTENT_TYPE = "text/plain; version=0.0.4"

# ':' is legal in prometheus names but reserved for recording rules by
# convention — sanitize it away along with '/' and '::'
_NAME_RE = re.compile(r"[^a-zA-Z0-9_]")


def _prom_name(name: str) -> str:
    """Sanitize a registry name into a prometheus metric name."""
    n = _NAME_RE.sub("_", name)
    if n and n[0].isdigit():
        n = "_" + n
    return n


def _escape_help(s: str) -> str:
    """Escape a # HELP docstring per the exposition format: backslash
    and newline are the two characters with wire meaning there — an
    unescaped newline would split the help text into a garbage sample
    line that kills the whole scrape."""
    return s.replace("\\", "\\\\").replace("\n", "\\n")


def _fmt(v) -> str:
    # the exposition format defines +Inf/-Inf/NaN literals — a single
    # inf loss-scale sentinel must not crash every later export
    if isinstance(v, float):
        if math.isnan(v):
            return "NaN"
        if math.isinf(v):
            return "+Inf" if v > 0 else "-Inf"
        if v != int(v):
            return repr(v)
    return str(int(v))


def _histogram_lines(lines, pname, snap, sel=""):
    """Emit one histogram series (bucket/sum/count). ``sel`` is a
    pre-escaped selector body (``k="v",...`` from format_labels) for a
    labeled child, empty for the bare family."""
    pre = f"{sel}," if sel else ""
    acc = 0
    for le, c in zip(snap["bounds"] + ["+Inf"], snap["buckets"]):
        acc += c
        le_s = le if isinstance(le, str) else repr(float(le))
        lines.append(f'{pname}_bucket{{{pre}le="{le_s}"}} {acc}')
    suffix = f"{{{sel}}}" if sel else ""
    lines.append(f"{pname}_sum{suffix} {_fmt(snap['sum'])}")
    lines.append(f"{pname}_count{suffix} {snap['count']}")


def prometheus_text() -> str:
    """Render the registry + profiler counters in the Prometheus text
    exposition format (one # TYPE line per family, # HELP when the
    metric carries help text).

    Labeled families emit every child series with its label selector
    AND the bare parent series; for counters/histograms the parent is
    the exact aggregate over labels (child updates propagate up in the
    registry), so scrapers that ignore labels keep reading totals.

    Name-collision safety: ``_prom_name`` is lossy ('/' and ':' both
    become '_'), so two distinct registry names can sanitize to the same
    series — emitting both would silently corrupt whichever the scraper
    keeps. That is an error here, naming both originals.
    """
    lines = []
    # sanitized -> source-qualified origin: names are unique within each
    # source, so ANY repeat claim is a duplicate family — including the
    # same raw name living in both the registry and the profiler
    # counters (two '# TYPE x' blocks kill the scrape just as dead as a
    # sanitization clash)
    seen: dict[str, str] = {}

    def _claim(pname, origin):
        prior = seen.get(pname)
        if prior is not None:
            raise ValueError(
                f"prometheus name collision: {origin} and {prior} both "
                f"emit the series {pname!r}; rename one metric")
        seen[pname] = origin

    for name, m in _reg.all_metrics().items():
        pname = _prom_name(name)
        _claim(pname, f"registry metric {name!r}")
        # one snapshot() = one lock acquisition: buckets/sum/count come
        # from the same instant, so a concurrent observe() can never
        # yield a dump where _count disagrees with the +Inf bucket
        snap = m.snapshot()
        if m.help:
            lines.append(f"# HELP {pname} {_escape_help(m.help)}")
        if snap["kind"] == "histogram":
            lines.append(f"# TYPE {pname} histogram")
            _histogram_lines(lines, pname, snap)
            for sel, sub in (snap.get("series") or {}).items():
                _histogram_lines(lines, pname, sub, sel)
        else:
            lines.append(f"# TYPE {pname} {snap['kind']}")
            lines.append(f"{pname} {_fmt(snap['value'])}")
            for sel, sub in (snap.get("series") or {}).items():
                lines.append(f"{pname}{{{sel}}} {_fmt(sub['value'])}")
    # the profiler's always-on dispatch counters live outside the
    # registry (PR 1 predates it); export them under the same roof —
    # collisions with registry names are just as fatal for the scraper
    for name, v in sorted(profiler.counters().items()):
        pname = _prom_name(name)
        _claim(pname, f"profiler counter {name!r}")
        lines.append(f"# TYPE {pname} counter")
        lines.append(f"{pname} {_fmt(v)}")
    return "\n".join(lines) + "\n"


def export_prometheus(path=None) -> str:
    """Write (optional) and return the Prometheus text dump."""
    text = prometheus_text()
    if path:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    return text


def _device_trace_events(trace_dir):
    """traceEvents from the jax device trace under ``trace_dir``.

    jax.profiler.start_trace writes TensorBoard-layout runs:
    ``<dir>/plugins/profile/<run>/<host>.trace.json.gz`` — each already a
    chrome-trace JSON. Collect every run's events; missing/partial files
    are skipped (the tracer may be unsupported on this backend).
    """
    events = []
    if not trace_dir:
        return events
    pattern = os.path.join(trace_dir, "**", "*.trace.json.gz")
    for fn in sorted(glob.glob(pattern, recursive=True)):
        try:
            with gzip.open(fn, "rt") as f:
                trace = json.load(f)
        except Exception:
            continue
        events.extend(trace.get("traceEvents", []))
    return events


def _align_clock_bases(host, device):
    """Shift device events onto the host span clock.

    Host spans stamp time.perf_counter_ns (arbitrary monotonic epoch);
    the XLA profiler stamps its own base — merged raw, the two tracks
    land as disjoint clusters an enormous offset apart. Both recordings
    start at (approximately) the same instant — start_profiler() starts
    the device trace — so anchoring earliest-to-earliest puts host
    dispatch gaps against device kernel occupancy to within the
    start_trace call latency. Returns the device events shifted in
    place; events without a ts (metadata) pass through untouched.
    """
    host_ts = [e["ts"] for e in host if "ts" in e]
    dev_ts = [e["ts"] for e in device if "ts" in e]
    if not host_ts or not dev_ts:
        return device
    offset = min(host_ts) - min(dev_ts)
    for e in device:
        if "ts" in e:
            e["ts"] = e["ts"] + offset
    return device


def _retained_trace_events(host):
    """Retained per-request traces (monitor.tracing) as chrome events,
    one synthetic thread per trace, re-based onto the host span clock.

    Trace spans stamp epoch time; host spans stamp perf_counter_ns/1e3.
    Unlike the device trace, a retained trace does NOT start when the
    recording starts (a p99 outlier may be retained hours in), so the
    earliest-to-earliest anchoring of ``_align_clock_bases`` would slide
    it to the front of the profile. Both clocks are readable NOW, so one
    paired sample gives the exact offset instead.
    """
    from . import tracing as _tracing

    st = _tracing.store()
    events = []
    for row in st.summaries():
        payload = st.get(row["trace_id"])
        if payload is not None:
            events.extend(_tracing.chrome_events(payload))
    if not host:
        return events  # no host track: epoch timestamps stand alone
    offset_us = time.perf_counter_ns() / 1e3 - time.time() * 1e6
    for e in events:
        if "ts" in e:
            e["ts"] = e["ts"] + offset_us
    return events


def export_merged_chrome_trace(path, device_trace_dir=None) -> str:
    """Write host RecordEvent spans + jax device trace + retained
    request/step traces as one chrome://tracing JSON (device and trace
    clocks re-based onto the host track — see _align_clock_bases).
    ``device_trace_dir`` defaults to the directory of the most recent
    device trace (profiler.device_trace_dir())."""
    if device_trace_dir is None:
        device_trace_dir = profiler.device_trace_dir()
    host = profiler.host_events()
    # label the host track so the merged view reads unambiguously
    events = [{"name": "process_name", "ph": "M", "pid": os.getpid(),
               "args": {"name": "paddle_tpu host"}}]
    events.extend(host)
    events.extend(profiler.counter_samples())
    events.extend(_align_clock_bases(
        host, _device_trace_events(device_trace_dir)))
    # the tail-sampled traces ride along: a p99 outlier's span tree
    # lands next to the host/device timeline it happened inside
    events.extend(_retained_trace_events(host))
    # goodput phase track (monitor.goodput): same perf_counter clock
    # family as the host spans, so no re-basing — a checkpoint stall or
    # lost-work replay reads directly against dispatch/kernel occupancy
    from . import goodput as _goodput

    events.extend(_goodput.chrome_events())
    # per-op replay tracks (monitor.opprof): one synthetic thread per
    # stored profile, ops laid end-to-end at measured durations —
    # relative layout, so durations/shares/order are the signal, not
    # absolute alignment against the host clock
    from . import opprof as _opprof

    events.extend(_opprof.chrome_events())
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return path

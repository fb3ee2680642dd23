"""Metrics registry: counters, gauges, bucketed histograms.

Reference parity: paddle/fluid/platform/monitor.h — the STAT_INT /
STAT_FLOAT registry (DEFINE_INT_STATUS / StatRegistry::Instance) that
every subsystem bumps and the exporters walk. The reference keys stats
by string name in a global singleton; so does this module, guarded by
one lock (stat updates are rare relative to the work they measure).

TPU-native additions the reference's registry never needed:
- HBM gauges fed from the PJRT arena counters
  (``jax.local_devices()[i].memory_stats()``) — the reference polled its
  own allocator, XLA owns ours.
- jax.monitoring listeners: XLA compile/retrace events arrive as named
  monitoring events; they land here as counters + duration histograms so
  a retrace storm is visible in the same dump as everything else.
"""
from __future__ import annotations

import bisect
import threading

__all__ = [
    "Counter", "Gauge", "Histogram",
    "counter", "gauge", "histogram",
    "STAT_INT", "STAT_FLOAT", "stat_add", "stat_reset",
    "registry_snapshot", "reset_registry", "all_metrics",
    "histogram_quantile", "merge_histogram_snapshots",
    "format_labels",
    "collect_hbm_gauges", "hbm_watermark_bytes",
    "install_jax_listeners",
]

_lock = threading.Lock()
_metrics: dict[str, "_Metric"] = {}

# default latency-ish buckets (ms): sub-ms to minutes, roughly 4x apart
DEFAULT_BUCKETS = (0.1, 0.5, 1.0, 5.0, 10.0, 50.0, 100.0, 500.0,
                   1000.0, 5000.0, 30000.0)

# label value every dimension collapses to once a family hits
# FLAGS_metrics_max_series — one shared series absorbs the overflow so
# a hostile/unbounded dimension can never grow memory past the bound
OVERFLOW_LABEL_VALUE = "other"


def _escape_label_value(v) -> str:
    """Escape a label VALUE per the prometheus exposition format:
    backslash, double-quote and newline are the three characters with
    wire meaning inside a quoted label value."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def format_labels(labels) -> str:
    """Canonical selector body for one label set — sorted keys, escaped
    values: ``k="v",k2="v2"``. This exact string keys the ``series``
    dict in snapshots and is what :func:`prometheus_text` emits inside
    ``{}``, so snapshot consumers and scrapers agree on series identity.
    Accepts a dict or an iterable of (key, value) pairs."""
    items = sorted(labels.items() if isinstance(labels, dict) else labels)
    return ",".join(f'{k}="{_escape_label_value(v)}"' for k, v in items)


def _max_series() -> int:
    # lazy flag read: the registry is imported before flags in some
    # entrypoints, and set_flags must apply to live families
    try:
        from ..flags import flag

        return int(flag("metrics_max_series"))
    except Exception:
        return 64


class _Metric:
    kind = "untyped"

    def __init__(self, name, help=""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        # labeled child series (prometheus label semantics), keyed by
        # the sorted ((key, value), ...) tuple. For counters and
        # histograms every child update propagates into the parent, so
        # the bare family stays the exact aggregate over its labels and
        # label-free readers (/statz, /histz merges) see totals.
        self._children: dict = {}
        self._label_keys = None  # fixed by the first labels() call
        self._labels = ()        # ((k, v), ...) — set on children only
        self._parent = None
        self._overflowed = False

    def _new_child(self):
        return type(self)(self.name, help=self.help)

    def labels(self, **dims):
        """Child metric for one label set (``labels(kind="predict",
        tenant="a")``), get-or-create. The family's label KEYS are
        fixed by the first call; a later call with different keys
        raises — mixed key sets would make series identity ambiguous.

        Cardinality is hard-bounded by ``FLAGS_metrics_max_series``:
        once the family holds that many distinct label sets, every NEW
        set collapses into one shared series whose label values are all
        ``"other"`` (recording a single ``metric_series_overflow``
        flight event), so an unbounded dimension — a hostile tenant
        header — costs one series, never unbounded memory."""
        if self._parent is not None:
            raise ValueError(
                f"metric {self.name!r}: labels() called on a labeled "
                "child; call it on the family root")
        if not dims:
            raise ValueError(
                f"metric {self.name!r}: labels() needs at least one "
                "label")
        keys = tuple(sorted(dims))
        key = tuple((k, str(dims[k])) for k in keys)
        first_overflow = False
        with self._lock:
            if self._label_keys is None:
                self._label_keys = keys
            elif keys != self._label_keys:
                raise ValueError(
                    f"metric {self.name!r} labeled with keys "
                    f"{list(self._label_keys)}, got {list(keys)}; a "
                    "family's label keys are fixed by its first use")
            child = self._children.get(key)
            if child is None and len(self._children) >= _max_series():
                key = tuple((k, OVERFLOW_LABEL_VALUE) for k in keys)
                child = self._children.get(key)
                first_overflow = not self._overflowed
                self._overflowed = True
            if child is None:
                child = self._new_child()
                child._parent = self
                child._labels = key
                self._children[key] = child
        if first_overflow:
            try:
                from . import flight_recorder as _flight

                _flight.record_event(
                    "metric_series_overflow", metric=self.name,
                    max_series=_max_series())
            except Exception:
                pass
        return child

    def series(self) -> dict:
        """Live labeled children by selector body (``k="v",...``)."""
        with self._lock:
            children = list(self._children.values())
        return {format_labels(c._labels): c for c in children}

    def _series_snapshots(self) -> dict:
        with self._lock:
            children = list(self._children.values())
        out = {}
        for c in children:
            s = c.snapshot()
            s["labels"] = dict(c._labels)
            out[format_labels(c._labels)] = s
        return out

    def _reset_children(self):
        with self._lock:
            children = list(self._children.values())
        for c in children:
            c._reset()


class Counter(_Metric):
    """Monotonic counter (STAT_INT's common use: only ever added to)."""

    kind = "counter"

    def __init__(self, name, help=""):
        super().__init__(name, help)
        self._value = 0

    def inc(self, n=1):
        with self._lock:
            self._value += n
        if self._parent is not None:
            self._parent.inc(n)

    @property
    def value(self):
        with self._lock:
            return self._value

    def snapshot(self):
        snap = {"kind": self.kind, "value": self.value}
        series = self._series_snapshots()
        if series:
            snap["series"] = series
        return snap

    def _reset(self):
        with self._lock:
            self._value = 0
        self._reset_children()


class Gauge(_Metric):
    """Set-to-current-value stat (HBM in use, queue depth, lr).

    Gauge children do NOT propagate into the parent: "sum of last-set
    values" has no meaning for a set-semantics stat, so the parent and
    each labeled child are independent series."""

    kind = "gauge"

    def __init__(self, name, help=""):
        super().__init__(name, help)
        self._value = 0.0

    def set(self, v):
        with self._lock:
            self._value = v

    def add(self, v):
        with self._lock:
            self._value += v

    @property
    def value(self):
        with self._lock:
            return self._value

    def snapshot(self):
        snap = {"kind": self.kind, "value": self.value}
        series = self._series_snapshots()
        if series:
            snap["series"] = series
        return snap

    def _reset(self):
        with self._lock:
            self._value = 0.0
        self._reset_children()


class Histogram(_Metric):
    """Cumulative bucketed histogram (prometheus semantics: bucket i
    counts observations <= bounds[i]; +Inf bucket is implicit)."""

    kind = "histogram"

    def __init__(self, name, buckets=None, help=""):
        super().__init__(name, help)
        bounds = tuple(sorted(buckets if buckets is not None
                              else DEFAULT_BUCKETS))
        if not bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self.bounds = bounds
        self._counts = [0] * (len(bounds) + 1)  # last = > max bound (+Inf)
        self._sum = 0.0
        self._count = 0

    def _new_child(self):
        # children must share the family's bucket ladder or label-aware
        # merges would mis-bin
        return Histogram(self.name, buckets=self.bounds, help=self.help)

    def observe(self, v):
        v = float(v)
        i = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1
        if self._parent is not None:
            self._parent.observe(v)

    @property
    def count(self):
        with self._lock:
            return self._count

    @property
    def sum(self):
        with self._lock:
            return self._sum

    def bucket_counts(self):
        """Per-bucket (non-cumulative) counts, +Inf bucket last."""
        with self._lock:
            return list(self._counts)

    def cumulative_counts(self):
        """Prometheus-style cumulative counts per le bound, +Inf last."""
        out, acc = [], 0
        with self._lock:
            for c in self._counts:
                acc += c
                out.append(acc)
        return out

    def snapshot(self):
        with self._lock:
            snap = {
                "kind": self.kind, "sum": self._sum, "count": self._count,
                "bounds": list(self.bounds), "buckets": list(self._counts),
            }
        series = self._series_snapshots()
        if series:
            snap["series"] = series
        return snap

    def _reset(self):
        with self._lock:
            self._counts = [0] * (len(self.bounds) + 1)
            self._sum = 0.0
            self._count = 0
        self._reset_children()


def _get(name, cls, **kwargs):
    with _lock:
        m = _metrics.get(name)
        if m is None:
            m = cls(name, **kwargs)
            _metrics[name] = m
        elif not isinstance(m, cls):
            raise TypeError(
                f"metric {name!r} already registered as {m.kind}, "
                f"requested {cls.kind}")
        return m


def counter(name, help="") -> Counter:
    """Get-or-create the named counter."""
    return _get(name, Counter, help=help)


def gauge(name, help="") -> Gauge:
    return _get(name, Gauge, help=help)


def histogram(name, buckets=None, help="") -> Histogram:
    h = _get(name, Histogram, buckets=buckets, help=help)
    # explicit bounds that disagree with the registered metric must fail
    # loudly — silently observing into someone else's buckets corrupts
    # both callers' data (same contract as the kind-collision TypeError)
    if buckets is not None and tuple(sorted(buckets)) != h.bounds:
        raise ValueError(
            f"histogram {name!r} already registered with bounds "
            f"{h.bounds}, requested {tuple(sorted(buckets))}")
    return h


# -- STAT_INT / STAT_FLOAT parity -------------------------------------------
# The reference macros (platform/monitor.h DEFINE_INT_STATUS) define a
# named stat once and bump it anywhere via STAT_ADD/STAT_RESET; both int
# and float stats are gauges with add semantics here.

def STAT_INT(name) -> Gauge:
    """DEFINE_INT_STATUS equivalent: named integer stat (gauge w/ add)."""
    return gauge(f"stat/int/{name}")


def STAT_FLOAT(name) -> Gauge:
    return gauge(f"stat/float/{name}")


def stat_add(name, v=1):
    """STAT_ADD(name, v) — int stat add by name."""
    STAT_INT(name).add(v)


def stat_reset(name):
    """STAT_RESET(name)."""
    STAT_INT(name).set(0)


def histogram_quantile(h: Histogram, q: float):
    """Approximate quantile from the bucketed counts (prometheus
    histogram_quantile semantics: linear interpolation inside the
    matching bucket; observations in the +Inf bucket clamp to the
    largest finite bound). Returns ``None`` on an empty histogram —
    0.0 would be indistinguishable from a real 0ms quantile on a
    merged/fleet view, so callers render the series as absent."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    snap = h.snapshot()
    total = snap["count"]
    if total == 0:
        return None
    target = q * total
    acc, lo = 0, 0.0
    for bound, c in zip(snap["bounds"], snap["buckets"]):
        if c and acc + c >= target:
            return lo + (bound - lo) * (target - acc) / c
        acc += c
        lo = bound
    return float(snap["bounds"][-1])


def merge_histogram_snapshots(snapshots, name="merged") -> Histogram:
    """Merge histogram ``snapshot()`` dicts from several sources (e.g. N
    serving backends' ``/histz`` payloads) into one UNREGISTERED
    :class:`Histogram` whose bucket counts are the elementwise sums —
    feed it to :func:`histogram_quantile` for fleet-wide p50/p99.

    Bucketed histograms merge exactly: summing per-bucket counts over
    backends is identical to having observed every sample into one
    pooled histogram (same bounds), so the router's merged quantiles
    match the single-histogram golden. All snapshots must share the
    same bounds; a mismatch raises rather than silently mis-binning.

    Label-aware: snapshots carrying a ``series`` dict (labeled
    families) get their per-selector child snapshots merged the same
    elementwise way; the merged children hang off the returned
    histogram's :meth:`~_Metric.series` so fleet quantiles exist per
    labeled series too. A series only some sources carry merges over
    the sources that have it.
    """
    snapshots = [s for s in snapshots if s]
    if not snapshots:
        raise ValueError("merge_histogram_snapshots needs >= 1 snapshot")
    bounds = tuple(snapshots[0]["bounds"])
    h = Histogram(name, buckets=bounds)
    counts = [0] * (len(bounds) + 1)
    total, sum_ = 0, 0.0
    for s in snapshots:
        if tuple(s["bounds"]) != bounds:
            raise ValueError(
                f"histogram bounds mismatch: {tuple(s['bounds'])} vs "
                f"{bounds}; backends must share one bucket ladder")
        if len(s["buckets"]) != len(counts):
            raise ValueError(
                f"histogram has {len(s['buckets'])} buckets, expected "
                f"{len(counts)} (bounds + the +Inf bucket)")
        for i, c in enumerate(s["buckets"]):
            counts[i] += int(c)
        total += int(s["count"])
        sum_ += float(s["sum"])
    h._counts = counts
    h._count = total
    h._sum = sum_
    per_series: dict = {}
    for s in snapshots:
        for sub in (s.get("series") or {}).values():
            labels = tuple(sorted((sub.get("labels") or {}).items()))
            per_series.setdefault(labels, []).append(sub)
    for labels, subs in per_series.items():
        child = merge_histogram_snapshots(subs, name=name)
        # static merged data: labeled for series(), but no parent link —
        # nothing observes into a merge result
        child._labels = labels
        h._children[labels] = child
    return h


def all_metrics() -> dict:
    """Live metric objects by name (ordered by registration)."""
    with _lock:
        return dict(_metrics)


def registry_snapshot() -> dict:
    """Plain-data snapshot of every metric (JSON-safe)."""
    return {name: m.snapshot() for name, m in all_metrics().items()}


def reset_registry(unregister=False):
    """Zero every metric; ``unregister=True`` also drops the definitions
    (tests use this so registrations can't leak across files)."""
    with _lock:
        if unregister:
            _metrics.clear()
            return
        metrics = list(_metrics.values())
    for m in metrics:
        m._reset()


# -- HBM gauges --------------------------------------------------------------

_HBM_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
             "largest_free_block_bytes")


def collect_hbm_gauges(devices=None) -> dict:
    """Populate per-device HBM gauges from PJRT arena counters.

    Sets ``hbm/device<i>/<key>`` gauges for every counter the backend
    publishes and returns the values set. Backends that publish none
    (the CPU) contribute nothing rather than zeros —
    a zero gauge would read as "no memory in use", which is a lie.
    ``devices`` is injectable for tests; defaults to jax.local_devices().
    """
    if devices is None:
        import jax

        devices = jax.local_devices()
    out = {}
    for i, d in enumerate(devices):
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        for key in _HBM_KEYS:
            if key in stats:
                name = f"hbm/device{i}/{key}"
                gauge(name).set(int(stats[key]))
                out[name] = int(stats[key])
    return out


def hbm_watermark_bytes(devices=None) -> int:
    """Max peak_bytes_in_use across local devices (0 if unpublished)."""
    vals = collect_hbm_gauges(devices)
    peaks = [v for k, v in vals.items() if k.endswith("peak_bytes_in_use")]
    return max(peaks) if peaks else 0


# -- jax.monitoring listeners ------------------------------------------------

_jax_listeners_installed = [False]


def install_jax_listeners() -> bool:
    """Route jax.monitoring events (XLA compile, cache hits, retraces)
    into the registry: every event bumps ``jax/<event>``; duration events
    also observe ``jax/<event>/duration_ms``. Idempotent; returns whether
    the listeners are active (False on a jax without jax.monitoring).

    jax emits keys like ``/jax/core/compile`` — each fresh compile of a
    jitted function is one event, so a retrace storm (unstable shapes or
    hash-unstable static args) shows up as this counter racing the step
    counter.
    """
    if _jax_listeners_installed[0]:
        return True
    try:
        from jax import monitoring as jmon
    except Exception:
        return False

    def _flight_record(event, **fields):
        # XLA compile events land in the flight recorder too: a dump of a
        # hung/dying run shows whether a retrace storm preceded the stall
        # (lazy import: flight_recorder must stay importable first)
        try:
            from . import flight_recorder as _flight

            _flight.record_event("xla_event", event=event, **fields)
        except Exception:
            pass

    def _on_event(event, **kwargs):
        counter(f"jax/{event.lstrip('/')}").inc()
        _flight_record(event)

    def _on_duration(event, duration_secs, **kwargs):
        counter(f"jax/{event.lstrip('/')}").inc()
        histogram(f"jax/{event.lstrip('/')}/duration_ms").observe(
            duration_secs * 1e3)
        _flight_record(event, duration_ms=round(duration_secs * 1e3, 3))

    # mark installed as soon as the FIRST registration lands: there is no
    # public unregister, so a retry after a partial failure must never
    # re-register _on_event (duplicate listeners would double-count every
    # compile). A jax missing the duration API degrades to counters-only.
    try:
        jmon.register_event_listener(_on_event)
    except Exception:
        return False
    _jax_listeners_installed[0] = True
    try:
        jmon.register_event_duration_secs_listener(_on_duration)
    except Exception:
        pass
    return True

"""Hardware-utilization accounting: XLA cost models + device peak tables.

The stack's north star is "as fast as the hardware allows", but tokens/sec
alone cannot say how fast that *is*: a 10% regression hides inside run-to-
run noise unless the number is normalized by what the compiled program
*should* cost. This module owns both halves of that ratio:

- **What a program costs** — on every compile (executor RunPlan jit,
  framework/jit.py compiled steps, hapi fit) the caller captures XLA's own
  ``cost_analysis()`` (FLOPs, bytes accessed — the numbers the compiler
  schedules against, not a formula that drifts from the implementation)
  and ``memory_analysis()`` (argument/output/temp sizes, i.e. the
  program's HBM footprint) into a :class:`CostRecord`, keyed by the same
  identity the plan/jit caches use.
- **What the hardware offers** — a per-device-kind peak table (MXU
  FLOPs/s, HBM bytes/s, ICI bytes/s), overridable via
  ``FLAGS_device_peaks`` for new silicon or derated SKUs.

Dividing the two gives MFU (the Gemma-on-TPU comparison papers' headline
denominator), HBM bandwidth utilization, and a roofline classification
(compute- vs memory-bound) per step — surfaced in the TrainingMonitor
line, the Prometheus dump, and the ``/costz`` debug endpoint; the cluster
aggregator (:mod:`monitor.cluster`) ships them cross-rank.
"""
from __future__ import annotations

import threading
import time

from ..flags import flag
from . import registry as _reg

__all__ = [
    "CostRecord",
    "analyze_cost", "analyze_memory", "flops_and_bytes",
    "capture", "note_run",
    "cost_records", "latest_record", "reset_cost_records",
    "device_peaks", "mfu", "hbm_bw_util", "roofline_class",
    "costz_payload",
]

# ---------------------------------------------------------------------------
# XLA analysis normalization (the ONE guard for every call site)
# ---------------------------------------------------------------------------


def analyze_cost(stage) -> dict | None:
    """``stage.cost_analysis()`` normalized to one plain dict, or None.

    ``stage`` is a jax ``Lowered`` or ``Compiled`` (both expose the
    client-side HLO cost analysis). Backends differ: some return a dict,
    some a one-element list of dicts (per-partition), some ``None`` or an
    empty mapping, and some raise — every caller used to hand-roll this
    guard; now there is exactly one.
    """
    if stage is None:
        return None
    try:
        ca = stage.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict) or not ca:
        return None
    return dict(ca)


_MEM_ATTRS = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")


def analyze_memory(compiled) -> dict | None:
    """``compiled.memory_analysis()`` as a plain dict, or None.

    Only a ``Compiled`` carries the backend buffer-assignment sizes; a
    backend without the API (or one returning a partial stats object)
    degrades to None / missing keys rather than raising.
    """
    if compiled is None:
        return None
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for attr in _MEM_ATTRS:
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr] = int(v)
    return out or None


def flops_and_bytes(stage):
    """(flops, bytes_accessed) of a Lowered/Compiled, or None when the
    backend publishes no cost analysis — the shared shape of the old
    ad-hoc call sites (hapi layer costing, the HLO dump tools)."""
    ca = analyze_cost(stage)
    if ca is None:
        return None
    return (float(ca.get("flops", 0.0) or 0.0),
            float(ca.get("bytes accessed", 0.0) or 0.0))


# ---------------------------------------------------------------------------
# CostRecord registry
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_records: dict = {}          # key -> CostRecord (insertion-ordered, LRU-ish)
_RECORDS_LIMIT = 256         # long-lived processes fed many programs


class CostRecord:
    """One compiled program's static cost sheet.

    ``flops``/``bytes_accessed`` come from XLA's HLO cost analysis of the
    whole module (one training step = one record); the ``*_bytes`` memory
    fields from the backend buffer assignment. ``runs`` counts dispatches
    (bumped by :func:`note_run`), so ``flops * runs`` is the executed-work
    ledger the MFU window math consumes via the registry counters.
    """

    __slots__ = ("key", "label", "flops", "bytes_accessed",
                 "argument_bytes", "output_bytes", "temp_bytes",
                 "alias_bytes", "peak_hbm_bytes", "partial", "meta",
                 "runs", "created_t", "predicted_peak_bytes",
                 "plan_accuracy", "predicted_op_us", "measured_op_us",
                 "time_accuracy")

    def __init__(self, key, label, cost, mem, meta):
        self.key = key
        self.label = label
        self.flops = float((cost or {}).get("flops", 0.0) or 0.0)
        self.bytes_accessed = float(
            (cost or {}).get("bytes accessed", 0.0) or 0.0)
        mem = mem or {}
        self.argument_bytes = int(mem.get("argument_size_in_bytes", 0))
        self.output_bytes = int(mem.get("output_size_in_bytes", 0))
        self.temp_bytes = int(mem.get("temp_size_in_bytes", 0))
        # donated input/output pairs share one buffer; alias_bytes is
        # that shared size, so arg+out+temp-alias is the true resident
        # footprint (the planner's actual-side comparison, analysis/
        # memory.note_actual)
        self.alias_bytes = int(mem.get("alias_size_in_bytes", 0))
        # the program's live-HBM high-water mark: inputs + outputs + XLA
        # scratch (aliased pairs count on BOTH sides here — the historic
        # gauge semantics; subtract alias_bytes for the true resident
        # footprint, as note_actual does)
        self.peak_hbm_bytes = (self.argument_bytes + self.output_bytes
                               + self.temp_bytes)
        self.partial = cost is None or mem is None
        self.meta = dict(meta)
        self.runs = 0
        self.created_t = time.time()
        # closed by analysis.memory.note_actual after the first dispatch
        # of a statically-planned program (predicted peak vs this
        # record's arg+out+temp-alias)
        self.predicted_peak_bytes = None
        self.plan_accuracy = None
        # closed by monitor.opprof.profile_program: calibrated-roofline
        # predicted per-op µs vs the replay-measured total (the time-
        # accuracy analog of plan_accuracy; ratio, 1.0 = perfect)
        self.predicted_op_us = None
        self.measured_op_us = None
        self.time_accuracy = None

    def to_dict(self) -> dict:
        return {
            "key": str(self.key), "label": self.label,
            "flops": self.flops, "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "alias_bytes": self.alias_bytes,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "predicted_peak_bytes": self.predicted_peak_bytes,
            "plan_accuracy": (round(self.plan_accuracy, 4)
                              if self.plan_accuracy is not None else None),
            "predicted_op_us": self.predicted_op_us,
            "measured_op_us": self.measured_op_us,
            "time_accuracy": (round(self.time_accuracy, 4)
                              if self.time_accuracy is not None else None),
            "arithmetic_intensity": (
                self.flops / self.bytes_accessed
                if self.bytes_accessed else 0.0),
            "roofline": roofline_class(self.flops, self.bytes_accessed),
            "partial": self.partial, "runs": self.runs,
            "meta": self.meta,
        }


def capture(label, lowered=None, compiled=None, key=None, **meta):
    """Record one compiled program's cost sheet (idempotent per ``key``).

    Cost comes from ``compiled`` when it publishes an analysis, else from
    ``lowered`` (client-side HLO pass — some backends only implement one);
    memory needs ``compiled``. A backend returning nothing still yields a
    record (``partial=True``, zero FLOPs) so ``/costz`` says "analysis
    unavailable" instead of silently showing no program at all.

    Per-label gauges (``cost/<label>/flops`` etc.) land in the registry so
    the Prometheus dump carries the latest program's static costs.
    """
    if key is None:
        key = label
    cost = analyze_cost(compiled)
    if cost is None:
        cost = analyze_cost(lowered)
    mem = analyze_memory(compiled)
    rec = CostRecord(key, label, cost, mem, meta)
    with _lock:
        _records.pop(key, None)
        _records[key] = rec
        while len(_records) > _RECORDS_LIMIT:
            _records.pop(next(iter(_records)))
    for field in ("flops", "bytes_accessed", "peak_hbm_bytes"):
        _reg.gauge(f"cost/{label}/{field}").set(getattr(rec, field))
    try:
        from . import flight_recorder as _flight

        _flight.record_event(
            "cost_capture", label=label, flops=rec.flops,
            bytes_accessed=rec.bytes_accessed,
            peak_hbm_bytes=rec.peak_hbm_bytes, partial=rec.partial,
            **{k: str(v)[:120] for k, v in meta.items()})
    except Exception:
        pass
    return rec


def note_run(record, n=1):
    """Account ``n`` dispatches of a captured program into the executed-
    work ledger (``cost/executed_flops``, ``cost/executed_bytes``) the
    TrainingMonitor's MFU window math differences. Hot-path cheap: two
    counter adds; a ``None`` record (capture failed/disabled) is free."""
    if record is None:
        return
    record.runs += n
    if record.flops:
        _reg.counter("cost/executed_flops").inc(record.flops * n)
    if record.bytes_accessed:
        _reg.counter("cost/executed_bytes").inc(record.bytes_accessed * n)


def cost_records() -> dict:
    """Live CostRecords by key (insertion order)."""
    with _lock:
        return dict(_records)


def latest_record(label=None):
    """Most recently captured record (optionally filtered by label)."""
    with _lock:
        for rec in reversed(list(_records.values())):
            if label is None or rec.label == label:
                return rec
    return None


def reset_cost_records():
    with _lock:
        _records.clear()


# ---------------------------------------------------------------------------
# Device peak table
# ---------------------------------------------------------------------------

# (device_kind substring match, ordered most-specific first) -> peaks in
# FLOP/s (bf16 dense MXU), HBM bytes/s, ICI bytes/s, and HBM CAPACITY
# bytes per chip. Published per-chip numbers; new silicon or derated
# SKUs override any subset via FLAGS_device_peaks. hbm_bytes is the
# memory-budget denominator the static planner admits against
# (analysis/memory.check_memory_budget, FLAGS_memory_budget_check).
_PEAKS_TABLE = (
    ("v6", {"flops": 918e12, "hbm_bw": 1640e9, "ici_bw": 448e9,
            "hbm_bytes": 32e9}),
    ("v5p", {"flops": 459e12, "hbm_bw": 2765e9, "ici_bw": 600e9,
             "hbm_bytes": 95e9}),
    # the v5e reports device_kind "TPU v5 lite" and
    # memory_stats()["bytes_limit"] == 16,909,336,064 (chip run, PR 21)
    ("v5 lite", {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 200e9,
                 "hbm_bytes": 16.9e9}),
    ("v5e", {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 200e9,
             "hbm_bytes": 16.9e9}),
    ("v5", {"flops": 459e12, "hbm_bw": 2765e9, "ici_bw": 600e9,
            "hbm_bytes": 95e9}),
    ("v4", {"flops": 275e12, "hbm_bw": 1228e9, "ici_bw": 300e9,
            "hbm_bytes": 32e9}),
    ("v3", {"flops": 123e12, "hbm_bw": 900e9, "ici_bw": 140e9,
            "hbm_bytes": 32e9}),
    ("v2", {"flops": 45e12, "hbm_bw": 700e9, "ici_bw": 100e9,
            "hbm_bytes": 16e9}),
)

# The CPU gets NOMINAL peaks (order-of-magnitude host numbers) so the
# utilization plumbing works in the test suite — the payload says so via
# "nominal": true. An accelerator that is not in the table is an error,
# not a default: MFU against made-up peaks is worse than none.
_NOMINAL_PEAKS = {"flops": 1e11, "hbm_bw": 5e10, "ici_bw": 1e10,
                  "hbm_bytes": 8e9}
_PEAK_KEYS = ("flops", "hbm_bw", "ici_bw", "hbm_bytes")

_detected_kind = [None]  # cache: jax backend init is not free
_parse_memo = [None, {}]  # [last raw flag string, its parsed overrides]


def _device_kind() -> str:
    """``device_kind`` of local device 0 (the CPU's reads "cpu"). A
    backend that fails to initialise raises here."""
    if _detected_kind[0] is None:
        import jax

        _detected_kind[0] = str(jax.local_devices()[0].device_kind)
    return _detected_kind[0]


def _parse_peaks_flag(raw: str) -> dict:
    """``FLAGS_device_peaks``: comma-separated ``k=v`` floats over
    {flops, hbm_bw, ici_bw, hbm_bytes} (units: FLOP/s, B/s, B/s, B).
    Unknown keys and unparseable entries are ignored loudly-enough (they
    simply don't override), so a typo degrades to the detected table,
    not a crash."""
    out = {}
    for part in raw.split(","):
        part = part.strip()
        if not part or "=" not in part:
            continue
        k, _, v = part.partition("=")
        k = k.strip().lower()
        if k not in _PEAK_KEYS:
            continue
        try:
            out[k] = float(v)
        except ValueError:
            continue
    return out


def device_peaks(kind=None) -> dict:
    """Peak throughput/capacity sheet for the detected (or given) device
    kind: ``{"kind", "flops", "hbm_bw", "ici_bw", "hbm_bytes",
    "nominal"}`` — the MFU/bandwidth/roofline denominators plus the HBM
    capacity the static memory planner budgets against.
    ``FLAGS_device_peaks`` overrides any subset; an override clears the
    nominal marker (the operator asserted real numbers). The CPU (kind
    "cpu") gets the nominal sheet; any other kind the table does not
    know raises ``ValueError`` naming it, unless the flag supplies all
    four peaks (new silicon)."""
    kind = kind if kind is not None else _device_kind()
    lowered = kind.lower()
    peaks, nominal = None, True
    for sub, vals in _PEAKS_TABLE:
        if sub in lowered:
            peaks, nominal = dict(vals), False
            break
    raw = str(flag("device_peaks"))
    if raw != _parse_memo[0]:  # memo: skip re-parsing per call
        _parse_memo[0], _parse_memo[1] = raw, _parse_peaks_flag(raw)
    override = _parse_memo[1]
    if peaks is None:
        if lowered != "cpu" and not all(k in override for k in _PEAK_KEYS):
            raise ValueError(
                f"no peak numbers for device_kind {kind!r}: add it to "
                "monitor/cost_model.py _PEAKS_TABLE with its source, or "
                "set FLAGS_device_peaks=flops=..,hbm_bw=..,ici_bw=..,"
                "hbm_bytes=..")
        peaks = dict(_NOMINAL_PEAKS)
    if override:
        peaks.update(override)
        nominal = False
    peaks["kind"] = kind
    peaks["nominal"] = nominal
    return peaks


# ---------------------------------------------------------------------------
# Utilization math
# ---------------------------------------------------------------------------


def mfu(flops_per_s, peaks=None) -> float:
    """Model FLOPs utilization: achieved FLOP/s over the chip's peak."""
    peaks = peaks or device_peaks()
    return float(flops_per_s) / peaks["flops"] if peaks["flops"] else 0.0


def hbm_bw_util(bytes_per_s, peaks=None) -> float:
    """Achieved HBM traffic over the chip's peak memory bandwidth."""
    peaks = peaks or device_peaks()
    return float(bytes_per_s) / peaks["hbm_bw"] if peaks["hbm_bw"] else 0.0


def roofline_class(flops, bytes_accessed, peaks=None) -> str:
    """Roofline classification of a program (or a step window): compare
    its arithmetic intensity (FLOPs per HBM byte) against the machine's
    ridge point (peak FLOPs / peak bandwidth). Left of the ridge the
    program cannot reach peak FLOPs no matter how good the schedule —
    it is ``memory-bound``; right of it, ``compute-bound``."""
    if not flops or not bytes_accessed:
        return "unknown"
    peaks = peaks or device_peaks()
    if not peaks["hbm_bw"] or not peaks["flops"]:
        return "unknown"
    ridge = peaks["flops"] / peaks["hbm_bw"]
    return ("compute-bound" if (flops / bytes_accessed) >= ridge
            else "memory-bound")


def costz_payload() -> dict:
    """The ``/costz`` debug-endpoint body: device peaks + every captured
    program's cost sheet + the executed-work ledger."""
    return {
        "device_peaks": device_peaks(),
        "executed_flops": _reg.counter("cost/executed_flops").value,
        "executed_bytes": _reg.counter("cost/executed_bytes").value,
        "records": [rec.to_dict() for rec in cost_records().values()],
    }

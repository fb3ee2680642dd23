"""Per-op device-time attribution (``/profilez``): stamped scopes, trace
folding, and the replay profiler.

The cost model (cost_model.py) predicts FLOPs/bytes per program and the
goodput/SLO planes account wall time — this module closes the loop at the
granularity everything else argues about: **individual Program ops**. Three
legs, matching the TVM-style measured-cost feedback loop (PAPERS.md):

1. **Attribution stamping.** Every op the executor traces gets a stable
   identity ``op.type#<block>/<index>`` (:func:`op_scope_name`) pushed
   through ``jax.named_scope`` (static/executor.py), so XLA HLO location
   metadata and ``jax.profiler`` device traces carry per-op identity.
   :func:`attribute_trace` parses the profiler's emitted
   ``*.trace.json.gz`` files and folds device events back onto stamped
   ops, reporting a **coverage ratio** = stamped device time / total
   device time (on the timelines that carry stamps at all — the python
   tracer's ``$``-prefixed host rows are excluded by construction).

2. **Replay profiler.** :func:`profile_program` re-runs a program
   op-by-op through the REGISTRY kernels: per-op ``jax.jit`` (the jitted
   callable is *named with the stamp*, so even CPU traces — where XLA
   thunks carry no HLO metadata — self-identify as
   ``PjitFunction(matmul#0/3)``), warmup + best-of-N timing behind
   ``block_until_ready`` barriers. Yields µs, share, achieved FLOP/s,
   per-op MFU and roofline class (cost_model peaks), plus the
   **time-accuracy closure**: roofline-predicted µs (from a per-process
   *calibrated* machine model, :func:`calibration`) vs measured µs per op
   and per program, landing on the executor's CostRecord and ``/costz``
   exactly like memplan's ``plan_accuracy``.

3. **Surfaces.** :func:`profilez_payload` backs ``/profilez`` (debug
   server + both serving server kinds, ``?program=``/``?topk=``),
   :func:`top_ops` the ``/statz`` top-K table, the
   ``opprof/op_time_ms`` labeled histogram family lands on ``/metrics``,
   and :func:`chrome_events` appends a per-op track to
   ``export_merged_chrome_trace``.

Accuracy contract: *replay* timings are per-op kernel latencies measured
in isolation (no inter-op fusion, no overlap) — an upper bound on each
op's standalone cost and the right currency for comparing a fused kernel
against the chain it replaced. *Trace attribution* measures ops inside
the real fused program — authoritative for shares, but only as complete
as its coverage ratio. Report both; trust trace shares when coverage
>= 0.9, replay deltas for A/B kernel decisions.

Overhead contract: stamping happens at jax *trace* time only (once per
compile) — the steady-state dispatch path never formats a stamp. Replay
and trace parsing run only on demand.
"""
from __future__ import annotations

import glob
import gzip
import json
import math
import os
import re
import tempfile
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "TIME_ACCURACY_ENVELOPE",
    "op_scope_name",
    "parse_op_scope",
    "load_trace_events",
    "attribute_trace",
    "calibration",
    "predict_op_us",
    "profile_program",
    "record_profile",
    "profiles",
    "latest_profile",
    "reset_profiles",
    "top_ops",
    "opprof_stats",
    "profilez_payload",
    "chrome_events",
]

# Program-level predicted-vs-measured envelope that /profilez reports
# beside `time_accuracy`: the calibrated roofline prediction against the
# measured replay total, either direction (time_accuracy in
# [1/ENVELOPE, ENVELOPE]). An order of magnitude is deliberately wide:
# off the chip the "device" is a shared host, per-op kernels sit
# microseconds from the dispatch floor, and ambient load moves measured
# totals about 2x run to run. It is there to catch the model or the
# measurement going off the rails; how the model tracks a TPU is not
# measured on the chip.
TIME_ACCURACY_ENVELOPE = 10.0


# ---------------------------------------------------------------------------
# Leg 1a: the stamp grammar (shared with static/executor.py)
# ---------------------------------------------------------------------------

# stamp = <op.type>#<block>/<index>. The op type charset matches the
# registry's names (incl. "grad::mul" colons); '#' and '/' never appear
# in an op type, so the grammar is unambiguous and survives embedding in
# longer scope paths ("jit(main)/matmul#0/3/dot_general",
# "PjitFunction(matmul#0/3)").
_STAMP_RE = re.compile(r"(?P<type>[A-Za-z0-9_.:\-]+)#(?P<block>\d+)/(?P<index>\d+)")


def op_scope_name(op_type, block_idx, op_index) -> str:
    """The stable per-op scope identity: ``op.type#<block>/<index>``."""
    return f"{op_type}#{int(block_idx)}/{int(op_index)}"


def parse_op_scope(name):
    """Extract ``(op_type, block_idx, op_index)`` from a scope/event name
    carrying a stamp anywhere inside it, or None."""
    m = _STAMP_RE.search(str(name))
    if m is None:
        return None
    return m.group("type"), int(m.group("block")), int(m.group("index"))


# ---------------------------------------------------------------------------
# Leg 1b: trace parsing + attribution folding
# ---------------------------------------------------------------------------


def load_trace_events(trace_dir):
    """All chrome traceEvents under ``trace_dir`` (recursive,
    ``*.trace.json[.gz]``) as ``(events, files_ok, files_skipped)``.

    A truncated/corrupt file (the profiler died mid-write) is counted in
    ``files_skipped`` and never raises — the edge table in
    tests/test_opprof.py holds this to it.
    """
    events, ok, skipped = [], 0, 0
    if not trace_dir or not os.path.isdir(trace_dir):
        return events, ok, skipped
    names = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                  recursive=True)
        + glob.glob(os.path.join(trace_dir, "**", "*.trace.json"),
                    recursive=True))
    for fn in names:
        try:
            if fn.endswith(".gz"):
                with gzip.open(fn, "rt") as f:
                    trace = json.load(f)
            else:
                with open(fn) as f:
                    trace = json.load(f)
            evs = trace.get("traceEvents", []) if isinstance(trace, dict) \
                else []
        except Exception:
            skipped += 1
            continue
        ok += 1
        if isinstance(evs, list):
            events.extend(e for e in evs if isinstance(e, dict))
    return events, ok, skipped


def _union_us(intervals) -> float:
    """Total covered span of ``[(ts, dur), ...]`` with overlaps/nesting
    folded (a stamped scope containing a stamped sub-scope must not count
    its device time twice)."""
    total, end = 0.0, None
    for ts, dur in sorted(intervals):
        s, e = ts, ts + dur
        if end is None or s >= end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def attribute_trace(trace_dir) -> dict:
    """Fold a profiler trace directory into a per-op attribution table.

    Only timelines (pid, tid) that carry at least one stamped event are
    scored — device/op rows, not the python tracer or unrelated host
    threads (python-tracer rows are additionally excluded by their ``$``
    name prefix). Within each scored timeline, time is interval-folded
    so nested scopes never double count. Events with *no* stamp on a
    scored timeline count against coverage but never crash the parse.

    Returns ``{"status", "coverage", "total_us", "stamped_us",
    "unattributed_us", "files", "files_skipped", "timelines", "ops"}``
    where ``ops`` rows carry ``scope/op_type/block/index/time_us/share/
    events``. An empty or missing dir is ``status="no-data"`` — a clean
    payload, not a 500.
    """
    events, ok, skipped = load_trace_events(trace_dir)
    # (pid, tid) -> {"all": [(ts, dur)], "stamped": [...],
    #                "per_op": {stamp: [...]}}
    lanes = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = str(ev.get("name", ""))
        if name.startswith("$"):
            continue  # python-tracer host row
        try:
            ts = float(ev["ts"])
            dur = float(ev.get("dur", 0.0))
        except (KeyError, TypeError, ValueError):
            continue
        if dur <= 0.0:
            continue
        lane = lanes.setdefault((ev.get("pid"), ev.get("tid")), {
            "all": [], "stamped": [], "per_op": {}})
        lane["all"].append((ts, dur))
        parsed = parse_op_scope(name)
        if parsed is not None:
            stamp = op_scope_name(*parsed)
            lane["stamped"].append((ts, dur))
            lane["per_op"].setdefault(stamp, []).append((ts, dur))
    scored = {k: v for k, v in lanes.items() if v["stamped"]}
    total = sum(_union_us(v["all"]) for v in scored.values())
    stamped = sum(_union_us(v["stamped"]) for v in scored.values())
    per_op = {}
    n_events = {}
    for lane in scored.values():
        for stamp, ivals in lane["per_op"].items():
            per_op[stamp] = per_op.get(stamp, 0.0) + _union_us(ivals)
            n_events[stamp] = n_events.get(stamp, 0) + len(ivals)
    ops = []
    for stamp, us in sorted(per_op.items(), key=lambda kv: -kv[1]):
        op_type, blk, idx = parse_op_scope(stamp)
        ops.append({
            "scope": stamp, "op_type": op_type, "block": blk, "index": idx,
            "time_us": round(us, 3),
            "share": round(us / total, 4) if total else 0.0,
            "events": n_events[stamp],
        })
    if not scored:
        return {"status": "no-data", "coverage": None, "total_us": 0.0,
                "stamped_us": 0.0, "unattributed_us": 0.0, "files": ok,
                "files_skipped": skipped, "timelines": 0, "ops": []}
    return {
        "status": "ok",
        "coverage": round(stamped / total, 4) if total else None,
        "total_us": round(total, 3),
        "stamped_us": round(stamped, 3),
        "unattributed_us": round(max(total - stamped, 0.0), 3),
        "files": ok,
        "files_skipped": skipped,
        "timelines": len(scored),
        "ops": ops,
    }


# ---------------------------------------------------------------------------
# Leg 2a: the calibrated machine model (time prediction)
# ---------------------------------------------------------------------------

_CALIB: dict = {}
_calib_lock = threading.Lock()


def _best_of_us(fn, *args, warmup=1, repeats=5) -> float:
    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn(*args))
    best = math.inf
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def calibration(refresh=False) -> dict:
    """The per-process calibrated machine model behind
    :func:`predict_op_us`.

    ``device_peaks()`` are *nominal* datasheet numbers (and pure
    placeholders on CPU) — honest MFU denominators, hopeless µs
    predictors. Instead measure, once per process: the per-call dispatch
    floor (tiny elementwise op), effective FLOP/s (reference 256³
    matmul) and effective memory bandwidth (large strided elementwise
    op). Predicted time is then ``floor + max(flops/eff_flops,
    bytes/eff_bw)`` — the roofline shape with empirical ceilings.
    Cached; ~tens of ms to (re)build.
    """
    with _calib_lock:
        if _CALIB and not refresh:
            return dict(_CALIB)
    # references are AOT-compiled and timed with the SAME warmup/best-of
    # discipline the replay uses for real ops: replay calls AOT
    # executables (no jit C++ dispatch fastpath), so the floor must be
    # an AOT call's floor — a jit-wrapper floor is several times lower
    # and would skew every small-op prediction
    def _aot(fn, *args):
        return jax.jit(fn).lower(*args).compile()

    tiny = jnp.ones((8,), jnp.float32)
    floor_us = _best_of_us(_aot(lambda x: x + 1.0, tiny), tiny,
                           warmup=2, repeats=3)
    # AOT argument processing is per-argument python work — charge
    # multi-input ops for it (layer_norm's 3 tensors cost real µs on
    # the dispatch floor even when their math is trivial)
    many = [tiny] * 8

    def _sum8(*xs):
        y = xs[0]
        for x in xs[1:]:
            y = y + x
        return y

    sum8_us = _best_of_us(_aot(_sum8, *many), *many, warmup=2, repeats=3)
    per_arg_us = max((sum8_us - floor_us) / 7.0, 0.0)
    n = 256
    a = jnp.ones((n, n), jnp.float32)
    mm_us = _best_of_us(_aot(lambda x, y: x @ y, a, a), a, a)
    mm_flops = 2.0 * n * n * n
    eff_flops = mm_flops / max((mm_us - floor_us) * 1e-6, 1e-9)
    # convolutions run a different code path with a much lower achieved
    # FLOP/s ceiling than the contraction reference (drastically so on
    # the CPU runner) — calibrate the conv family separately
    img = jnp.ones((4, 8, 16, 16), jnp.float32)
    ker = jnp.ones((8, 8, 3, 3), jnp.float32)

    def _conv(x, k):
        return jax.lax.conv_general_dilated(x, k, (1, 1), "VALID")

    conv_us = _best_of_us(_aot(_conv, img, ker), img, ker)
    conv_flops = 2.0 * 4 * 8 * 14 * 14 * 8 * 3 * 3
    eff_conv = conv_flops / max((conv_us - floor_us) * 1e-6, 1e-9)
    big = jnp.ones((4 << 20,), jnp.float32)  # 16 MiB
    bw_us = _best_of_us(_aot(lambda x: x * 1.5 + 2.0, big), big)
    bw_bytes = 2.0 * big.size * 4  # read + write
    eff_bw = bw_bytes / max((bw_us - floor_us) * 1e-6, 1e-9)
    calib = {
        "dispatch_floor_us": round(floor_us, 3),
        "per_arg_us": round(per_arg_us, 3),
        "eff_flops_per_s": float(eff_flops),
        "eff_conv_flops_per_s": float(eff_conv),
        "eff_bytes_per_s": float(eff_bw),
    }
    with _calib_lock:
        _CALIB.clear()
        _CALIB.update(calib)
    return dict(calib)


def predict_op_us(flops, bytes_accessed, op_type=None, n_args=1) -> float:
    """Calibrated-roofline predicted kernel time in µs (conv-family ops
    use the conv FLOP/s ceiling; extra arguments pay the per-arg
    dispatch charge)."""
    c = calibration()
    ceiling = c["eff_conv_flops_per_s"] if "conv" in str(op_type or "") \
        else c["eff_flops_per_s"]
    roof_s = max(
        (float(flops) / ceiling) if flops else 0.0,
        (float(bytes_accessed) / c["eff_bytes_per_s"]) if bytes_accessed
        else 0.0)
    return (c["dispatch_floor_us"]
            + c.get("per_arg_us", 0.0) * max(int(n_args) - 1, 0)
            + roof_s * 1e6)


def _symmetric_ratio(predicted, measured):
    """time_accuracy: predicted/measured (1.0 = perfect), None if either
    side is missing — the plan_accuracy convention."""
    if not predicted or not measured:
        return None
    return float(predicted) / float(measured)


# ---------------------------------------------------------------------------
# Leg 2b: the replay profiler
# ---------------------------------------------------------------------------


# profile_program's defaults: untimed runs an op, timed runs an op
# (best-of-N), and the rows of the /statz and default /profilez tables
WARMUP = 1
REPEATS = 3
TOPK = 10


def profile_program(program, feed=None, fetch_list=None, *, scope=None,
                    name=None, warmup=None, repeats=None, with_trace=True,
                    record=True) -> dict:
    """Replay ``program``'s top block op-by-op through the REGISTRY
    kernels and measure each op in isolation.

    Every op gets its own ``jax.jit`` whose callable is *named with the
    op's stamp* (so the jax.profiler trace taken around the timed pass
    self-identifies per op even on CPU), AOT-compiled once, then timed
    warmup + best-of-N behind ``block_until_ready``. Inputs come from
    ``feed`` plus the scope's persistables — run the program through the
    Executor once first so parameters/constants are materialized.

    Control-flow (`cond/scan/while`) and ``grad::`` ops are not
    replayable in isolation; they are reported with ``replayed: False``
    and their downstream consumers degrade the same way — replay targets
    inference-shaped programs (the /profilez contract; train steps get
    trace attribution instead).

    Returns the profile dict (also stored for ``/profilez`` under
    ``name``). When ``record`` is set, the time-accuracy closure lands
    on the latest executor CostRecord like memplan's ``plan_accuracy``.
    """
    from ..ops.registry import EAGER_ONLY_OPS, has_op, kernel
    from ..static import executor as _exec
    from . import cost_model as _cost
    from . import registry as _registry

    scope = scope or _exec.global_scope()
    warmup = WARMUP if warmup is None else int(warmup)
    repeats = REPEATS if repeats is None else int(repeats)
    block = program.global_block()
    name = name or f"program{getattr(program, '_identity_token', id(program))}"

    env = {}
    for n in scope.var_names():
        env[n] = scope.get(n)
    for k, v in (feed or {}).items():
        env[k] = v if isinstance(v, jax.Array) else jnp.asarray(np.asarray(v))

    peaks = _cost.device_peaks()
    base_key = jax.random.PRNGKey(0)
    rows, runnable = [], []
    for i, op in enumerate(block.ops):
        stamp = op_scope_name(op.type, block.idx, i)
        row = {"scope": stamp, "op_type": op.type, "block": block.idx,
               "index": i, "replayed": False, "time_us": None}
        rows.append(row)
        if op.type in _exec._BLOCK_OPS or op.type.startswith("grad::"):
            row["reason"] = "control-flow/grad op (not replayable)"
            continue
        if not has_op(op.type):
            row["reason"] = "no registry kernel"
            continue
        if op.type in EAGER_ONLY_OPS:
            row["reason"] = "eager-only kernel (unjittable)"
            continue
        in_names = _exec.op_in_names(op)
        missing = [n for n in in_names if n not in env]
        if missing:
            row["reason"] = f"missing inputs {missing[:3]}"
            continue
        f_attrs = {k: v for k, v in op.attrs.items()
                   if not k.startswith("__")}
        if op.attrs.get("__rng__"):
            f_attrs["key"] = _exec._op_key(base_key, op)
        fn_k = kernel(op.type)

        def _call(*arrays, _fn=fn_k, _attrs=f_attrs):
            return _fn(*arrays, **_attrs)

        # the stamp IS the callable name: trace events become
        # PjitFunction(<stamp>) and attribute_trace folds them with zero
        # backend cooperation (CPU has no HLO-metadata device rows)
        _call.__name__ = stamp
        _call.__qualname__ = stamp
        arrays = [env[n] for n in in_names]
        try:
            lowered = jax.jit(_call).lower(*arrays)
            compiled = lowered.compile()
            out = compiled(*arrays)
        except Exception as e:  # keep profiling the rest of the program
            row["reason"] = f"compile/run failed: {e}"
            continue
        results = list(out) if isinstance(out, (tuple, list)) else [out]
        for out_name, value in zip(_exec.op_out_names(op), results):
            if out_name and value is not None:
                env[out_name] = value
        fb = _cost.flops_and_bytes(compiled) or (0, 0)
        row["flops"], row["bytes"] = int(fb[0] or 0), int(fb[1] or 0)
        row["n_args"] = len(arrays)
        runnable.append((row, compiled, arrays))

    # timed pass, optionally under a jax.profiler trace so one profiling
    # run also yields the attribution table (+ coverage) from real trace
    # events. Compilation happened above — the trace sees steady state.
    trace_dir, tracing = None, False
    if with_trace:
        trace_dir = tempfile.mkdtemp(prefix="opprof_trace_")
        try:
            jax.profiler.start_trace(trace_dir)
            tracing = True
        except Exception:
            tracing = False  # an outer trace is live: skip, never break it
    try:
        for row, compiled, arrays in runnable:
            row["time_us"] = round(
                _best_of_us(compiled, *arrays, warmup=warmup,
                            repeats=repeats), 3)
            row["replayed"] = True
    finally:
        if tracing:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass

    total_us = sum(r["time_us"] for r in rows if r["replayed"])
    pred_total = 0.0
    hist = _registry.histogram(
        "opprof/op_time_ms",
        buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0,
                 50.0, 100.0, 500.0),
        help="replay-measured per-op device time (opprof)")
    for row in rows:
        if not row["replayed"]:
            continue
        us = row["time_us"]
        row["share"] = round(us / total_us, 4) if total_us else 0.0
        secs = max(us * 1e-6, 1e-12)
        row["flops_per_s"] = row["flops"] / secs
        row["mfu"] = round(_cost.mfu(row["flops_per_s"], peaks), 6)
        row["roofline"] = _cost.roofline_class(row["flops"], row["bytes"],
                                               peaks)
        row["predicted_us"] = round(
            predict_op_us(row["flops"], row["bytes"], row["op_type"],
                          n_args=row.get("n_args", 1)), 3)
        pred_total += row["predicted_us"]
        row["time_accuracy"] = ta = _symmetric_ratio(row["predicted_us"], us)
        if ta is not None:
            row["time_accuracy"] = round(ta, 4)
        hist.labels(op_type=row["op_type"]).observe(us / 1e3)

    attribution = attribute_trace(trace_dir) if trace_dir else {
        "status": "no-data", "coverage": None, "ops": []}
    accuracy = _symmetric_ratio(pred_total, total_us)
    profile = {
        "name": name,
        "n_ops": len(rows),
        "replayed_ops": sum(1 for r in rows if r["replayed"]),
        "total_us": round(total_us, 3),
        "predicted_total_us": round(pred_total, 3),
        "time_accuracy": round(accuracy, 4) if accuracy else None,
        "coverage": attribution.get("coverage"),
        "warmup": warmup,
        "repeats": repeats,
        "ops": rows,
        "attribution": attribution,
        "calibration": calibration(),
        "created_t": time.time(),
    }
    record_profile(profile)
    if record:
        # the /costz closure: predicted vs measured per-op time on the
        # program's CostRecord, the exact shape plan_accuracy landed as
        rec = _cost.latest_record("executor")
        if rec is not None and accuracy is not None:
            rec.predicted_op_us = round(pred_total, 3)
            rec.measured_op_us = round(total_us, 3)
            rec.time_accuracy = round(accuracy, 4)
    return profile


# ---------------------------------------------------------------------------
# the profile store (+ /statz /profilez /metrics chrome surfaces)
# ---------------------------------------------------------------------------

_PROFILES: dict = {}  # name -> profile, insertion-ordered
_profiles_lock = threading.Lock()
_STORE_CAP = 16


def record_profile(profile):
    with _profiles_lock:
        _PROFILES.pop(profile["name"], None)
        _PROFILES[profile["name"]] = profile
        while len(_PROFILES) > _STORE_CAP:
            _PROFILES.pop(next(iter(_PROFILES)))


def profiles() -> list:
    with _profiles_lock:
        return list(_PROFILES)


def latest_profile(name=None):
    with _profiles_lock:
        if name is not None:
            return _PROFILES.get(name)
        return next(reversed(_PROFILES.values()), None) if _PROFILES \
            else None


def reset_profiles():
    with _profiles_lock:
        _PROFILES.clear()


def top_ops(k=None) -> list:
    """Top-K replayed ops by measured device time from the most recent
    profile — the /statz table."""
    k = TOPK if k is None else int(k)
    prof = latest_profile()
    if prof is None:
        return []
    rows = sorted((r for r in prof["ops"] if r.get("replayed")),
                  key=lambda r: -(r["time_us"] or 0.0))
    return [{"scope": r["scope"], "op_type": r["op_type"],
             "time_us": r["time_us"], "share": r.get("share", 0.0),
             "mfu": r.get("mfu"), "roofline": r.get("roofline")}
            for r in rows[:max(k, 0)]]


def opprof_stats() -> dict:
    """The /statz opprof block: stored programs + top-K op table."""
    prof = latest_profile()
    return {
        "programs": profiles(),
        "latest": None if prof is None else {
            "name": prof["name"], "total_us": prof["total_us"],
            "time_accuracy": prof["time_accuracy"],
            "coverage": prof["coverage"],
        },
        "top_ops": top_ops(),
    }


def profilez_payload(query=None):
    """``(status, payload)`` for GET /profilez.

    ``?program=<name>`` selects a stored profile (404 when unknown),
    ``?topk=N`` trims the op table. With nothing profiled yet the
    payload is a clean ``status="no-data"`` hint, not an error.
    """
    query = query or {}
    with _profiles_lock:
        names = list(_PROFILES)
    if not names:
        return 200, {
            "status": "no-data", "programs": [],
            "hint": "run paddle_tpu.monitor.opprof.profile_program(...) "
                    "to populate"}
    want = query.get("program")
    if want is not None and latest_profile(want) is None:
        return 404, {"status": "unknown-program", "program": want,
                     "programs": names}
    prof = latest_profile(want)
    try:
        topk = int(query.get("topk", TOPK))
    except (TypeError, ValueError):
        topk = TOPK
    ops = sorted((r for r in prof["ops"] if r.get("replayed")),
                 key=lambda r: -(r["time_us"] or 0.0))[:max(topk, 0)]
    skipped = [{"scope": r["scope"], "reason": r.get("reason", "")}
               for r in prof["ops"] if not r.get("replayed")]
    attribution = dict(prof["attribution"])
    attribution["ops"] = attribution.get("ops", [])[:max(topk, 0)]
    return 200, {
        "status": "ok",
        "programs": names,
        "program": prof["name"],
        "summary": {
            "n_ops": prof["n_ops"],
            "replayed_ops": prof["replayed_ops"],
            "total_us": prof["total_us"],
            "predicted_total_us": prof["predicted_total_us"],
            "time_accuracy": prof["time_accuracy"],
            "time_accuracy_envelope": TIME_ACCURACY_ENVELOPE,
            "coverage": prof["coverage"],
            "warmup": prof["warmup"],
            "repeats": prof["repeats"],
        },
        "ops": ops,
        "skipped": skipped,
        "attribution": attribution,
        "calibration": prof["calibration"],
    }


def chrome_events() -> list:
    """Per-op replay tracks for ``export_merged_chrome_trace``: one
    synthetic thread per stored profile, ops laid end-to-end at their
    measured durations (relative layout — replay times ops in isolation,
    so only durations, shares and order are meaningful)."""
    with _profiles_lock:
        profs = list(_PROFILES.values())
    if not profs:
        return []
    pid = os.getpid()
    events = []
    for ti, prof in enumerate(profs):
        tid = f"opprof:{prof['name']}"
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid,
                       "args": {"name": f"opprof replay [{prof['name']}]"}})
        t = 0.0
        for row in prof["ops"]:
            if not row.get("replayed"):
                continue
            events.append({
                "name": row["scope"], "ph": "X", "pid": pid, "tid": tid,
                "ts": t, "dur": row["time_us"], "cat": "opprof",
                "args": {"mfu": row.get("mfu"),
                         "roofline": row.get("roofline"),
                         "predicted_us": row.get("predicted_us")},
            })
            t += row["time_us"]
    return events

"""Headline benchmark: BERT-base MLM pretraining tokens/sec/chip, plus
ResNet-50 images/sec/chip and BERT phase-2 (seq 512, pallas flash
attention) as secondary BASELINE.md metrics.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"loss_start", "loss_end", "median_of", "samples",
"secondary": {...resnet50...}, "secondary2": {...bert phase-2 flash...}}.

vs_baseline compares against the A100 GPU-parity target from BASELINE.md
(the reference publishes no numbers in-tree; NVIDIA DeepLearningExamples
BERT-base phase-1 pretraining, seq 128 fp16 + fused kernels, reports
~700-800 sequences/sec on one A100 ≈ 90-100k tokens/sec — we use 90000
tokens/sec/chip as the parity bar; phase-2 at seq 512 reports ~80-90k
tokens/sec — we use 85000; ResNet-50 v1.5 AMP+DALI ~2500-2900 images/sec
— we use 2500).

Recipe parity: phase-1 pretraining at seq 128 with
max_predictions_per_seq=20 (phase-2: seq 512, 80) — MLM logits are
computed only at the gathered masked positions (BertForPretraining
masked_positions path), exactly as the A100 reference recipe does; dropout
(hidden 0.1 + attention 0.1) is ON, as in the standard config. RNG uses
the TPU-native rbg implementation (framework/random.py) — part of the
measured win. Phase-2 runs the pallas flash-attention kernel
(ops/pallas/flash_attention.py): seq 512 >= FLASH_ATTENTION_MIN_SEQ, where
the XLA path OOMs at this batch and the kernel is the measured winner.

Noise discipline: a single sample cannot certify a bar crossing. Every
metric times ``repeats`` independent passes in-process and reports the
MEDIAN (all samples are included in the JSON for auditability).

Timing note: each timed pass ends in a fetch of the final loss value
(np.asarray), which waits for the device like block_until_ready does.

One process for each chip: this process holds the chip once bench_bert has
run, so the two rows that start ``python -m paddle_tpu.serving.backend``
children (router_throughput, decode_throughput.disagg) pin those children
to the CPU platform and say ``"platform": "cpu"`` — they measure host-side
routing, not the device.
"""
from __future__ import annotations

import json
import time

import numpy as np

GPU_PARITY_TOKENS_PER_SEC = 90000.0
GPU_PARITY_TOKENS_PER_SEC_PHASE2 = 85000.0
GPU_PARITY_IMAGES_PER_SEC = 2500.0

REPEATS_TPU = 3  # median-of-3

# A chip belongs to one process, and bench's main() holds it: children that
# serve models are pinned to the CPU platform, and their rows say so.
CPU_CHILD_ENV = {"JAX_PLATFORMS": "cpu"}


def _timed_median(step_once, items_per_iter, iters, repeats):
    """Run ``repeats`` timed passes of ``iters`` steps; return
    (median items/sec, samples, last_loss)."""
    samples = []
    last = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            m = step_once()
        last = float(np.asarray(m["loss"]))  # value fetch = barrier
        dt = time.perf_counter() - t0
        samples.append(round(items_per_iter * iters / dt, 1))
    return float(np.median(samples)), samples, last


def _utilization_fields(row, items_per_iter):
    """Attach hardware-utilization fields to a throughput row: MFU and
    HBM-bandwidth utilization from the cost model's captured per-step
    FLOPs/bytes (the compiled module's own cost_analysis, not an
    estimate) at the row's measured steps/sec — BENCH_*.json then tracks
    utilization regressions, not just absolute tokens/sec."""
    from paddle_tpu.monitor import cost_model

    rec = cost_model.latest_record("train_step")
    peaks = cost_model.device_peaks()
    steps_per_sec = row["value"] / items_per_iter if items_per_iter else 0.0
    if rec is None or not rec.flops:
        row["mfu"] = 0.0
        row["hbm_bw_util"] = 0.0
        return row
    row["mfu"] = round(cost_model.mfu(rec.flops * steps_per_sec, peaks), 5)
    row["hbm_bw_util"] = round(
        cost_model.hbm_bw_util(rec.bytes_accessed * steps_per_sec, peaks), 5)
    row["cost_model"] = {
        "flops_per_step": rec.flops,
        "bytes_per_step": rec.bytes_accessed,
        "peak_hbm_bytes": rec.peak_hbm_bytes,
        "roofline": cost_model.roofline_class(
            rec.flops, rec.bytes_accessed, peaks),
        "device_kind": peaks["kind"],
        "peaks_nominal": peaks["nominal"],
    }
    return row


def _annotate_variance(row):
    """Flag runs where even in-process samples disagree: the median of
    such a run is not a number to compare against."""
    s = row.get("samples", [])
    if len(s) >= 2 and row["value"]:
        spread = (max(s) - min(s)) / row["value"]
        if spread > 0.15:
            row["variance_note"] = (
                f"in-process sample spread {spread:.0%}: do not compare "
                "this median")
    return row


def bench_resnet50(on_tpu):
    """ResNet-50 images/sec/chip (BASELINE.md row 1)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu import amp
    from paddle_tpu.framework import jit as fjit
    from paddle_tpu.models import resnet50, resnet18

    if on_tpu:
        # 50 iters per timed pass: one value fetch ends the pass, as a
        # real training loop (which fetches loss rarely) would
        batch, size, iters, make = 128, 224, 50, resnet50
        repeats = REPEATS_TPU
        name = "resnet50_images_per_sec_per_chip"
    else:  # CPU smoke: tiny net, tiny images
        batch, size, iters, make = 8, 32, 2, resnet18
        repeats = 1
        name = "resnet18_cpu_smoke_images_per_sec"

    paddle.seed(0)
    model = make(num_classes=1000)
    optimizer = opt.Momentum(
        learning_rate=0.1, momentum=0.9, parameters=model.parameters()
    )

    def loss_fn(m, x, y):
        with amp.auto_cast():
            logits = m(x)
        return F.cross_entropy(logits.astype("float32"), y).mean()

    step = fjit.train_step(model, optimizer, loss_fn)
    rng = np.random.RandomState(0)
    import jax

    # device-resident batch: the DataLoader's prefetch stage owns the
    # host→TPU copy in real training; the bench measures step compute.
    x = jax.device_put(rng.randn(batch, 3, size, size).astype("float32"))
    y = jax.device_put(rng.randint(0, 1000, (batch,)).astype("int64"))

    l0 = float(np.asarray(step(x, y)["loss"]))  # warmup/compile
    float(np.asarray(step(x, y)["loss"]))
    ips, samples, l1 = _timed_median(
        lambda: step(x, y), batch, iters, repeats
    )
    return _utilization_fields(_annotate_variance({
        "metric": name,
        "value": round(ips, 1),
        "unit": "images/sec",
        "vs_baseline": round(ips / GPU_PARITY_IMAGES_PER_SEC, 3)
        if on_tpu else 0.0,
        "loss_start": round(l0, 4),
        "loss_end": round(l1, 4),
        "median_of": repeats,
        "samples": samples,
    }), batch)


def bench_bert(on_tpu, phase=1):
    """BERT-base MLM pretraining tokens/sec/chip.

    phase 1: seq 128, n_pred 20, batch 128 — the headline (XLA attention
    path below FLASH_ATTENTION_MIN_SEQ, the measured winner at seq 128).
    phase 2: seq 512, n_pred 80, batch 32 — runs the pallas flash
    attention kernel (the measured winner at seq >= 512, where the plain
    XLA path exhausts HBM at this batch).
    """
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import amp
    from paddle_tpu.framework import jit as fjit
    from paddle_tpu.models import (
        BertConfig,
        BertForPretraining,
        BertPretrainingCriterion,
    )

    if on_tpu:
        cfg = BertConfig(use_flash_attention=True)  # base: 12L/768H
        if phase == 1:
            batch, seq, n_pred, iters = 128, 128, 20, 50
        else:
            batch, seq, n_pred, iters = 32, 512, 80, 25
        repeats = REPEATS_TPU
        name = ("bert_base_pretrain_tokens_per_sec_per_chip" if phase == 1
                else "bert_base_phase2_seq512_flash_tokens_per_sec_per_chip")
        bar = (GPU_PARITY_TOKENS_PER_SEC if phase == 1
               else GPU_PARITY_TOKENS_PER_SEC_PHASE2)
    else:
        cfg = BertConfig(
            vocab_size=8192, hidden_size=256, num_hidden_layers=4,
            num_attention_heads=8, intermediate_size=1024,
            max_position_embeddings=512 if phase == 2 else 128,
            use_flash_attention=(phase == 2),
        )
        if phase == 1:
            batch, seq, n_pred, iters = 8, 128, 20, 3
        else:
            batch, seq, n_pred, iters = 2, 512, 80, 2
        repeats = 1
        name = ("bert_small_cpu_smoke_tokens_per_sec" if phase == 1
                else "bert_small_cpu_smoke_phase2_tokens_per_sec")
        bar = None

    paddle.seed(0)
    model = BertForPretraining(cfg)
    crit = BertPretrainingCriterion(cfg.vocab_size)
    optimizer = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())

    def loss_fn(m, ids, tt, pos, mlm, nsp):
        with amp.auto_cast():
            pred, rel = m(ids, tt, masked_positions=pos)
        return crit(
            pred.astype("float32"), rel.astype("float32"), mlm, nsp
        )

    step = fjit.train_step(model, optimizer, loss_fn)

    rng = np.random.RandomState(0)
    # device-resident batch (see bench_resnet50 note)
    ids = jax.device_put(
        rng.randint(1, cfg.vocab_size, (batch, seq)).astype("int64")
    )
    tt = jax.device_put(rng.randint(0, 2, (batch, seq)).astype("int64"))
    # flat positions into the [B*L] hidden-state table, n_pred per sequence
    pos = jax.device_put(np.stack(
        [rng.choice(seq, n_pred, replace=False) + i * seq
         for i in range(batch)]
    ).ravel().astype("int64"))
    mlm = jax.device_put(
        rng.randint(0, cfg.vocab_size, (batch * n_pred,)).astype("int64")
    )
    nsp = jax.device_put(rng.randint(0, 2, (batch, 1)).astype("int64"))

    # warmup + compile
    loss_start = float(np.asarray(step(ids, tt, pos, mlm, nsp)["loss"]))
    float(np.asarray(step(ids, tt, pos, mlm, nsp)["loss"]))

    # the timed loop runs under a TrainingMonitor so the bench prints the
    # utilization line end-to-end (mfu/hbm_bw_util from the compiled
    # module's own cost_analysis via the executed-work ledger); per-step
    # monitor cost is inside the certified <2% monitor_overhead budget
    import sys

    from paddle_tpu import monitor as _monitor

    # stderr: bench stdout stays exactly ONE JSON line (driver contract)
    mon = _monitor.TrainingMonitor(
        f"bench_bert_phase{phase}", interval=iters,
        log_fn=lambda line: print(line, file=sys.stderr))

    def monitored_step():
        with mon.step(examples=batch * seq):
            return step(ids, tt, pos, mlm, nsp)

    tps, samples, loss_end = _timed_median(
        monitored_step, batch * seq, iters, repeats
    )
    mon.close()
    return _utilization_fields(_annotate_variance({
        "metric": name,
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tps / bar, 3) if bar else 0.0,
        # convergence evidence: repeated steps on one batch must drive the
        # loss down (full loss-parity training lives in tests/test_book.py)
        "loss_start": round(loss_start, 4),
        "loss_end": round(loss_end, 4),
        "median_of": repeats,
        "samples": samples,
    }), batch * seq)


def bench_monitor_overhead(iters=300):
    """Instrumentation overhead on the executor_dispatch micro-bench.

    The whole-stack spans (RecordEvent around plan/feed/dispatch/
    writeback) ride the dispatch hot path even when nobody profiles —
    with the profiler DISABLED each span is two perf_counter_ns calls
    and a no-op end(). This row measures exactly that cost: the same
    steady-state loop with the spans live vs. with RecordEvent stubbed
    to a literal no-op, profiler off in both. The per-run cost-model
    accounting (cost_model.note_run — two counter adds feeding the MFU
    ledger) rides the same hot path, so the stubbed mode no-ops it too:
    the row certifies spans + utilization accounting together. Target:
    < 2% overhead (the always-on price of observability must be noise).
    """
    import paddle_tpu.monitor.cost_model as cost_mod
    import paddle_tpu.static.executor as executor_mod

    class _NullEvent:
        __slots__ = ("name",)

        def __init__(self, name):
            self.name = name

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def begin(self):
            return self

        def end(self):
            pass

    real_event = executor_mod.RecordEvent
    real_note_run = cost_mod.note_run
    live, stubbed = [], []
    # alternate modes so slow drift (thermal, competing load) hits both;
    # compare BEST-of-5 rates: scheduler/GC noise only ever slows a pass,
    # so the max of each mode is the least-contaminated estimate of its
    # true dispatch rate (medians of overlapping noisy distributions
    # routinely fabricate multi-percent "overheads" here)
    for _ in range(5):
        live.append(bench_executor_dispatch(iters=iters)["value"])
        executor_mod.RecordEvent = _NullEvent
        cost_mod.note_run = lambda record, n=1: None
        try:
            stubbed.append(bench_executor_dispatch(iters=iters)["value"])
        finally:
            executor_mod.RecordEvent = real_event
            cost_mod.note_run = real_note_run
    live_best = float(max(live))
    stub_best = float(max(stubbed))
    # overhead of the live spans relative to the stubbed loop; negative
    # means the difference drowned in run-to-run noise (good)
    overhead = (stub_best - live_best) / stub_best
    # DIRECT decomposition of the per-run cost-accounting price (the
    # flight-recorder row's discipline): a whole-loop A/B cannot resolve
    # 2% on a contended box, but the tight-loop per-call cost of
    # note_run (the only per-run work the cost model adds — two counter
    # adds) divided by the measured run period is noise-immune.
    import time as _time

    rec = cost_mod.latest_record("executor")

    def _note_us(n=20000):
        t0 = _time.perf_counter()
        for _ in range(n):
            real_note_run(rec)
        return (_time.perf_counter() - t0) / n * 1e6

    note_us = min(_note_us() for _ in range(3))
    period_us = 1e6 / live_best
    cost_overhead = note_us / period_us  # one note_run per executor run
    return {
        "metric": "executor_dispatch_instrumentation_overhead",
        "value": round(overhead * 100, 2),
        "unit": "percent",
        "target_pct": 2.0,
        "within_target": bool(overhead < 0.02),
        "instrumented_runs_per_sec": live_best,
        "stubbed_runs_per_sec": stub_best,
        "best_of": 5,
        "samples": {"instrumented": live, "stubbed": stubbed},
        "cost_accounting": {
            "per_note_run_us": round(note_us, 3),
            "run_period_us": round(period_us, 1),
            "overhead_pct": round(cost_overhead * 100, 3),
            "within_target": bool(cost_overhead < 0.02),
        },
    }


def bench_flight_recorder_overhead(iters=300):
    """Flight-recorder cost on the executor_dispatch micro-bench.

    Recording is always-on (FLAGS_flight_recorder defaults True): every
    run() appends 2 structured events to the ring buffer (one flag read
    + dict build + short lock hold each). Target: < 2% — the black box
    must be free enough to never turn off.

    Measurement discipline: a whole-loop A/B cannot resolve 2% on a
    contended box (the dispatch bench itself swings ±20% run to run —
    observed sign flips across repeats), so the certified number is the
    DIRECT decomposition: per-event record cost (tight loop, on minus
    off, best-of-3 — the only quantity noise at this scale can't bury)
    × events actually recorded per run ÷ the measured steady-state run
    period. The whole-loop A/B (best-of-5 per mode, alternating) ships
    alongside as corroboration; on a quiet box both agree.
    """
    import time as _time

    from paddle_tpu.flags import get_flags, set_flags
    from paddle_tpu.monitor import flight_recorder as fr

    def _per_event_us(n=20000):
        t0 = _time.perf_counter()
        for _ in range(n):
            fr.record_event(
                "bench_probe", program="p@v1", plan_cache="hit",
                jit_cache="hit", feeds=2, fetches=1, donated=4)
        return (_time.perf_counter() - t0) / n * 1e6

    prev = get_flags("flight_recorder")["flight_recorder"]
    recording, disabled = [], []
    try:
        set_flags({"flight_recorder": True})
        on_us = min(_per_event_us() for _ in range(3))
        # events per run + steady-state period, with recording live
        rec = fr.get_recorder()
        before = rec.total_recorded
        live_row = bench_executor_dispatch(iters=iters)
        events_per_run = (
            (rec.total_recorded - before) / float(live_row["runs"]))
        period_us = 1e6 / live_row["value"]
        set_flags({"flight_recorder": False})
        off_us = min(_per_event_us() for _ in range(3))
        # whole-loop A/B corroboration (alternating so drift hits both)
        for _ in range(5):
            set_flags({"flight_recorder": True})
            recording.append(bench_executor_dispatch(iters=iters)["value"])
            set_flags({"flight_recorder": False})
            disabled.append(bench_executor_dispatch(iters=iters)["value"])
    finally:
        set_flags({"flight_recorder": prev})
    per_event_delta_us = max(0.0, on_us - off_us)
    overhead = per_event_delta_us * events_per_run / period_us
    rec_best, off_best = float(max(recording)), float(max(disabled))
    return {
        "metric": "flight_recorder_overhead",
        "value": round(overhead * 100, 3),
        "unit": "percent",
        "target_pct": 2.0,
        "within_target": bool(overhead < 0.02),
        "per_event_us": {"recording": round(on_us, 3),
                         "disabled": round(off_us, 3),
                         "delta": round(per_event_delta_us, 3)},
        "events_per_run": round(events_per_run, 2),
        "run_period_us": round(period_us, 1),
        "ab_corroboration": {
            "overhead_pct": round(
                (off_best - rec_best) / off_best * 100, 2),
            "recording_runs_per_sec": rec_best,
            "disabled_runs_per_sec": off_best,
            "best_of": 5,
            "samples": {"recording": recording, "disabled": disabled},
        },
    }


def bench_goodput_overhead(iters_direct=20000):
    """Goodput-ledger cost on the training step path (target < 1%).

    The ledger touches a step exactly at its phase transitions:
    ``step_begin`` / ``step_commit`` bracket the frame, and each
    sub-phase feed (``note_phase`` for input wait, the checkpoint /
    compile spans) is one more lock-held float add. A whole-loop A/B
    can't resolve sub-percent cost (monitor_overhead discipline), so
    the certified number is the DIRECT decomposition: per-transition
    cost (tight loop on an in-memory ledger, best-of-3) × transitions
    per step ÷ the measured steady-state dispatch period.
    """
    import time as _time

    from paddle_tpu.monitor.goodput import GoodputLedger

    led = GoodputLedger(dir=None)  # in-memory: no sidecar, no metrics

    def _per_frame_us(n=iters_direct):
        t0 = _time.perf_counter()
        for _ in range(n):
            led.step_begin()
            led.step_commit()
        return (_time.perf_counter() - t0) / n * 1e6

    def _per_note_us(n=iters_direct):
        t0 = _time.perf_counter()
        for _ in range(n):
            led.note_phase("input_wait", 0.0)
        return (_time.perf_counter() - t0) / n * 1e6

    frame_us = min(_per_frame_us() for _ in range(3))
    note_us = min(_per_note_us() for _ in range(3))
    # steady-state step period from the dispatch micro-bench (the same
    # reference period every observability overhead row certifies
    # against)
    live_row = bench_executor_dispatch(iters=200)
    period_us = 1e6 / live_row["value"]
    # a representative step: one frame + input-wait note + one
    # amortized sub-phase span (checkpoint/compile every few steps)
    notes_per_step = 2.0
    step_cost_us = frame_us + note_us * notes_per_step
    overhead = step_cost_us / period_us
    return {
        "metric": "goodput_overhead",
        "value": round(overhead * 100, 3),
        "unit": "percent",
        "target_pct": 1.0,
        "within_target": bool(overhead < 0.01),
        "per_frame_us": round(frame_us, 3),
        "per_note_us": round(note_us, 3),
        "notes_per_step": notes_per_step,
        "step_period_us": round(period_us, 1),
    }


def bench_opprof_overhead(iters_direct=20000):
    """Per-op attribution cost on the dispatch path (target < 1%).

    The op stamps (``op.type#<block>/<index>`` named_scope, executor
    _exec_one) are written only while an op walk is TRACING — a plan-
    cache miss. A steady-state dispatch replays the compiled callable
    and never touches them, so the certified idle number is the direct
    decomposition of the trace-time cost amortized over the window it
    buys: per-stamp cost (format + named_scope enter/exit, tight loop,
    best-of-3) × ops per trace epoch ÷ (dispatches per epoch × the
    measured dispatch period). Sampling-mode cost — one on-demand
    ``profile_program`` replay — is reported unasserted: it runs only
    when explicitly requested, never on the dispatch path, and is
    bounded by warmup+repeats per op.
    """
    import jax

    from paddle_tpu.monitor import opprof

    def _per_stamp_us(n=iters_direct):
        scope = opprof.op_scope_name
        t0 = time.perf_counter()
        for i in range(n):
            with jax.named_scope(scope("matmul", 0, i & 63)):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    stamp_us = min(_per_stamp_us() for _ in range(3))
    live_row = bench_executor_dispatch(iters=200)
    period_us = 1e6 / live_row["value"]
    # a trace epoch = one plan-cache miss; the dispatch bench's train
    # step (fwd+grad+Adam) walks ~24 ops once and then serves at least
    # the bench window of dispatches from the cache
    ops_per_trace = 24.0
    dispatches_per_trace = 200.0
    overhead = (stamp_us * ops_per_trace) / (
        dispatches_per_trace * period_us)

    # sampling mode: replay-profile a small program once, wall-clock
    import paddle_tpu.static as static
    from paddle_tpu import ops

    static.enable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    try:
        x = static.data("x", [32, 64], "float32")
        w = static.nn.create_parameter([64, 16], "float32")
        out = ops.relu(ops.matmul(x, w))
        exe = static.Executor()
        exe.run_startup()
        feeds = {"x": np.random.RandomState(0).randn(32, 64)
                 .astype("float32")}
        exe.run(feed=feeds, fetch_list=[out])
        t0 = time.perf_counter()
        prof = opprof.profile_program(
            static.default_main_program(), feeds, name="bench",
            with_trace=False, record=False)
        sample_ms = (time.perf_counter() - t0) * 1e3
    finally:
        static.disable_static()
        static.reset_default_programs()
        static.global_scope().clear()

    return {
        "metric": "opprof_overhead",
        "value": round(overhead * 100, 4),
        "unit": "percent",
        "target_pct": 1.0,
        "within_target": bool(overhead < 0.01),
        "per_stamp_us": round(stamp_us, 3),
        "ops_per_trace": ops_per_trace,
        "dispatches_per_trace": dispatches_per_trace,
        "step_period_us": round(period_us, 1),
        "sampling": {
            "profile_ms": round(sample_ms, 1),
            "ops_replayed": prof["replayed_ops"],
            "time_accuracy": prof["time_accuracy"],
        },
    }


def bench_tracing_overhead(requests=160, iters_direct=4000):
    """Per-request tracing cost on the serving path (target < 2%).

    Every served request records a span tree (root + queue-wait +
    assemble + dispatch and its fan-in copy) through the tail-sampled
    trace store; tracing ships always-on, so the cost must be certified
    the way ``monitor_overhead``/``flight_recorder_overhead`` are.

    Discipline: the certified number is the DIRECT decomposition — the
    per-span cost of a representative span tree (enabled minus disabled,
    tight loop, best-of-3: the quantity box noise cannot bury) scaled by
    the spans a real request actually records, over the measured
    per-request period of a live batcher+replica loop. The whole-loop
    A/B (alternating, best-of-5) ships alongside as corroboration.
    """
    import tempfile
    import time as _time

    import paddle_tpu.static as static
    from paddle_tpu.flags import get_flags, set_flags
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.monitor import tracing
    from paddle_tpu.serving import DynamicBatcher, ReplicaPool

    # a 5-span tree per iteration: the serving request's shape
    def _per_tree_us(n=iters_direct):
        t0 = _time.perf_counter()
        for _ in range(n):
            with tracing.start_trace("bench::request"):
                with tracing.start_span("bench::queue_wait"):
                    pass
                with tracing.start_span("bench::assemble", bucket=4,
                                        fill=1.0):
                    pass
                with tracing.start_span("bench::dispatch", flops=1.0):
                    pass
                with tracing.start_span("bench::reply", status=200):
                    pass
        return (_time.perf_counter() - t0) / n * 1e6

    static.enable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    try:
        x = static.data("x", [None, 32], "float32")
        y = static.nn.fc(static.nn.fc(x, 64, name="tr_fc1"), 8,
                         name="tr_fc2")
        exe = static.Executor()
        exe.run_startup()
        model_dir = tempfile.mkdtemp(prefix="ptpu_bench_trace_")
        static.save_inference_model(model_dir, ["x"], [y], exe)
    finally:
        static.disable_static()
        static.reset_default_programs()
    pred = create_predictor(Config(model_dir))
    batcher = DynamicBatcher(["x"], buckets=(1, 2, 4),
                             queue_capacity=64, batch_timeout_ms=0.5)
    pool = ReplicaPool(pred, batcher, replicas=2)
    pool.warmup()
    pool.start()
    rng = np.random.RandomState(0)
    feeds = [rng.randn((i % 3) + 1, 32).astype("float32")
             for i in range(requests)]

    def _request_loop():
        """One closed-loop client, a trace root per request — the HTTP
        frontend's shape without the socket noise."""
        t0 = _time.perf_counter()
        for a in feeds:
            with tracing.start_trace("serving::bench"):
                batcher.predict({"x": a}, timeout=30)
        return (_time.perf_counter() - t0) / len(feeds) * 1e6

    prev = get_flags("trace_enabled")["trace_enabled"]
    traced, untraced = [], []
    try:
        set_flags({"trace_enabled": True})
        on_us = min(_per_tree_us() for _ in range(3))
        # spans per request, measured not assumed: flag one live trace
        # so the sampler must retain it, then count its spans
        with tracing.start_trace("serving::bench_probe") as root:
            tracing.flag_current_trace("bench")
            batcher.predict({"x": feeds[0]}, timeout=30)
        payload = tracing.store().get(root.trace_id)
        spans_per_request = len(payload["spans"]) if payload else 5
        period_us = _request_loop()
        set_flags({"trace_enabled": False})
        off_us = min(_per_tree_us() for _ in range(3))
        # whole-loop A/B corroboration (alternating so drift hits both)
        for _ in range(5):
            set_flags({"trace_enabled": True})
            traced.append(_request_loop())
            set_flags({"trace_enabled": False})
            untraced.append(_request_loop())
    finally:
        set_flags({"trace_enabled": prev})
        pool.stop(drain=False)
        tracing.reset_store()
    per_span_delta_us = max(0.0, on_us - off_us) / 5.0
    overhead = per_span_delta_us * spans_per_request / period_us
    t_best, u_best = float(min(traced)), float(min(untraced))
    return {
        "metric": "tracing_overhead",
        "value": round(overhead * 100, 3),
        "unit": "percent",
        "target_pct": 2.0,
        "within_target": bool(overhead < 0.02),
        "per_span_us": {"traced": round(on_us / 5.0, 3),
                        "disabled": round(off_us / 5.0, 3),
                        "delta": round(per_span_delta_us, 3)},
        "spans_per_request": spans_per_request,
        "request_period_us": round(period_us, 1),
        "ab_corroboration": {
            "overhead_pct": round((t_best - u_best) / u_best * 100, 2),
            "traced_request_us": round(t_best, 1),
            "untraced_request_us": round(u_best, 1),
            "best_of": 5,
            "samples": {"traced": [round(v, 1) for v in traced],
                        "untraced": [round(v, 1) for v in untraced]},
        },
    }


def bench_observability_overhead(requests=160, iters_direct=20000,
                                 backends=8):
    """Labeled metric families + /fleetz merge cost (target < 2%).

    The SLO plane adds two prices. (1) The hot serving path now observes
    into LABELED histogram children (child lookup under the family lock
    plus parent propagation) where it used to observe a bare histogram —
    certified with the tracing row's discipline: the tight-loop
    per-observe delta (labeled minus bare, best-of-3) scaled by the
    labeled observes one served predict request records (queue-wait +
    e2e = 2), over the measured per-request period of a live
    batcher+replica loop. (2) The router's fleet merge — per-backend
    ``registry_snapshot()`` serialization plus the label-aware
    elementwise bucket merge across the fleet — measured directly and
    reported per scrape; it runs on the PROBER thread, so it is reported
    against the probe period, not the request period.
    """
    import tempfile
    import time as _time

    import paddle_tpu.static as static
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.monitor import (histogram, merge_histogram_snapshots,
                                    registry_snapshot)
    from paddle_tpu.serving import DynamicBatcher, ReplicaPool

    # serving-shaped bucket ladder; distinct names so the registry's
    # real serving families stay untouched
    ladder = (1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
              1000.0)
    h_bare = histogram("bench/obs_bare_ms", buckets=ladder)
    h_lab = histogram("bench/obs_labeled_ms", buckets=ladder)

    def _bare_us(n=iters_direct):
        t0 = _time.perf_counter()
        for _ in range(n):
            h_bare.observe(7.0)
        return (_time.perf_counter() - t0) / n * 1e6

    def _labeled_us(n=iters_direct):
        # the batcher resolves labels() per observe (tenant varies per
        # request), so the lookup is part of the certified price
        t0 = _time.perf_counter()
        for _ in range(n):
            h_lab.labels(kind="predict", bucket="4",
                         tenant="default").observe(7.0)
        return (_time.perf_counter() - t0) / n * 1e6

    bare_us = min(_bare_us() for _ in range(3))
    labeled_us = min(_labeled_us() for _ in range(3))
    per_observe_delta_us = max(0.0, labeled_us - bare_us)

    # live request period: same mini-model loop the tracing row uses
    static.enable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    try:
        x = static.data("x", [None, 32], "float32")
        y = static.nn.fc(static.nn.fc(x, 64, name="ob_fc1"), 8,
                         name="ob_fc2")
        exe = static.Executor()
        exe.run_startup()
        model_dir = tempfile.mkdtemp(prefix="ptpu_bench_obs_")
        static.save_inference_model(model_dir, ["x"], [y], exe)
    finally:
        static.disable_static()
        static.reset_default_programs()
    pred = create_predictor(Config(model_dir))
    batcher = DynamicBatcher(["x"], buckets=(1, 2, 4),
                             queue_capacity=64, batch_timeout_ms=0.5)
    pool = ReplicaPool(pred, batcher, replicas=2)
    pool.warmup()
    pool.start()
    rng = np.random.RandomState(0)
    feeds = [rng.randn((i % 3) + 1, 32).astype("float32")
             for i in range(requests)]
    try:
        t0 = _time.perf_counter()
        for a in feeds:
            batcher.predict({"x": a}, timeout=30)
        period_us = (_time.perf_counter() - t0) / len(feeds) * 1e6
    finally:
        pool.stop(drain=False)
    observes_per_request = 2  # predict path: queue-wait + e2e
    overhead = per_observe_delta_us * observes_per_request / period_us

    # fleet merge: one backend snapshot serialization + the label-aware
    # merge across the fleet's serving histograms (prober-thread work)
    for v in (3.0, 30.0, 300.0):
        for t in ("a", "b", "c"):
            h_lab.labels(kind="predict", bucket="4", tenant=t).observe(v)
    t0 = _time.perf_counter()
    snap_reps = 20
    for _ in range(snap_reps):
        snap = registry_snapshot()
    snapshot_us = (_time.perf_counter() - t0) / snap_reps * 1e6
    hist_snaps = {name: s for name, s in snap.items()
                  if isinstance(s, dict) and s.get("kind") == "histogram"}
    fleet = [hist_snaps] * backends
    t0 = _time.perf_counter()
    merge_reps = 20
    for _ in range(merge_reps):
        for name in hist_snaps:
            merge_histogram_snapshots([b[name] for b in fleet],
                                      name=name)
    merge_us = (_time.perf_counter() - t0) / merge_reps * 1e6
    return {
        "metric": "observability_overhead",
        "value": round(overhead * 100, 3),
        "unit": "percent",
        "target_pct": 2.0,
        "within_target": bool(overhead < 0.02),
        "per_observe_us": {"labeled": round(labeled_us, 3),
                           "bare": round(bare_us, 3),
                           "delta": round(per_observe_delta_us, 3)},
        "observes_per_request": observes_per_request,
        "request_period_us": round(period_us, 1),
        "fleet_merge": {
            "backends": backends,
            "histograms": len(hist_snaps),
            "snapshot_us": round(snapshot_us, 1),
            "merge_us": round(merge_us, 1),
            "per_scrape_us": round(snapshot_us + merge_us, 1),
        },
    }


def bench_serving_throughput(requests=120, rows_cycle=(1, 2, 3, 4),
                             levels=(1, 4, 16)):
    """Online-serving throughput: the dynamic batcher + replica pool vs
    sequential single-request Predictor calls on the same model.

    Sequential baseline: one thread, one ``Predictor.run`` per request
    (each distinct row count warmed first, so it pays per-request
    dispatch but no compiles — the OLD inference story at its best).
    Batched: an offered-load sweep — ``levels`` concurrent clients
    pushing the same request mix through the batcher — reporting
    requests/sec per level, mean batch fill, p50/p99 end-to-end latency
    from the serving histograms, and the compile accounting (bounded at
    the bucket-ladder length, asserted).

    fp32-vs-int8 sub-metric: the same model is PTQ-calibrated, saved
    through ``save_int8_model`` and driven through the same sequential
    steady-state loop — reporting int8 requests/sec, the speed ratio,
    and the max output delta vs the fp32 program (the accuracy half of
    the cost-per-token tradeoff; on the CPU smoke the speedup is noise,
    on TPU the int8 HBM/MXU savings are the point).
    """
    import tempfile

    import paddle_tpu.static as static
    from paddle_tpu import monitor, profiler, slim
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.monitor import histogram_quantile
    from paddle_tpu.serving import DynamicBatcher, ReplicaPool

    static.enable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    try:
        x = static.data("x", [None, 64], "float32")
        h = static.nn.fc(x, 512, name="serve_fc1")
        h = static.nn.fc(h, 512, name="serve_fc2")
        y = static.nn.fc(h, 8, name="serve_fc3")
        exe = static.Executor()
        exe.run_startup()
        model_dir = tempfile.mkdtemp(prefix="ptpu_bench_serve_")
        static.save_inference_model(model_dir, ["x"], [y], exe)
        # int8 twin of the same program: calibrate on the request
        # distribution, fold the scales into a deployable int8 save
        rng_cal = np.random.RandomState(7)
        calib = [{"x": rng_cal.randn(8, 64).astype("float32")}
                 for _ in range(4)]
        ptq = slim.PostTrainingQuantization(exe, static
                                            .default_main_program(), calib)
        ptq.quantize()
        int8_dir = tempfile.mkdtemp(prefix="ptpu_bench_serve_int8_")
        ptq.save_int8_model(int8_dir, ["x"], [y])
    finally:
        static.disable_static()
        static.reset_default_programs()
        static.global_scope().clear()
    pred = create_predictor(Config(model_dir))

    rng = np.random.RandomState(0)
    reqs = [rng.randn(rows_cycle[i % len(rows_cycle)], 64).astype("float32")
            for i in range(requests)]

    # -- sequential baseline (steady state: per-shape warmup first) -------
    for r in sorted(set(rows_cycle)):
        pred.run([rng.randn(r, 64).astype("float32")])
    t0 = time.perf_counter()
    fp32_outs = []
    for a in reqs:
        fp32_outs.append(np.asarray(pred.run([a])[0]))
    seq_rps = requests / (time.perf_counter() - t0)

    # -- int8 A/B on the same loop ----------------------------------------
    pred8 = create_predictor(Config(int8_dir))
    for r in sorted(set(rows_cycle)):
        pred8.run([rng.randn(r, 64).astype("float32")])
    t0 = time.perf_counter()
    int8_outs = []
    for a in reqs:
        int8_outs.append(np.asarray(pred8.run([a])[0]))
    int8_rps = requests / (time.perf_counter() - t0)
    out_scale = max(np.abs(o).max() for o in fp32_outs)
    max_delta = max(np.abs(a - b).max()
                    for a, b in zip(fp32_outs, int8_outs))

    # -- batched path through the serving stack ---------------------------
    import threading

    batcher = DynamicBatcher(["x"], buckets=(1, 2, 4, 8),
                             queue_capacity=max(64, requests),
                             batch_timeout_ms=1.0)
    pool = ReplicaPool(pred, batcher, replicas=2)
    pool.warmup()
    pool.start()
    counters0 = profiler.counters()
    sweep = []
    try:
        for level in levels:
            per_client = max(1, requests // level)

            def client(cid):
                r = np.random.RandomState(cid)
                for i in range(per_client):
                    a = r.randn(rows_cycle[i % len(rows_cycle)],
                                64).astype("float32")
                    batcher.predict({"x": a}, timeout=60)

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(level)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            sweep.append({"concurrency": level,
                          "requests": per_client * level,
                          "req_per_sec": round(per_client * level / dt, 1)})
        snap = monitor.registry_snapshot()
        rows_done = snap["serving/batched_rows_total"]["value"]
        slots = snap["serving/batch_slots_total"]["value"]
        h_e2e = monitor.histogram("serving/e2e_ms")
        best = max(s["req_per_sec"] for s in sweep)
        extra = pool.extra_compiles()
        return {
            "metric": "serving_throughput",
            "value": best,
            "unit": "requests/sec",
            "sequential_req_per_sec": round(seq_rps, 1),
            "speedup_vs_sequential": round(best / seq_rps, 3),
            "int8_ab": {
                "int8_req_per_sec": round(int8_rps, 1),
                "int8_vs_fp32": round(int8_rps / seq_rps, 3),
                "max_output_delta": round(float(max_delta), 6),
                "max_output_delta_rel": round(
                    float(max_delta / out_scale), 6),
            },
            "offered_load_sweep": sweep,
            "mean_batch_fill": round(rows_done / slots, 4) if slots else 0.0,
            "p50_ms": round(histogram_quantile(h_e2e, 0.5), 3),
            "p99_ms": round(histogram_quantile(h_e2e, 0.99), 3),
            "compiles": {
                "buckets": 4,
                "extra_after_warmup": extra,
                "jit_misses_total": profiler.counters().get(
                    "executor::jit_cache_miss", 0)
                - counters0.get("executor::jit_cache_miss", 0),
            },
        }
    finally:
        pool.stop(drain=True)
        static.global_scope().clear()


def bench_router_throughput(requests=640, rows_cycle=(1, 2, 3, 4),
                            backend_counts=(1, 2), clients_per_backend=24):
    """Serving fleet scaling: an offered-load sweep over 1 -> N
    independent backend PROCESSES behind the router, vs the same load on
    a single backend.

    Each backend is a real ``python -m paddle_tpu.serving.backend``
    subprocess (own interpreter, own XLA client, own registry) booted by
    the scaler's SubprocessLauncher, and the router runs as ITS OWN
    process too (``python -m paddle_tpu.serving.router`` — an in-bench
    router would share the client threads' GIL and cap the whole sweep
    at one core of Python) — process-level parallelism end to end, not
    the thread-level replica pool the ``serving_throughput`` row
    measures. Reports requests/sec and rows/sec per fleet size, the
    1->N speedup (the near-linear scaling acceptance), fleet p50/p99
    merged from the backends' /histz bucket counts, and per-backend
    compile accounting scraped from /loadz (each backend exactly
    len(ladder) jit misses, zero unexpected — the bounded-compile
    discipline holds per process).
    """
    import os
    import subprocess
    import tempfile
    import threading
    from urllib.request import urlopen

    import paddle_tpu.static as static
    from paddle_tpu.serving import SubprocessLauncher

    buckets = (1, 2, 4, 8)
    static.enable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    try:
        # wide enough that one backend process is genuinely compute-
        # bound well below the client side's capacity — the sweep must
        # measure BACKEND scaling, not the load generator's ceiling
        x = static.data("x", [None, 64], "float32")
        h = static.nn.fc(x, 4096, name="rt_fc1")
        h = static.nn.fc(h, 4096, name="rt_fc2")
        h = static.nn.fc(h, 4096, name="rt_fc3")
        y = static.nn.fc(h, 8, name="rt_fc4")
        exe = static.Executor()
        exe.run_startup()
        model_dir = tempfile.mkdtemp(prefix="ptpu_bench_router_")
        static.save_inference_model(model_dir, ["x"], [y], exe)
    finally:
        static.disable_static()
        static.reset_default_programs()

    from paddle_tpu.serving.scaler import launch_process

    # core layout: disjoint sets per backend (on one box XLA:CPU would
    # otherwise spread each backend's intra-op threads across EVERY
    # core and co-hosted backends would contend for the same silicon —
    # pinning emulates one host per backend, what a real fleet has),
    # the router on its own pair, the load generator on the rest.
    # Boxes too small to split run everything unpinned — the scaling
    # number is then contention-limited, but the row still runs.
    ncores = os.cpu_count() or 1
    # 2 cores per backend: small enough that neither shared DRAM
    # bandwidth (the 4096-wide weights stream from memory every
    # dispatch) nor the single-process load generator approaches its
    # ceiling before the second backend shows — measured headroom is
    # what makes the scaling number repeatable
    per = min(2, ncores // (max(backend_counts) + 1))
    cpu_sets = ([f"{i * per}-{(i + 1) * per - 1}"
                 for i in range(max(backend_counts))]
                if per >= 1 else None)
    n_backend_cores = per * max(backend_counts) if cpu_sets else 0
    router_cores = None
    orig_affinity = None
    if cpu_sets and ncores > n_backend_cores + 2:
        router_cores = f"{n_backend_cores}-{n_backend_cores + 1}"
        try:
            orig_affinity = os.sched_getaffinity(0)
            os.sched_setaffinity(
                0, set(range(n_backend_cores + 2, ncores)))
        except (AttributeError, OSError):
            orig_affinity = None
    launcher = SubprocessLauncher(model_dir, buckets=buckets,
                                  batch_timeout_ms=1.0, replicas=2,
                                  queue_capacity=max(64, requests),
                                  cpu_sets=cpu_sets, env=CPU_CHILD_ENV)

    def spawn_router(urls):
        """Router as its own process (shared launch_process recipe:
        PYTHONPATH, port-file-when-ready, taskset); (proc, url)."""
        args = ["--probe-interval-s", "1.0"]
        for u in urls:
            args += ["--backend", u]
        h = launch_process("paddle_tpu.serving.router", args,
                           cpus=router_cores, startup_timeout_s=120)
        return h.proc, h.url

    payloads = []
    rng = np.random.RandomState(0)
    for i in range(max(requests // (clients_per_backend
                            * max(backend_counts)), 1)):
        rows = rows_cycle[i % len(rows_cycle)]
        payloads.append(json.dumps({
            "inputs": rng.randn(rows, 64).astype("float32").tolist()
        }).encode())
    rows_per_client = sum(
        rows_cycle[i % len(rows_cycle)] for i in range(len(payloads)))

    sweep = []
    try:
        for n in backend_counts:
            # WEAK scaling: offered load grows with the fleet (a fleet
            # exists because traffic grew) — a fixed closed-loop client
            # count would hand each fleet backend a shallower queue and
            # worse batch fill than the solo baseline enjoyed, and the
            # sweep would measure that artifact, not capacity
            clients = clients_per_backend * n
            handles = [launcher.launch() for _ in range(n)]
            rproc, rurl = spawn_router([h.url for h in handles])
            try:
                failures = []
                from http.client import HTTPConnection
                from urllib.parse import urlsplit

                ru = urlsplit(rurl)
                # all clients connect + warm OUTSIDE the timed window
                # (a closed-loop sweep otherwise times its own
                # ramp-up), then release together per trial
                barrier = None

                def post_one(conn, body):
                    try:
                        conn.request("POST", "/predict", body=body,
                                     headers={"Content-Type":
                                              "application/json"})
                        resp = conn.getresponse()
                        resp.read()
                        if resp.status != 200:
                            failures.append(f"HTTP {resp.status}")
                        if resp.will_close:
                            conn.close()
                            conn = HTTPConnection(ru.hostname, ru.port,
                                                  timeout=60)
                    except Exception as e:  # noqa: BLE001
                        failures.append(repr(e))
                        conn.close()
                        conn = HTTPConnection(ru.hostname, ru.port,
                                              timeout=60)
                    return conn

                def client(cid):
                    # keep-alive load generator: one persistent
                    # connection per client (a connection-per-request
                    # generator measures TCP/thread churn, not the
                    # fleet)
                    conn = HTTPConnection(ru.hostname, ru.port,
                                          timeout=60)
                    try:
                        for body in payloads[:2]:  # untimed warmup
                            conn = post_one(conn, body)
                        barrier.wait()
                        for body in payloads:
                            conn = post_one(conn, body)
                    finally:
                        conn.close()

                # best-of-2 timed trials (the deeply saturated
                # closed loop is noisy at the few-percent level; the
                # ratio of two levels doubles that)
                dts = []
                for _trial in range(2):
                    barrier = threading.Barrier(clients + 1)
                    threads = [threading.Thread(target=client,
                                                args=(c,))
                               for c in range(clients)]
                    for t in threads:
                        t.start()
                    barrier.wait()
                    t0 = time.perf_counter()
                    for t in threads:
                        t.join()
                    dts.append(time.perf_counter() - t0)
                    assert not failures, failures[:3]
                dt = min(dts)
                per_backend = []
                for h in handles:
                    lz = json.loads(urlopen(h.url + "/loadz").read())
                    assert lz["compiles"]["jit_misses"] == len(buckets), lz
                    assert lz["compiles"]["unexpected"] == 0, lz
                    per_backend.append({
                        "url": h.url,
                        "compiles": lz["compiles"],
                        "mean_fill": lz["mean_fill"],
                    })
                sz = json.loads(urlopen(rurl + "/statz").read())
                assert (sz["fleet"]["requests"]
                        >= len(payloads) * clients), sz["fleet"]
                merged = sz["latency"]["backends_merged"][
                    "serving/e2e_ms"]
                total = len(payloads) * clients
                sweep.append({
                    "backends": n,
                    "requests": total,
                    "req_per_sec": round(total / dt, 1),
                    "rows_per_sec": round(
                        rows_per_client * clients / dt, 1),
                    "p50_ms": merged["p50_ms"],
                    "p99_ms": merged["p99_ms"],
                    "per_backend": per_backend,
                })
            finally:
                rproc.terminate()
                try:
                    rproc.wait(15)
                except subprocess.TimeoutExpired:
                    rproc.kill()
                for h in handles:
                    launcher.terminate(h, drain=True)
    finally:
        if orig_affinity is not None:
            # the affinity squeeze is sweep-local: the remaining bench
            # rows must see the whole machine again
            try:
                os.sched_setaffinity(0, orig_affinity)
            except OSError:
                pass
    base = sweep[0]["req_per_sec"]
    best = sweep[-1]
    return {
        "metric": "router_throughput",
        "platform": "cpu",  # backend processes are CPU-pinned
        "value": best["req_per_sec"],
        "unit": "requests/sec",
        "scaling_vs_one_backend": round(best["req_per_sec"] / base, 3),
        "scaling_target": 1.6,
        "offered_load_sweep": sweep,
        "compiles_per_backend_expected": len(buckets),
    }


def bench_decode_throughput(requests=16, slots=4, cache_len=64,
                            prefill_buckets=(8, 16)):
    """Generative decoding: continuous batching vs static batching on a
    mixed-length request sweep.

    Static baseline: requests grouped into batches of ``slots``; a group
    runs until its LONGEST member finishes (finished slots idle — the
    tear-down-and-reassemble serving model). Continuous: a finished
    sequence vacates its slot mid-batch and the next request is admitted
    at the next step, so slots stay full across the same sweep. Both run
    the SAME engine (same compiled prefill/decode programs); the only
    variable is slot turnover. Reports per-chip tokens/sec, per-token
    latency, the continuous/static speedup, compile accounting (exactly
    len(prefill ladder) + 1 programs), and decode MFU from the
    cost-model ledger.

    KV-cache economics sub-metric: the same sweep re-runs on an int8-KV
    engine (``FLAGS_generation_kv_cache_dtype=int8`` semantics) over the
    same weights — reporting ``kv_bytes_per_token`` per mode and
    ``slots_at_equal_hbm`` (how many int8 slots the fp32 cache's HBM
    buys, measured on the real cache arrays), the capacity multiplier
    decode capacity is bound by.
    """
    import paddle_tpu as paddle
    from paddle_tpu import monitor, profiler
    from paddle_tpu.generation import COMPILE_COUNTER, GenerationEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.monitor import cost_model as _cost

    paddle.seed(0)
    cfg = GPTConfig(
        vocab_size=512, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=256,
        max_position_embeddings=256, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, attention_window=cache_len)
    model = GPTForCausalLM(cfg)
    engine = GenerationEngine(model, slots=slots, cache_len=cache_len,
                              prefill_buckets=prefill_buckets)
    c0 = profiler.counters().get(COMPILE_COUNTER, 0)
    engine.warmup()
    warm_compiles = profiler.counters().get(COMPILE_COUNTER, 0) - c0

    # mixed sweep: short and long generations interleaved — the case
    # where static batching pays max(budget) per group
    rng = np.random.RandomState(1)
    prompts = [list(map(int, rng.randint(3, 500, size=int(n))))
               for n in rng.randint(2, prefill_buckets[-1] + 1,
                                    size=requests)]
    budgets = [int(b) for b in rng.randint(4, 33, size=requests)]

    # drive the engine primitives directly for BOTH modes so the
    # comparison is pure scheduling policy (no HTTP/thread noise);
    # static admits a new group only once EVERY slot has drained
    from collections import deque

    def drive(eng, continuous):
        pending = deque(zip(prompts, budgets))
        active = {}
        last = np.zeros(slots, np.int32)
        temps = np.zeros(slots, np.float32)
        done_tokens = 0
        steps = 0
        t0 = time.perf_counter()
        while pending or active:
            can_admit = bool(pending) and (continuous or not active)
            while can_admit and pending and len(active) < slots:
                free = next(s for s in range(slots) if s not in active)
                p, b = pending.popleft()
                tok = eng.admit(free, p)
                done_tokens += 1
                if b <= 1:
                    continue
                active[free] = b - 1
                last[free] = tok
            if not active:
                continue
            if eng.speculative:
                # one draft+verify round emits 1..k+1 tokens per slot
                # (truncated at each request's budget, the scheduler
                # semantics)
                nxt, counts = eng.spec_step(last, temps,
                                            busy=list(active))
                steps += 1
                for s in list(active):
                    take = min(int(counts[s]), active[s])
                    done_tokens += take
                    last[s] = nxt[s, take - 1]
                    active[s] -= take
                    if active[s] <= 0:
                        del active[s]
                continue
            nxt = eng.step(last, temps)
            steps += 1
            for s in list(active):
                done_tokens += 1
                last[s] = nxt[s]
                active[s] -= 1
                if active[s] <= 0:
                    del active[s]
        dt = time.perf_counter() - t0
        return done_tokens, steps, dt

    flops0 = monitor.registry_snapshot().get(
        "cost/executed_flops", {}).get("value", 0.0)
    static_tokens, static_steps, static_dt = drive(engine, continuous=False)
    cont_tokens, cont_steps, cont_dt = drive(engine, continuous=True)
    executed = (monitor.registry_snapshot().get(
        "cost/executed_flops", {}).get("value", 0.0) - flops0)
    assert static_tokens == cont_tokens, "both modes decode the sweep"
    extra = engine.extra_compiles()

    # -- int8 KV cache on the same sweep (after the fp32 accounting
    # closes: the int8 engine's own warmup compiles and drive FLOPs must
    # not pollute the fp32 row's extra-compile/MFU numbers) -------------
    fp32_cache_bytes = engine.cache_nbytes()
    engine8 = GenerationEngine(model, slots=slots, cache_len=cache_len,
                               prefill_buckets=prefill_buckets,
                               kv_cache_dtype="int8")
    engine8.warmup()
    int8_tokens, int8_steps, int8_dt = drive(engine8, continuous=True)
    assert int8_tokens == cont_tokens, "int8 KV decodes the same sweep"
    assert engine8.extra_compiles() == 0, "int8 decode stays compile-bound"
    int8_cache_bytes = engine8.cache_nbytes()
    slots_at_equal_hbm = int(slots * fp32_cache_bytes / int8_cache_bytes)
    peaks = _cost.device_peaks()
    cont_tps = cont_tokens / cont_dt
    static_tps = static_tokens / static_dt

    # -- speculative decoding on the same sweep (after everything
    # above closes its accounting): a 1-layer truncated draft proposes
    # k tokens, the target verifies k+1 in one batched forward — the
    # decode-is-serial lever. Greedy budgets make the sweep token-count
    # identical; the per-k engine is warmed LAST so its extra_compiles
    # reads exactly its own steady state. ---------------------------------
    from paddle_tpu.models import truncated_draft

    draft = truncated_draft(model, num_layers=1)
    speculative = {"draft_layers": 1}
    for k in (2, 4):
        eng_k = GenerationEngine(model, slots=slots, cache_len=cache_len,
                                 prefill_buckets=prefill_buckets,
                                 draft_model=draft, draft_k=k)
        warm0 = profiler.counters().get(COMPILE_COUNTER, 0)
        eng_k.warmup()
        warm_k = profiler.counters().get(COMPILE_COUNTER, 0) - warm0
        assert warm_k == eng_k.expected_compiles(), (
            warm_k, eng_k.expected_compiles())
        spec_tokens, spec_rounds, spec_dt = drive(eng_k, continuous=True)
        assert spec_tokens == cont_tokens, \
            "speculative decodes the same sweep"
        assert eng_k.extra_compiles() == 0, \
            "speculative decode stays compile-bound"
        stats = eng_k.spec_stats()
        spec_tps = spec_tokens / spec_dt
        speculative[f"k{k}"] = {
            "tokens_per_sec": round(spec_tps, 1),
            "ms_per_token": round(1e3 * spec_dt / spec_tokens, 3),
            "rounds": spec_rounds,
            "acceptance_rate": stats["acceptance_rate"],
            "vs_plain_tokens_per_sec": round(spec_tps / cont_tps, 3),
            "warmup_compiles": warm_k,
        }
    return {
        "metric": "decode_throughput",
        "value": round(cont_tps, 1),
        "unit": "tokens/sec",
        "requests": requests,
        "slots": slots,
        "tokens_generated": cont_tokens,
        "continuous": {
            "tokens_per_sec": round(cont_tps, 1),
            "decode_steps": cont_steps,
            "ms_per_token": round(1e3 * cont_dt / cont_tokens, 3),
        },
        "static": {
            "tokens_per_sec": round(static_tps, 1),
            "decode_steps": static_steps,
            "ms_per_token": round(1e3 * static_dt / static_tokens, 3),
        },
        "speedup_continuous_vs_static": round(cont_tps / static_tps, 3),
        "speculative": speculative,
        "kv_cache": {
            "fp32_bytes_per_token": engine.kv_bytes_per_token(),
            "int8_bytes_per_token": engine8.kv_bytes_per_token(),
            "fp32_cache_bytes": fp32_cache_bytes,
            "int8_cache_bytes": int8_cache_bytes,
            "slots_at_equal_hbm": slots_at_equal_hbm,
            "int8_tokens_per_sec": round(int8_tokens / int8_dt, 1),
            "int8_vs_fp32_tokens_per_sec": round(
                (int8_tokens / int8_dt) / cont_tps, 3),
        },
        "compiles": {
            "warmup": warm_compiles,
            "expected": len(prefill_buckets) + 1,
            "extra_after_warmup": extra,
        },
        "mfu_decode": round(
            _cost.mfu(executed / (static_dt + cont_dt), peaks), 6),
        "device_kind": peaks.get("kind"),
    }


def bench_paged_kv(cache_len=64, page_size=4,
                   prefill_buckets=(4, 8, 16, 32, 48, 64), slots=4):
    """Paged KV cache vs the contiguous ring on three axes.

    Shared-prefix sweep (the tentpole economics): requests repeating a
    templated prefix at 0/25/50/75/90/95% of the prompt admit through
    the radix prefix index — matched full pages are retained (CoW
    shared), and only the unmatched suffix is prefilled, in the
    smallest bucket that holds it. Per ratio the row reports the
    measured per-tenant hit rate, the prefill-FLOPs-saved fraction
    ``1 - suffix_bucket/full_bucket`` (program-size accounting — on a
    bucketed ladder the saving is exactly the bucket shrink), and
    measured TTFT (admit wall time), which must scale down together.

    Capacity: the SAME mixed short/long sweep that needs ``slots`` full
    ring windows runs token-identically on a page pool 1.6x smaller —
    short requests hold only the pages they touch and idle prefix-cache
    pages evict under pressure — i.e. >= 1.3x slots at equal HBM.

    Parity: every paged row above decodes the ring engine's exact
    greedy tokens, at exactly len(prefill ladder) + 1 compiled programs
    (the unified full/suffix prefill is ONE program per bucket;
    ``shared_len`` is a traced scalar, not a shape).
    """
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.generation import COMPILE_COUNTER, GenerationEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(
        vocab_size=512, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=256,
        max_position_embeddings=256, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, attention_window=cache_len)
    model = GPTForCausalLM(cfg)
    ring = GenerationEngine(model, slots=slots, cache_len=cache_len,
                            prefill_buckets=prefill_buckets)
    ring.warmup()
    eng = GenerationEngine(model, slots=slots, cache_len=cache_len,
                           prefill_buckets=prefill_buckets,
                           kv_cache_layout="paged",
                           kv_page_size=page_size)
    c0 = profiler.counters().get(COMPILE_COUNTER, 0)
    eng.warmup()
    warm_compiles = profiler.counters().get(COMPILE_COUNTER, 0) - c0

    # -- parity: mixed burst decodes the ring's exact greedy tokens ----
    rng = np.random.RandomState(7)
    mixed = [list(map(int, rng.randint(3, 500, size=n)))
             for n in (6, 48, 3, 40, 12, 30, 7, 24)]
    want = ring.generate(mixed, max_new_tokens=8, temperature=0.0)
    got = eng.generate(mixed, max_new_tokens=8, temperature=0.0)
    assert got == want, "paged layout diverged from the ring goldens"
    assert eng.extra_compiles() == 0, "paged burst must stay compile-bound"

    # -- shared-prefix sweep: hit rate, FLOPs saved, TTFT per ratio ----
    def bucket_for(n):
        return next(b for b in prefill_buckets if b >= max(n, 1))

    full = prefill_buckets[-1]
    sweep = []
    for share in (0.0, 0.25, 0.5, 0.75, 0.9, 0.95):
        shared_n = int(share * full) // page_size * page_size
        prefix = list(map(int, rng.randint(3, 500, size=shared_n)))
        tenant = f"share{int(share * 100)}"
        ttfts = []
        for _ in range(4):  # 1 cold admit populates the index + 3 warm
            req = prefix + list(map(int, rng.randint(
                3, 500, size=full - shared_n)))
            t0 = time.perf_counter()
            eng.admit(0, req, 0.0, tenant=tenant)
            ttfts.append(time.perf_counter() - t0)
            eng.release_slot(0)
        st = eng.paging_stats()["per_tenant"][tenant]
        suffix_bucket = bucket_for(full - shared_n)
        sweep.append({
            "share": share,
            "shared_tokens": shared_n,
            "measured_hit_rate": st["hit_rate"],
            "suffix_bucket": suffix_bucket,
            "prefill_flops_saved": round(1.0 - suffix_bucket / full, 4),
            "ttft_cold_ms": round(1e3 * ttfts[0], 3),
            "ttft_reused_ms": round(
                1e3 * sorted(ttfts[1:])[len(ttfts[1:]) // 2], 3),
        })
    assert eng.extra_compiles() == 0, (
        "suffix prefill recompiled; shared_len must be traced")
    extra = eng.extra_compiles()  # before the cap engine's own warmup
    index = eng.paging_stats()["prefix_index"]

    # -- slots at equal HBM: the mixed sweep on a 1.6x-smaller pool ----
    ring_equiv_pages = slots * (cache_len // page_size)
    pool_pages = int(ring_equiv_pages / 1.6)
    cap = GenerationEngine(model, slots=slots, cache_len=cache_len,
                           prefill_buckets=prefill_buckets,
                           kv_cache_layout="paged",
                           kv_page_size=page_size,
                           kv_pool_pages=pool_pages)
    cap.warmup()
    got_cap = cap.generate(mixed, max_new_tokens=8, temperature=0.0)
    assert got_cap == want, "mixed burst diverged on the constrained pool"
    assert cap.extra_compiles() == 0, (
        "constrained pool must not change the compiled programs' count")
    cap_stats = cap.paging_stats()
    slots_ratio = ring_equiv_pages / pool_pages
    return {
        "metric": "paged_kv",
        "value": round(slots_ratio, 3),
        "unit": "x_slots_at_equal_hbm",
        "page_size": page_size,
        "cache_len": cache_len,
        "parity_prompts": len(mixed),
        "shared_prefix_sweep": sweep,
        "prefix_index": {
            "lookups": index["lookups"],
            "hits": index["hits"],
            "hit_rate": index["hit_rate"],
            "evictions": index["evictions"],
        },
        "slots_at_equal_hbm": {
            "ring_equiv_pages": ring_equiv_pages,
            "pool_pages": pool_pages,
            "peak_pages_used": cap_stats["peak_pages_used"],
            "cow_copies": cap_stats["cow_copies"],
            "ratio": round(slots_ratio, 3),
        },
        "compiles": {
            "warmup": warm_compiles,
            "expected": len(prefill_buckets) + 1,
            "extra_after_warmup": extra,
        },
        "kv_bytes_per_token": eng.kv_bytes_per_token(),
        "page_nbytes": eng.page_nbytes(),
    }


def bench_disagg_fleet(requests=36, clients=12):
    """Disaggregated prefill/decode fleet vs a unified fleet at EQUAL
    backend count (2 processes each) on a mixed prompt-length sweep.

    Unified: two ``--kind generate`` backends, each splitting its slots
    between serving decode steps and running its own prefills. Disagg:
    one ``--kind prefill`` backend (all compute on the bucket-ladder
    forward, ships KV slabs) + one ``--kind decode`` backend whose
    capacity is ALL decode slots — the asymmetry disaggregation buys:
    prefill scales on compute, decode on HBM, so the decode tier
    dedicates its whole memory budget to slots (2x the unified fleet's
    total here) where a unified backend must also hold prefill
    activations and share its loop between the two phases. The router
    (its own process, like the backends) orchestrates the prompt ->
    slab -> decode handoff. The offered load oversubscribes the
    unified fleet's slots (clients > unified slots), which is where
    the slot-wait tail lives.

    Clients stream (``"stream": true``) so TTFT is measured CLIENT-side
    — submit to first token line through the router, the number a user
    sees — under long-budget background generations that keep decode
    slots busy: the unified fleet's p99 arrival waits for a slot on a
    loop that is also prefilling, the disaggregated fleet's waits only
    on the dedicated decode tier. Reports TTFT p50/p99 and
    tokens/sec(/chip) per fleet shape, with per-backend compile
    accounting asserted from /loadz (zero unexpected on every process
    — the handoff path compiles nothing).
    """
    import json as _json
    import signal as _signal
    import tempfile
    import threading
    from urllib.request import Request, urlopen

    import paddle_tpu as paddle
    from paddle_tpu.models import (
        GPTConfig,
        GPTForCausalLM,
        save_gpt_model,
    )
    from paddle_tpu.serving.scaler import launch_process

    cache_len = 64
    buckets = "16,64"
    paddle.seed(7)
    cfg = GPTConfig(
        vocab_size=512, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=256,
        max_position_embeddings=256, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, attention_window=cache_len)
    gpt_dir = tempfile.mkdtemp(prefix="ptpu_bench_disagg_")
    save_gpt_model(GPTForCausalLM(cfg), gpt_dir)

    rng = np.random.RandomState(3)
    prompts = [list(map(int, rng.randint(3, 500, size=int(n))))
               for n in rng.randint(8, 65, size=requests)]
    budgets = [int(b) for b in rng.randint(24, 65, size=requests)]

    def boot_backend(kind, slots):
        args = ["--kind", kind, "--gpt-dir", gpt_dir,
                "--cache-len", str(cache_len),
                "--prefill-buckets", buckets,
                "--slots", str(slots),
                "--queue-capacity", "64"]
        return launch_process("paddle_tpu.serving.backend", args,
                              env=CPU_CHILD_ENV, startup_timeout_s=180)

    def boot_router(urls):
        args = ["--probe-interval-s", "0.5"]
        for u in urls:
            args += ["--backend", u]
        return launch_process("paddle_tpu.serving.router", args,
                              startup_timeout_s=120)

    def run_fleet(shape):
        if shape == "unified":
            backends = [boot_backend("generate", 3),
                        boot_backend("generate", 3)]
        else:
            backends = [boot_backend("prefill", 1),
                        boot_backend("decode", 14)]
        router = boot_router([b.url for b in backends])
        ttfts, tokens_out, errs = [], [0], []
        lock = threading.Lock()
        work = list(zip(prompts, budgets))

        def client(idx):
            for i in range(idx, len(work), clients):
                p, b = work[i]
                body = _json.dumps({
                    "prompt": p, "max_new_tokens": b,
                    "temperature": 0.0, "stream": True}).encode()
                t0 = time.perf_counter()
                try:
                    r = urlopen(Request(
                        router.url + "/generate", data=body,
                        headers={"Content-Type": "application/json"}),
                        timeout=300)
                    first = None
                    n = 0
                    for line in r:
                        msg = _json.loads(line)
                        if "token" in msg:
                            if first is None:
                                first = time.perf_counter() - t0
                            n += 1
                        if "error" in msg:
                            raise RuntimeError(msg["error"])
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errs.append(f"{type(e).__name__}: {e}")
                    continue
                with lock:
                    if first is not None:
                        ttfts.append(first * 1e3)
                    tokens_out[0] += n

        try:
            # settle the prober's kind map before offering load
            time.sleep(1.5)
            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            assert not errs, errs[:3]
            assert len(ttfts) == requests, (len(ttfts), requests)
            # per-process compile accounting: the handoff path must
            # compile NOTHING beyond each kind's warmup set
            compiles = {}
            for b in backends:
                lz = _json.loads(urlopen(b.url + "/loadz",
                                         timeout=10).read())
                assert lz["compiles"]["unexpected"] == 0, (b.url, lz)
                compiles[lz["kind"]] = lz["compiles"]
            ttfts.sort()
            return {
                "ttft_p50_ms": round(ttfts[len(ttfts) // 2], 1),
                "ttft_p99_ms": round(ttfts[min(len(ttfts) - 1, int(
                    len(ttfts) * 0.99))], 1),
                "tokens_per_sec": round(tokens_out[0] / wall, 1),
                "tokens_per_sec_per_chip": round(
                    tokens_out[0] / wall / len(backends), 1),
                "backends": len(backends),
                "compiles": compiles,
            }
        finally:
            for h in [router] + backends:
                try:
                    h.proc.send_signal(_signal.SIGTERM)
                except OSError:
                    pass
            for h in [router] + backends:
                try:
                    h.proc.wait(20)
                except Exception:  # noqa: BLE001
                    h.proc.kill()

    unified = run_fleet("unified")
    disagg = run_fleet("disagg")
    return {
        "metric": "disagg_fleet",
        "platform": "cpu",  # backend processes are CPU-pinned
        "value": disagg["ttft_p99_ms"],
        "unit": "ms (ttft p99, disaggregated)",
        "requests": requests,
        "clients": clients,
        "unified": unified,
        "disaggregated": disagg,
        "ttft_p99_disagg_vs_unified": round(
            disagg["ttft_p99_ms"] / unified["ttft_p99_ms"], 3),
    }


def bench_checkpoint_overhead(steps=150, every=25):
    """Async-checkpoint cost on the training step path.

    The preemption-tolerance contract (ISSUE 8 / ROADMAP item 5) is only
    free if snapshotting does not slow training: the capture is a
    device-side copy of the state pytree (donation-safe) dispatched
    async; serialize + fsync + atomic publish run on the background
    writer thread. This row runs the same deterministic train loop three
    ways — no checkpointing, a BLOCKING save every ``every`` steps (the
    reference's save-on-the-step-path behavior), and the async path —
    and reports the step-loop overhead of each vs the no-checkpoint
    baseline. Target: async < 2% (the blocking column is the price it
    replaces). The drain (wait for the last writes after the loop) is
    reported separately — it overlaps training everywhere except the
    final step.
    """
    import shutil
    import tempfile

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as popt
    from paddle_tpu.distributed import checkpoint as ckpt
    from paddle_tpu.framework import jit as fjit

    # compute-heavy, state-light: large batch over a narrow MLP keeps the
    # step on the XLA compute path for milliseconds while the snapshot
    # payload stays ~300KB — the realistic regime (any sane checkpoint
    # interval makes save bytes tiny next to inter-save compute; on real
    # accelerators the step doesn't even share cores with the writer)
    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(96, 96)
            self.fc2 = nn.Linear(96, 96)
            self.fc3 = nn.Linear(96, 16)

        def forward(self, x):
            return self.fc3(F.relu(self.fc2(F.relu(self.fc1(x)))))

    def loss_fn(m, x, y):
        return F.cross_entropy(m(x), y).mean()

    rng = np.random.RandomState(0)
    X = rng.randn(2048, 96).astype("float32")
    Y = rng.randint(0, 16, (2048,)).astype("int64")

    def build():
        paddle.seed(11)
        m = MLP()
        o = popt.Adam(learning_rate=0.01, parameters=m.parameters())
        return fjit.train_step(m, o, loss_fn)

    def run(mode, outdir):
        step = build()
        step(X, Y)  # compile outside the timed window
        saves = 0
        t0 = time.perf_counter()
        m = None
        for s in range(steps):
            m = step(X, Y)
            if mode != "none" and (s + 1) % every == 0:
                step.save_checkpoint(
                    f"{outdir}/step_{s}", step=s, keep=2,
                    async_=(mode == "async"))
                saves += 1
        loss = float(np.asarray(m["loss"]))  # value fetch = barrier
        loop_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        ckpt.wait_pending()
        drain_s = time.perf_counter() - t1
        return loop_s, drain_s, saves, loss

    root = tempfile.mkdtemp(prefix="ptpu_ckpt_bench_")
    try:
        # interleave arms best-of-3 so machine noise hits all three alike
        best = {"none": None, "blocking": None, "async": None}
        for _ in range(3):
            for mode in best:
                out = run(mode, f"{root}/{mode}")
                if best[mode] is None or out[0] < best[mode][0]:
                    best[mode] = out
        base_s, _, _, loss_none = best["none"]
        blk_s, _, n_saves, loss_blk = best["blocking"]
        asn_s, drain_s, _, loss_asn = best["async"]
        asn_pct = (asn_s - base_s) / base_s * 100.0
        blk_pct = (blk_s - base_s) / base_s * 100.0
        assert abs(loss_blk - loss_none) < 1e-6  # snapshots don't perturb
        assert abs(loss_asn - loss_none) < 1e-6

        # direct decomposition (monitor_overhead discipline): the step
        # path pays exactly the capture+submit of save_checkpoint — time
        # it in isolation and amortize over the save interval. The
        # whole-loop A/B above corroborates but swings with box noise;
        # this number is what the <2% contract is gated on.
        step = build()
        step(X, Y)
        step.save_checkpoint(f"{root}/direct/warm", step=0, async_=True)
        ckpt.wait_pending()
        t0 = time.perf_counter()
        for i in range(20):
            step.save_checkpoint(f"{root}/direct/s{i}", step=i,
                                 async_=True)
        capture_ms = (time.perf_counter() - t0) / 20 * 1e3
        ckpt.wait_pending()
        step_ms = base_s / steps * 1e3
        direct_pct = capture_ms / (every * step_ms) * 100.0
        return {
            "metric": "checkpoint_step_overhead_pct",
            "value": round(direct_pct, 3),
            "unit": "% of step time (capture+submit / save interval)",
            "steps": steps,
            "save_every": every,
            "saves": n_saves,
            "capture_submit_ms": round(capture_ms, 3),
            "baseline_steps_per_sec": round(steps / base_s, 1),
            "async_steps_per_sec": round(steps / asn_s, 1),
            "blocking_steps_per_sec": round(steps / blk_s, 1),
            "loop_async_overhead_pct": round(asn_pct, 3),
            "loop_blocking_overhead_pct": round(blk_pct, 3),
            "async_drain_ms": round(drain_s * 1e3, 3),
            "target_met": bool(direct_pct < 2.0),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_fused_kernels(iters=150, overlap_batches=40):
    """Fused-kernel + input-overlap A/B (the ResNet-gap levers).

    Three decompositions, each fused-vs-unfused on the SAME math (the
    fused jnp fallback is bit-identical, so off-TPU the ratio measures
    XLA's fusion of both forms and should sit near 1.0; on TPU the
    fused side runs the pallas kernels):

    - ``optimizer_update``: one Momentum(+wd) update over a ResNet-ish
      parameter set, µs/step tight-loop A/B (jitted, value-fetch
      barrier) — the kernel's one-VMEM-pass claim.
    - ``layernorm_residual``: the post-norm transformer's add+norm pair
      at BERT-base shape, fused op vs the two-op chain.
    - ``conv_bn_relu``: the ResNet triple at a mid-stage shape, the
      fused pallas dispatch vs the unfused conv2d->batch_norm->relu op
      chain (off-TPU both run the identical jnp sequence, ratio ~1.0).
    - ``autotune``: tuned-vs-default µs per kernel from a live
      best-of-N schedule search (save=False — the bench never mutates
      the process's tuning cache), the ROADMAP item-3 evidence row.
    - ``train_loop``: whole-loop corroboration — compiled Momentum
      steps on a small conv net with the flags on vs off (numerics
      asserted identical; wall-clock ratio is the honest end-to-end
      answer, noisier than the micro rows).

    Plus ``input_overlap``: the monitor's input-wait accounting driven
    through ``_DevicePrefetcher`` with a deliberately slow source and a
    fixed consumer step, overlap off vs on — the before/after
    input-wait ratio is the proof the H2D/parse work left the step
    path.
    """
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as popt
    from paddle_tpu.flags import get_flags, set_flags
    from paddle_tpu.framework import jit as fjit
    from paddle_tpu.framework.tensor import to_tensor
    from paddle_tpu.ops.pallas import fused_momentum_update

    import jax

    def _best_us(fn, *args, n=5):
        fn(*args)  # warm/compile
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    import jax.numpy as jnp_mod

    rng = np.random.RandomState(0)

    # -- optimizer update µs/step -----------------------------------------
    shapes = [(256, 256)] * 6 + [(1024, 256)] * 2 + [(1024,)] * 4
    params = [jnp_mod.asarray(rng.randn(*s).astype("f4")) for s in shapes]
    grads = [jnp_mod.asarray(rng.randn(*s).astype("f4")) for s in shapes]
    vels = [jnp_mod.asarray(np.zeros(s, "f4")) for s in shapes]

    def fused_all(ps, gs, vs, lr):
        out = [fused_momentum_update(p, g, v, lr, 0.9, 1e-4)
               for p, g, v in zip(ps, gs, vs)]
        return [o[0] for o in out], [o[1] for o in out]

    def unfused_all(ps, gs, vs, lr):
        new_p, new_v = [], []
        for p, g, v in zip(ps, gs, vs):
            g = g + 1e-4 * p
            v = 0.9 * v + g
            new_p.append(p - lr * v)
            new_v.append(v)
        return new_p, new_v

    lr = jnp_mod.asarray(0.1, jnp_mod.float32)
    opt_fused_us = _best_us(jax.jit(fused_all), params, grads, vels, lr)
    opt_unfused_us = _best_us(jax.jit(unfused_all), params, grads, vels, lr)

    # -- layernorm+residual µs/step ----------------------------------------
    from paddle_tpu.ops.pallas import layernorm_residual as _lnr_fn

    h = 768
    x = jnp_mod.asarray(rng.randn(8, 128, h).astype("f4"))
    res = jnp_mod.asarray(rng.randn(8, 128, h).astype("f4"))
    w = jnp_mod.asarray(np.ones(h, "f4"))
    b = jnp_mod.asarray(np.zeros(h, "f4"))

    def unfused_ln(x, res, w, b):
        a = x + res
        mean = jnp_mod.mean(a, axis=-1, keepdims=True)
        var = jnp_mod.var(a, axis=-1, keepdims=True)
        return (a - mean) * jax.lax.rsqrt(var + 1e-5) * w + b

    ln_fused_us = _best_us(
        jax.jit(lambda x, res, w, b: _lnr_fn(x, res, w, b, 1e-5)),
        x, res, w, b)
    ln_unfused_us = _best_us(jax.jit(unfused_ln), x, res, w, b)

    # -- conv+bn+relu µs/step (the ResNet triple) --------------------------
    import sys as _sys

    from paddle_tpu.ops.pallas import conv_bn_relu as _cbr_fn  # noqa: F401

    _cbr = _sys.modules["paddle_tpu.ops.pallas.conv_bn_relu"]
    xc = jnp_mod.asarray(rng.randn(8, 64, 16, 16).astype("f4"))
    wc = jnp_mod.asarray(rng.randn(128, 64, 3, 3).astype("f4") * 0.05)
    gam = jnp_mod.asarray(np.ones(128, "f4"))
    bet = jnp_mod.asarray(np.zeros(128, "f4"))
    rmean = jnp_mod.asarray(np.zeros(128, "f4"))
    rvar = jnp_mod.asarray(np.ones(128, "f4"))
    cbr_kw = dict(stride=1, padding=1, training=True, momentum=0.9,
                  eps=1e-5, data_format="NCHW")
    cbr_fused_us = _best_us(
        jax.jit(lambda x, w: _cbr._fused(x, w, gam, bet, rmean, rvar,
                                         **cbr_kw)[0]), xc, wc)
    cbr_unfused_us = _best_us(
        jax.jit(lambda x, w: _cbr._reference(x, w, gam, bet, rmean, rvar,
                                             **cbr_kw)[0]), xc, wc)

    # -- autotune sub-row: tuned-vs-default µs per kernel ------------------
    # a real (small) offline search per kernel, save=False so the bench
    # never mutates the process's tuning cache; on TPU these time the
    # pallas kernels, on CPU the interpret-mode pipeline (selection
    # logic identical, absolute numbers nominal)
    from paddle_tpu import tuning as _tuning

    autotune = {}
    tuner = _tuning.KernelTuner(measure_n=3)
    for kernel, info, cands in (
        ("layernorm_residual",
         dict(rows=256, h=512, dtype="float32"),
         [{"block_r": 16}, {"block_r": 64}, {"block_r": 256}]),
        ("conv_bn_relu",
         dict(m=512, k=64, c=128, dtype="float32"),
         [{"tile_m": 64}, {"tile_m": 256}]),
    ):
        try:
            r = tuner.tune(kernel, candidates=cands, save=False, **info)
            autotune[kernel] = {
                "tuned_us": round(r.best_us, 1),
                "default_us": (round(r.default_us, 1)
                               if r.default_us is not None else None),
                "speedup": round(r.speedup, 3),
                "params": r.params,
                "measured": r.measured,
                "pruned": r.pruned,
            }
        except Exception as e:  # a failed search is a report, not a crash
            autotune[kernel] = {"error": f"{type(e).__name__}: {e}"}

    # -- whole-loop corroboration ------------------------------------------
    def train_loop():
        paddle.seed(5)
        net = nn.Linear(128, 64)
        opt = popt.Momentum(learning_rate=0.05, momentum=0.9,
                            weight_decay=1e-4,
                            parameters=net.parameters())
        step = fjit.train_step(
            net, opt, lambda m, x, y: F.mse_loss(m(x), y).mean())
        loop_rng = np.random.RandomState(17)  # same data both arms
        X = loop_rng.randn(64, 128).astype("f4")
        Y = loop_rng.randn(64, 64).astype("f4")
        step(X, Y)  # compile
        t0 = time.perf_counter()
        m = None
        for _ in range(iters):
            m = step(X, Y)
        loss = float(np.asarray(m["loss"]))
        return time.perf_counter() - t0, loss

    prev = get_flags(["use_fused_optimizer", "use_fused_layernorm"])
    try:
        set_flags({"use_fused_optimizer": True,
                   "use_fused_layernorm": True})
        fused_s, fused_loss = train_loop()
        set_flags({"use_fused_optimizer": False,
                   "use_fused_layernorm": False})
        unfused_s, unfused_loss = train_loop()
    finally:
        set_flags(prev)
    assert abs(fused_loss - unfused_loss) < 1e-5  # the fusion is free

    # -- input overlap ------------------------------------------------------
    from paddle_tpu.io.dataloader import _DevicePrefetcher
    from paddle_tpu.monitor import registry as _reg

    def drive(overlap):
        def source():
            for i in range(overlap_batches):
                time.sleep(0.002)  # parse/collate latency
                yield np.full((16, 16), i, np.float32)

        set_flags({"io_prefetch_overlap": overlap})
        gauge = _reg.gauge("io/input_wait_ms")
        wait0 = gauge.value
        pf = _DevicePrefetcher(source(), depth=2, to_device=True)
        t0 = time.perf_counter()
        for _ in pf:
            time.sleep(0.002)  # the consumer's "step"
        wall = time.perf_counter() - t0
        return wall, (gauge.value - wait0) / (wall * 1e3)

    prev_ov = get_flags("io_prefetch_overlap")["io_prefetch_overlap"]
    try:
        sync_wall, ratio_before = drive(False)
        overlap_wall, ratio_after = drive(True)
    finally:
        set_flags({"io_prefetch_overlap": prev_ov})

    return {
        "metric": "fused_kernels",
        "value": round(opt_unfused_us / opt_fused_us, 3),
        "unit": "optimizer-update speedup (fused vs unfused)",
        "optimizer_update": {
            "fused_us": round(opt_fused_us, 1),
            "unfused_us": round(opt_unfused_us, 1),
            "speedup": round(opt_unfused_us / opt_fused_us, 3),
        },
        "layernorm_residual": {
            "fused_us": round(ln_fused_us, 1),
            "unfused_us": round(ln_unfused_us, 1),
            "speedup": round(ln_unfused_us / ln_fused_us, 3),
        },
        "conv_bn_relu": {
            "fused_us": round(cbr_fused_us, 1),
            "unfused_us": round(cbr_unfused_us, 1),
            "speedup": round(cbr_unfused_us / cbr_fused_us, 3),
        },
        # per-kernel tuned-vs-default from a live (save=False) search
        "autotune": autotune,
        "train_loop": {
            "fused_steps_per_sec": round(iters / fused_s, 1),
            "unfused_steps_per_sec": round(iters / unfused_s, 1),
            "speedup": round(unfused_s / fused_s, 3),
            "loss_identical": True,
        },
        "input_overlap": {
            "batches": overlap_batches,
            "sync_wall_ms": round(sync_wall * 1e3, 1),
            "overlap_wall_ms": round(overlap_wall * 1e3, 1),
            "wall_speedup": round(sync_wall / overlap_wall, 3),
            "input_wait_ratio_before": round(ratio_before, 4),
            "input_wait_ratio_after": round(ratio_after, 4),
        },
    }


def bench_executor_dispatch(iters=200):
    """Static-graph Executor steady-state dispatch micro-bench.

    Runs one small compiled train step ``iters+1`` times through
    Executor.run and reports dispatches/sec plus the executor's
    plan-cache / jit-cache / donation counters (profiler.counters): in
    steady state every run after the first must be a plan-cache hit — the
    op walk runs exactly once — and the written persistables are donated.
    """
    import paddle_tpu.static as static
    from paddle_tpu import ops, profiler

    static.enable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    try:
        x = static.data("x", [32, 64], "float32")
        y = static.data("y", [32, 1], "float32")
        w = static.nn.create_parameter([64, 1], "float32")
        pred = ops.matmul(x, w)
        loss = ops.mean(ops.square(ops.subtract(pred, y)))
        opt = static.optimizer.Adam(learning_rate=0.01)
        opt.minimize(loss)
        exe = static.Executor()
        exe.run_startup()
        rng = np.random.RandomState(0)
        X = rng.randn(32, 64).astype("float32")
        Y = rng.randn(32, 1).astype("float32")

        profiler.reset_counters()
        exe.run(feed={"x": X, "y": Y}, fetch_list=[loss])  # compile
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = exe.run(feed={"x": X, "y": Y}, fetch_list=[loss])
        loss_end = float(np.asarray(out[0]))  # value fetch = barrier
        dt = time.perf_counter() - t0
        counters = {k: v for k, v in profiler.counters().items()
                    if k.startswith("executor::")}

        # program_verify sub-row: the IR verifier runs once per program
        # MUTATION EPOCH (the verdict caches on the Program per version,
        # static/program.py Program.verify), so a steady-state dispatch
        # pays only the flag read + cache lookup. Direct decomposition
        # (the monitor_overhead discipline — end-to-end A/B of a ~1ms
        # dispatch cannot resolve a ~1us cost on a noisy box): time the
        # cached verify call itself and express it as a fraction of the
        # measured dispatch period; budget <1%.
        prog = static.default_main_program()
        feedns, fetchns = ["x", "y"], [loss.name]
        prog.verify(feed_names=feedns, fetch_list=fetchns)  # warm the cache
        # best-of batches: the first post-compile loop otherwise eats the
        # XLA-garbage GC pauses and reports 20x the true lookup cost
        reps, cached_us = 400, float("inf")
        for _ in range(5):
            tv = time.perf_counter()
            for _ in range(reps):
                prog.verify(feed_names=feedns, fetch_list=fetchns)
            cached_us = min(cached_us,
                            (time.perf_counter() - tv) / reps * 1e6)
        period_us = dt / iters * 1e6
        tfull = time.perf_counter()
        prog._verify_cache.clear()
        prog.verify(feed_names=feedns, fetch_list=fetchns)
        full_verify_us = (time.perf_counter() - tfull) * 1e6
        verify_overhead = cached_us / period_us

        # memplan sub-row: the peak-HBM admission gate
        # (FLAGS_memory_budget_check) pays a cached verdict lookup per
        # dispatch and ONE full liveness plan per program mutation
        # epoch — same direct-decomposition discipline as the
        # program_verify sub-row, same <1% budget. plan_accuracy comes
        # from the accuracy closure the steady-state loop's first
        # compile already ledgered (predicted vs XLA memory_analysis).
        from paddle_tpu.analysis import memory as _memplan
        from paddle_tpu.monitor import cost_model as _cost

        shapes = {"x": (32, 64), "y": (32, 1)}
        _memplan.check_memory_budget(prog, feedns, fetchns,
                                     feed_shapes=shapes)  # warm
        mem_cached_us = float("inf")
        for _ in range(5):
            tv = time.perf_counter()
            for _ in range(reps):
                _memplan.check_memory_budget(prog, feedns, fetchns,
                                             feed_shapes=shapes)
            mem_cached_us = min(mem_cached_us,
                                (time.perf_counter() - tv) / reps * 1e6)
        tfull = time.perf_counter()
        prog._memplan_cache.clear()
        plan = _memplan.check_memory_budget(prog, feedns, fetchns,
                                            feed_shapes=shapes)
        full_plan_us = (time.perf_counter() - tfull) * 1e6
        mem_overhead = mem_cached_us / period_us
        rec = _cost.latest_record("executor")

        return {
            "metric": "executor_steady_state_dispatches_per_sec",
            "value": round(iters / dt, 1),
            "unit": "runs/sec",
            "runs": iters + 1,
            "loss_end": round(loss_end, 4),
            "counters": counters,
            "program_verify": {
                # cached verdict cost paid by EVERY dispatch vs the
                # one-time full pass paid per program mutation epoch
                "cached_verify_us": round(cached_us, 3),
                "full_verify_us": round(full_verify_us, 1),
                "dispatch_period_us": round(period_us, 1),
                "overhead_pct": round(verify_overhead * 100, 3),
                "within_target": bool(verify_overhead < 0.01),
            },
            "memplan": {
                # steady-state admission = feed-shape tuples + one dict
                # lookup; the full liveness plan is per mutation epoch
                "cached_check_us": round(mem_cached_us, 3),
                "full_plan_us": round(full_plan_us, 1),
                "dispatch_period_us": round(period_us, 1),
                "overhead_pct": round(mem_overhead * 100, 3),
                "within_target": bool(mem_overhead < 0.01),
                "predicted_peak_bytes": (
                    plan.peak_bytes if plan is not None else None),
                "peak_op": (f"#{plan.peak_op_index} "
                            f"<{plan.peak_op_type}>"
                            if plan is not None else None),
                "plan_accuracy": (
                    round(rec.plan_accuracy, 4)
                    if rec is not None and rec.plan_accuracy is not None
                    else None),
            },
        }
    finally:
        static.disable_static()
        static.reset_default_programs()
        static.global_scope().clear()


def bench_ir_opt(iters=30):
    """Program-IR optimizer A/B on the three smoke programs.

    For each of the BERT/ResNet/GPT inference smokes (the ir_opt_smoke
    builders: residual+layernorm blocks, conv+bn+relu stages, an int8
    LM head in the ptq residue form) measure planned peak-HBM and
    steady-state µs/step with the optimizer OFF (level 0) vs ON
    (level 1), plus the per-pass rewrite stats (ops_rewritten,
    bytes_saved, wall_ms) the pipeline itself reports. The remat row
    runs the level-2 scenario: an over-budget holding chain whose
    planned peak the rematerializer must cut by >= 20%.
    """
    import importlib.util
    import os

    import paddle_tpu.static as static
    from paddle_tpu import ops
    from paddle_tpu.analysis import optimizer as _iropt
    from paddle_tpu.analysis import plan_memory
    from paddle_tpu.flags import set_flags

    spec = importlib.util.spec_from_file_location(
        "ir_opt_smoke",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tools", "ir_opt_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)

    static.enable_static()
    static.reset_default_programs()
    rows = {}
    try:
        for name, build in (("bert", smoke.build_bert),
                            ("resnet", smoke.build_resnet),
                            ("gpt", smoke.build_gpt)):
            static.global_scope().clear()
            main_p, startup = static.Program(), static.Program()
            with static.program_guard(main_p, startup):
                feeds, fetch = build()
            fetch_name = fetch if isinstance(fetch, str) else fetch.name
            shapes = {k: np.shape(v) for k, v in feeds.items()}
            exe = static.Executor()
            exe.run_startup(startup)

            def _steady(level):
                set_flags({"ir_opt_level": level})
                exe.run(main_p, feed=feeds, fetch_list=[fetch])  # compile
                t0 = time.perf_counter()
                out = None
                for _ in range(iters):
                    out = exe.run(main_p, feed=feeds, fetch_list=[fetch])
                np.asarray(out[0])  # value fetch = barrier
                return (time.perf_counter() - t0) / iters * 1e6

            us_before = _steady(0)
            us_after = _steady(1)
            res = _iropt.optimize_program(main_p, sorted(feeds),
                                          [fetch_name], level=1,
                                          feed_shapes=shapes)
            peak0 = plan_memory(main_p, sorted(feeds), [fetch_name],
                                feed_shapes=shapes).peak_bytes
            peak1 = plan_memory(res.program, sorted(feeds), [fetch_name],
                                feed_shapes=shapes).peak_bytes
            n_fused = sum(
                op.type in ("fused_conv_bn_relu", "fused_layernorm_residual",
                            "matmul_int8", "mul_int8")
                for op in res.program.global_block().ops)
            rows[name] = {
                "peak_bytes_before": int(peak0),
                "peak_bytes_after": int(peak1),
                "us_per_step_before": round(us_before, 1),
                "us_per_step_after": round(us_after, 1),
                "ops_before": len(main_p.global_block().ops),
                "ops_after": len(res.program.global_block().ops),
                "fused_ops": int(n_fused),
                "passes": [dict(name=s.name, ops_rewritten=s.ops_rewritten,
                                bytes_saved=s.bytes_saved,
                                wall_ms=round(s.wall_ms, 3))
                           for s in res.stats],
            }

        # remat scenario: the budget forces level 2 to recompute the
        # held activations; report the planned-peak cut it achieves
        static.global_scope().clear()
        remat_p = static.Program()
        with static.program_guard(remat_p, static.Program()):
            x = static.data("x", [64, 4096], "float32")
            held = [ops.scale(x, scale=float(i + 1)) for i in range(4)]
            acc = ops.relu(held[0])
            for h in held[1:]:
                acc = ops.add(acc, h)
            out = ops.mean(acc)
        shapes = {"x": (64, 4096)}
        budget = 4 * 1024 * 1024 + 256 * 1024
        set_flags({"device_peaks": f"hbm_bytes={budget}"})
        res = _iropt.optimize_program(remat_p, ["x"], [out.name], level=2,
                                      feed_shapes=shapes)
        set_flags({"device_peaks": ""})
        peak0 = plan_memory(remat_p, ["x"], [out.name],
                            feed_shapes=shapes).peak_bytes
        peak2 = plan_memory(res.program, ["x"], [out.name],
                            feed_shapes=shapes).peak_bytes
        rows["remat"] = {
            "budget_bytes": budget,
            "peak_bytes_before": int(peak0),
            "peak_bytes_after": int(peak2),
            "reduction_pct": round(100 * (peak0 - peak2) / peak0, 1),
            "passes": [dict(name=s.name, ops_rewritten=s.ops_rewritten,
                            bytes_saved=s.bytes_saved,
                            wall_ms=round(s.wall_ms, 3))
                       for s in res.stats if s.ops_rewritten],
        }
        return {"metric": "ir_opt", "programs": rows}
    finally:
        set_flags({"ir_opt_level": 1, "device_peaks": ""})
        static.disable_static()
        static.reset_default_programs()
        static.global_scope().clear()


def main():
    import jax

    on_tpu = jax.devices()[0].platform != "cpu"
    result = bench_bert(on_tpu, phase=1)
    result["secondary"] = bench_resnet50(on_tpu)
    # phase-2 at seq 512 exercises the pallas flash-attention kernel on a
    # driver-captured number (dispatch: nn/transformer.py
    # FLASH_ATTENTION_MIN_SEQ)
    result["secondary2"] = bench_bert(on_tpu, phase=2)
    # host-side dispatch health: plan-cache hit rate + donation counters
    result["executor_dispatch"] = bench_executor_dispatch()
    # program-IR optimizer: peak-HBM + µs/step A/B per pass on the
    # BERT/ResNet/GPT smokes, plus the level-2 remat planned-peak cut
    result["ir_opt"] = bench_ir_opt()
    # fused optimizer/layernorm kernels + h2d overlap A/B (ResNet levers)
    result["fused_kernels"] = bench_fused_kernels()
    # always-on span cost with the profiler disabled (target < 2%)
    result["monitor_overhead"] = bench_monitor_overhead()
    # always-on flight-recorder cost, recording on vs off (target < 2%)
    result["flight_recorder_overhead"] = bench_flight_recorder_overhead()
    # per-request trace spans + tail-sampled store, on vs off (target < 2%)
    result["tracing_overhead"] = bench_tracing_overhead()
    # labeled-family observes on the hot path + /fleetz merge (target < 2%)
    result["observability_overhead"] = bench_observability_overhead()
    # goodput-ledger phase transitions on the step path (target < 1%)
    result["goodput_overhead"] = bench_goodput_overhead()
    # per-op stamp cost amortized over a trace epoch (target < 1%) +
    # on-demand replay-profile wall cost, unasserted
    result["opprof_overhead"] = bench_opprof_overhead()
    # online serving: batcher+replicas vs sequential single-request calls
    result["serving_throughput"] = bench_serving_throughput()
    # generative decoding: continuous vs static batching, mixed lengths,
    # speculative draft/verify sub-row (k in {2, 4})
    result["decode_throughput"] = bench_decode_throughput()
    # disaggregated prefill/decode 2-process fleet vs unified, TTFT p99
    result["decode_throughput"]["disagg"] = bench_disagg_fleet()
    # paged KV: shared-prefix sweep (hit rate / FLOPs saved / TTFT),
    # slots-at-equal-HBM on a constrained pool, ring parity
    result["paged_kv"] = bench_paged_kv()
    # serving fleet: 1 -> N backend processes behind the router
    result["router_throughput"] = bench_router_throughput()
    # async snapshot capture on the step path vs blocking saves (target <2%)
    result["checkpoint_overhead"] = bench_checkpoint_overhead()
    print(json.dumps(result))


if __name__ == "__main__":
    main()

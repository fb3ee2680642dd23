"""The benchmark's readers of the serving loop's host timeline, fed a
timeline that a loop running one step ahead really recorded (a tiny
engine, on the CPU): the order of the phases changed (`decode` of step
n+1, then `decode_fetch` of step n), the readers did not. None may raise,
each reads a finite number or None, and the new reader reads None on a
timeline without its counter (a parent's).
"""
import math
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.generation import GenerationEngine
from paddle_tpu.models import GPTForCausalLM, gpt_tiny_config
from paddle_tpu.serving import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
READERS = ("host_gap_ms.serve", "trace_overhead_pct.serve",
           "prefill_share_pct", "loop_stall_max_ms", "slots_busy_pct.sched",
           "steps_ahead_pct.sched")
SLOTS = 2


def _reader(name):
    from benchmark.lib import common

    return common.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"))


@pytest.fixture(scope="module")
def recorded():
    """``(ctx, share of the iterations that ran ahead)`` of 3 s of a
    loop at depth 1 with admissions mid-batch; the device-traced
    interval is made up (the middle third of the window)."""
    paddle.seed(3)
    cfg = gpt_tiny_config()
    cfg.attention_window = 32
    model = GPTForCausalLM(cfg)
    model.eval()
    eng = GenerationEngine(model, slots=SLOTS, cache_len=32,
                           prefill_buckets=(4, 8), seed=7).warmup()
    assert eng.steps_ahead == 1
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    sched = ContinuousBatcher(eng, queue_capacity=64).start()
    try:
        rng = np.random.RandomState(0)
        w0 = time.monotonic()
        reqs = [sched.submit(list(rng.randint(3, 200, size=5)),
                             max_new_tokens=int(rng.randint(2, 24)),
                             temperature=0.0) for _ in range(40)]
        for r in reqs:
            r.wait(timeout=120)
        w1 = time.monotonic()
    finally:
        sched.stop(drain=False)
        profiler.stop_profiler()
    host = [(e["name"], e["ts"] * 1e3, (e["ts"] + e["dur"]) * 1e3)
            for e in profiler.host_events()]
    ahead = [s["args"]["value"] for s in profiler.counter_samples()
             if s["name"] == "serving::steps_ahead"]
    offset = time.perf_counter() - time.monotonic()
    third = (w1 - w0) / 3
    ctx = {"spans": SimpleNamespace(host=host),
           "res": {"window": (w0, w1), "slots": SLOTS},
           "trace": SimpleNamespace(t0=(w0 + third + offset) * 1e9,
                                    t1=(w1 - third + offset) * 1e9),
           "clock_offset_ns": 0.0,
           "cell": SimpleNamespace(
               dir=BENCH, cfg={"engine": {"cache_len": 32}})}
    yield ctx, sum(ahead) / len(ahead)
    profiler.reset_profiler()


@pytest.mark.parametrize("name", READERS)
def test_reader_takes_the_new_order_of_the_phases(recorded, name):
    ctx, _ = recorded
    value = _reader(name).read(ctx)
    assert value is None or math.isfinite(value)
    if name in ("host_gap_ms.serve", "prefill_share_pct",
                "loop_stall_max_ms", "trace_overhead_pct.serve"):
        # the timeline has what each of them looks for
        assert value is not None
    if name == "host_gap_ms.serve":
        # from a fetch's end to the next enqueue's end: the host's work
        # an iteration now (deliver, pick, enqueue), not a device's wait
        assert 0 < value < 1000


def test_steps_ahead_pct_reads_the_counter_or_none(recorded):
    ctx, by_count = recorded
    reader = _reader("steps_ahead_pct.sched")
    value = reader.read(ctx)
    assert 0 < by_count < 1 and 0 < value < 100
    # a timeline without the counter: the parent's
    samples = profiler.counter_samples
    try:
        profiler.counter_samples = lambda: [
            s for s in samples() if s["name"] != "serving::steps_ahead"]
        assert reader.read(ctx) is None
        assert _reader("slots_busy_pct.sched").read(ctx) is not None
    finally:
        profiler.counter_samples = samples

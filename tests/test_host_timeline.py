"""The host timeline of the two hot loops, from inside the program.

The serving loop thread's time is a partition into sibling phase spans
(`profiler` events on `perf_counter_ns`); the `*_fetch` spans close after
the tokens are on the host; counter samples share the clock and stay out
of `host_events()`; a loop iteration that stands still leaves one
`generation_stall` flight event whose split sums to the iteration;
`TrainStepFn` names its two host phases.
"""
import json
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.generation import GenerationEngine
from paddle_tpu.models import GPTForCausalLM, gpt_tiny_config
from paddle_tpu.monitor import flight_recorder
from paddle_tpu.serving import ContinuousBatcher

CACHE = 32
BUCKETS = (4, 8)
# the disjoint siblings that partition the loop thread's time
TOP = ("serving::pick", "generation::prefill", "generation::prefill_fetch",
       "serving::install", "generation::decode", "generation::decode_fetch",
       "serving::deliver", "serving::idle_wait", "generation::stats_fetch")
NESTED = ("generation::args", "runtime::lookup", "runtime::launch")
# which sibling may follow which on the loop thread (serving/continuous.py
# `_loop`): an iteration is pick, the admissions (prefill, its fetch,
# install, pick again; a chunk that is not its prompt's last has no fetch
# and no install, its last chunk's fetch and install come after the step
# in flight is delivered, and from a prompt's first chunk to the end of
# the iteration of its last nothing else is picked),
# then the step's enqueue, a fetch, deliver, or an idle wait; a program's
# statistics are fetched right after its tokens
AFTER = {
    "serving::pick": {"generation::prefill", "generation::decode",
                      "generation::decode_fetch", "serving::idle_wait",
                      "serving::pick"},
    "generation::prefill": {"generation::prefill_fetch",
                            "generation::decode",
                            "generation::decode_fetch",
                            "serving::idle_wait"},
    "generation::prefill_fetch": {"generation::stats_fetch",
                                  "serving::install"},
    "serving::install": {"serving::pick", "generation::decode",
                         "generation::decode_fetch", "serving::idle_wait"},
    "generation::decode": {"generation::decode_fetch", "serving::pick",
                           "generation::prefill"},
    "generation::decode_fetch": {"generation::stats_fetch",
                                 "serving::deliver"},
    "generation::stats_fetch": {"serving::install", "serving::deliver"},
    "serving::deliver": {"serving::pick", "generation::prefill",
                         "generation::prefill_fetch"},
    "serving::idle_wait": {"serving::pick", "generation::prefill"},
}
# between two siblings the loop thread runs a few lines of Python (0.2 ms
# at most on an idle machine). A stretch of the loop that no phase covers
# would show between the same two siblings in every iteration, so nine
# gaps in ten must stay under the bound; the tenth is the loaded
# machine's, whose scheduler takes the thread off its core for tens of
# milliseconds wherever it stands
GAP_US = 5e3


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    cfg = gpt_tiny_config()
    cfg.attention_window = CACHE
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture()
def spans_on():
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    yield
    profiler.stop_profiler()
    profiler.reset_profiler()


def _engine(model, buckets=BUCKETS, **kw):
    return GenerationEngine(model, slots=2, cache_len=CACHE,
                            prefill_buckets=buckets, seed=7, **kw).warmup()


def _serve(eng, n=5, budget=5):
    """Run n requests through a scheduler; returns the loop thread's
    spans (name, start_us, end_us), sorted by start."""
    sched = ContinuousBatcher(eng, queue_capacity=16).start()
    try:
        profiler.reset_profiler()
        reqs = [sched.submit(list(range(3, 6 + i)), max_new_tokens=budget,
                             temperature=0.0) for i in range(n)]
        for r in reqs:
            r.wait(timeout=60)
        time.sleep(0.12)  # two idle waits
    finally:
        sched.stop(drain=False)
    return sorted((e["ts"], e["ts"] + e["dur"], e["name"])
                  for e in profiler.host_events())


class _SlowTokens:
    """Stands in for a device array whose program is still running: the
    conversion to numpy is what waits."""

    def __init__(self, real, seconds):
        self.real, self.seconds = real, seconds

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.seconds)
        return np.asarray(self.real)


def _slow_first_decode(eng, monkeypatch, seconds):
    """The first decode step's tokens take ``seconds`` to arrive. The
    step enqueued behind it takes them where they are (the stand-in is
    unwrapped on its way into the program): only the fetch waits."""
    real, fired = eng._dispatch, []

    def dispatch(label, jitted, make_args):
        out = real(label, jitted, lambda: tuple(
            a.real if isinstance(a, _SlowTokens) else a
            for a in make_args()))
        if label == "decode" and not fired:
            fired.append(1)
            return out[0], _SlowTokens(out[1], seconds)
        return out

    monkeypatch.setattr(eng, "_dispatch", dispatch)


def _kinds_model():
    """A toy hybrid MoE decoder: its programs return routing statistics,
    which the engine fetches (``generation::stats_fetch``) while the
    profiler is on."""
    from paddle_tpu.models import HybridMoEConfig, HybridMoEForCausalLM

    paddle.seed(5)
    m = HybridMoEForCausalLM(HybridMoEConfig(
        vocab_size=97, vocab_held=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        gqa_layers=(0,), linear_attn_config=dict(
            short_conv_kernel_size=4, head_dim=8, num_heads=4),
        kda_gate_rank=8, moe_intermediate_size=16, n_routed_experts=16,
        num_experts_per_tok=4, experts_held=(4, 8)))
    m.eval()
    return m


def _rings_model():
    """The toy window-and-full decoder: every kind of its cache keeps
    K/V rings, so a prompt longer than its ladder's second bucket (4 of
    2, 4, 8) goes in by chunks."""
    import test_exaone_moe as toy

    return toy._model()[0]


@pytest.mark.parametrize("layout", ["ring", "paged", "kinds", "chunks"])
def test_loop_phases_partition_the_loop_threads_time(model, spans_on, layout):
    """Structural, so that a loaded machine keeps it: the siblings never
    overlap, follow each other in an order the loop can produce, and
    nine in ten of the gaps between them stay under a fixed few
    milliseconds (the share of the loop's time they cover is the chip's
    to read: `host_gap_ms.serve`)."""
    kw = dict(kv_cache_layout="paged", kv_page_size=8) \
        if layout == "paged" else {}
    made = dict(kinds=_kinds_model, chunks=_rings_model).get(layout)
    if layout == "chunks":
        kw["buckets"] = (2,) + BUCKETS
    eng = _engine(model if made is None else made(), **kw)
    assert eng.chunk_len == (4 if layout == "chunks" else None)
    spans = _serve(eng)
    top = [s for s in spans if s[2] in TOP]
    # only a model with per-layer kinds has statistics to fetch
    assert {s[2] for s in top} == set(TOP) - (
        set() if made else {"generation::stats_fetch"})
    for a, b in zip(top, top[1:]):
        assert b[0] >= a[1] - 1e-3, (a, b)  # siblings never overlap (us)
        assert b[2] in AFTER[a[2]], (a, b)
    gaps = sorted(b[0] - a[1] for a, b in zip(top, top[1:]))
    assert gaps[len(gaps) * 9 // 10] <= GAP_US, gaps[-10:]
    # prompts of 5, 6 and 7 tokens go in as two chunks of 4, the first of
    # them enqueued and left: a prefill with no fetch of its own
    count = {n: sum(s[2] == "generation::" + n for s in top)
             for n in ("prefill", "prefill_fetch")}
    assert count["prefill_fetch"] == 5
    assert count["prefill"] == 5 + (3 if layout == "chunks" else 0)
    assert not any("iteration" in s[2] for s in spans)  # no wrapper span


def test_only_the_three_sub_spans_nest_inside_the_enqueue_spans(
        model, spans_on):
    spans = _serve(_engine(model))
    outer = [s for s in spans
             if s[2] in ("generation::decode", "generation::prefill")]
    for name in NESTED:
        inner = [s for s in spans if s[2] == name]
        assert len(inner) == len(outer)
        for s, e, _ in inner:
            assert any(o[0] <= s and e <= o[1] + 1e-3 for o in outer)
    # after warm-up generation::args is a short span (the state and its
    # signature are kept), not a missing one: one in every enqueue span
    args = [s for s in spans if s[2] == "generation::args"]
    for kind in ("generation::decode", "generation::prefill"):
        enqueues = [o for o in outer if o[2] == kind]
        assert enqueues and all(
            sum(o[0] <= s and e <= o[1] + 1e-3 for s, e, _ in args) == 1
            for o in enqueues)


def test_decode_fetch_closes_after_the_tokens_are_on_the_host(
        model, spans_on, monkeypatch):
    eng = _engine(model)
    _slow_first_decode(eng, monkeypatch, 0.3)
    spans = _serve(eng, n=1, budget=3)
    fetch = max(e - s for s, e, n in spans
                if n == "generation::decode_fetch")
    enqueue = max(e - s for s, e, n in spans if n == "generation::decode")
    assert fetch >= 0.3e6  # us: the wait reads in the fetch span
    assert enqueue < 0.3e6  # and not in the enqueue span


def test_speculative_round_has_one_fetch(model, spans_on):
    eng = _engine(model, draft_model=model, draft_k=2)
    profiler.reset_profiler()
    eng.generate([[3, 4, 5]], max_new_tokens=4, temperature=0.0)
    names = [e["name"] for e in profiler.host_events()]
    assert names.count("generation::decode_fetch") == names.count(
        "generation::verify") >= 1
    # a driver that keeps its own split is handed both phases' time
    split = eng.phase_split = {}
    eng.admit(0, [3, 4, 5], 0.0)
    eng.spec_step(np.zeros(2, np.int32), np.zeros(2, np.float32))
    assert set(split) == {"generation::prefill", "generation::prefill_fetch",
                          "generation::decode", "generation::decode_fetch"}
    assert all(v > 0 for v in split.values())


@pytest.mark.parametrize("memory", ["reported", "raises"])
def test_stalled_iteration_leaves_one_flight_event_with_its_split(
        model, monkeypatch, memory):
    eng = _engine(model)
    _slow_first_decode(eng, monkeypatch, 1.2)
    if memory == "raises":
        # asking a device in trouble may fail: the loop must live, and
        # the record goes out without the allocator's fields
        def broken():
            raise RuntimeError("device lost")

        monkeypatch.setattr(eng, "device_memory_stats", broken)
    flight_recorder.reset_recorder()
    _serve(eng, n=2, budget=3)  # every request still completes
    stalls = [e for e in flight_recorder.events()
              if e["kind"] == "generation_stall"]
    assert len(stalls) == 1
    ev = stalls[0]
    assert ev["iteration_ms"] >= 1200
    assert abs(sum(ev["phases_ms"].values()) - ev["iteration_ms"]) < 0.05
    assert max(ev["phases_ms"], key=ev["phases_ms"].get) == \
        "generation::decode_fetch"
    assert "serving::idle_wait" not in ev["phases_ms"]
    assert ev["live_slots"] >= 1 and "queue_depth" in ev
    assert "bytes_in_use" not in ev  # the CPU reports none either way


def test_counter_samples_share_the_clock_and_stay_out_of_host_events(
        tmp_path):
    profiler.reset_profiler()
    profiler.record_counter("t::depth", 3)  # profiler off: nothing kept
    profiler.add_span("t::span", 0, 10)
    assert profiler.counter_samples() == [] and profiler.host_events() == []
    profiler.start_profiler(state="CPU")
    try:
        t0 = time.perf_counter_ns()
        profiler.record_counter("t::depth", 3)
        profiler.add_span("t::span", t0, t0 + 5000)
        t1 = time.perf_counter_ns()
    finally:
        profiler.stop_profiler()
    (sample,) = profiler.counter_samples()
    assert sample["ph"] == "C" and sample["args"] == {"value": 3}
    assert t0 / 1e3 <= sample["ts"] <= t1 / 1e3
    (span,) = profiler.host_events()  # the sample is not among the spans
    assert span["name"] == "t::span" and span["dur"] == 5.0
    path = profiler.export_chrome_tracing(str(tmp_path / "t.json"))
    with open(path) as f:
        phs = sorted(e["ph"] for e in json.load(f)["traceEvents"])
    assert phs == ["C", "X"]
    profiler.reset_profiler()
    assert profiler.counter_samples() == []


@pytest.mark.parametrize("layout", ["ring", "paged"])
def test_scheduler_samples_its_counts_once_an_iteration(model, spans_on,
                                                        layout):
    kw = {} if layout == "ring" else dict(kv_cache_layout="paged",
                                          kv_page_size=8)
    spans = _serve(_engine(model, **kw), n=3, budget=4)
    by_name = {}
    for s in profiler.counter_samples():
        by_name.setdefault(s["name"], []).append(s["args"]["value"])
    assert set(by_name) == {"serving::slots_busy", "serving::kv_live_tokens",
                            "serving::steps_ahead",
                            "generation::prefill_chunks"}
    # three prompts, each one program: chunk 1, and the last
    assert by_name["generation::prefill_chunks"] == [[1, 1]] * 3
    steps = sum(1 for s in spans if s[2] == "generation::decode")
    idles = sum(1 for s in spans if s[2] == "serving::idle_wait")
    # an iteration enqueues a step, or waits idle, or (one step ahead
    # only) drains: it fetches the step in flight and enqueues none
    ahead = by_name["serving::steps_ahead"]
    assert set(ahead) == ({0, 1} if layout == "ring" else {0})
    drains = sum(1 for s in spans
                 if s[2] == "generation::decode_fetch") - sum(ahead)
    assert len(ahead) == len(by_name["serving::slots_busy"])
    assert abs(len(ahead) - steps - idles - (layout == "ring") * drains) <= 1
    assert max(by_name["serving::slots_busy"]) == 2
    # prompts of 3, 4, 5 tokens with 4 new tokens each: at most two live
    assert 0 < max(by_name["serving::kv_live_tokens"]) <= 4 + 5 + 2 * 4


def test_scheduler_counts_nothing_while_the_profiler_is_off(
        model, monkeypatch):
    from paddle_tpu.serving import continuous

    profiler.reset_profiler()
    calls = []
    monkeypatch.setattr(continuous._profiler, "record_counter",
                        lambda *a: calls.append(a))
    _serve(_engine(model), n=1, budget=3)
    assert calls == [] and profiler.counter_samples() == []


def test_train_step_names_its_two_host_phases_once_a_call(spans_on):
    from paddle_tpu import nn, optimizer
    from paddle_tpu.framework import jit as fjit

    net = nn.Linear(4, 2)
    opt = optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
    step = fjit.train_step(net, opt, lambda m, x, y: ((m(x) - y) ** 2).mean())
    x = np.ones((3, 4), np.float32)
    y = np.zeros((3, 2), np.float32)
    step(x, y)
    profiler.reset_profiler()
    for _ in range(3):
        step(x, y)
    evs = sorted((e["ts"], e["ts"] + e["dur"], e["name"])
                 for e in profiler.host_events())
    names = [n for _, _, n in evs]
    assert names.count("train::shard_batch") == 3
    assert names.count("train::step_dispatch") == 3
    assert "train::step" not in names  # no wrapper here
    # the runtime's two spans nest inside the dispatch, with no code in
    # TrainStepFn
    outer = [e for e in evs if e[2] == "train::step_dispatch"]
    for name in ("runtime::lookup", "runtime::launch"):
        inner = [e for e in evs if e[2] == name]
        assert len(inner) == 3
        assert all(any(o[0] <= s and e <= o[1] + 1e-3 for o in outer)
                   for s, e, _ in inner)

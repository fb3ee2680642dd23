"""Continuous batching + the /generate HTTP frontend.

Pins the slot-scheduler contracts: mixed-length co-batched outputs are
identical to solo runs, finished sequences vacate their slot MID-BATCH
and queued requests are admitted into the vacancy at the next step,
backpressure/drain behave like the predict path (429 / 503 / graceful
drain with no live slots left), and /statz carries tokens/sec, slot
occupancy, and per-token latency quantiles.
"""
import json
import threading
import time
from urllib.error import HTTPError
from urllib.request import Request, urlopen

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.generation import GenerationEngine
from paddle_tpu.models import GPTForCausalLM, gpt_tiny_config
from paddle_tpu.serving import (
    ContinuousBatcher,
    GenerationServer,
    QueueFullError,
    ServingClosedError,
)

CACHE = 32
BUCKETS = (4, 8)


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    cfg = gpt_tiny_config()
    cfg.attention_window = CACHE
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _engine(model, slots=2, seed=7, **kw):
    return GenerationEngine(model, slots=slots, cache_len=CACHE,
                            prefill_buckets=BUCKETS, seed=seed, **kw)


def _prompts(n, rng_seed=0):
    rng = np.random.RandomState(rng_seed)
    return [list(rng.randint(3, 200, size=int(rng.randint(1, 9))))
            for _ in range(n)]


# -- scheduler correctness ----------------------------------------------------

def test_cobatched_outputs_match_solo_runs(model):
    """Mixed-length requests decoded together in shared slots must equal
    each request decoded ALONE (slot co-residency is numerically
    inert — the continuous-batching golden)."""
    prompts = _prompts(6)
    budgets = [3, 7, 2, 5, 8, 4]
    solo_eng = _engine(model, slots=1).warmup()
    solo = [solo_eng.generate([p], max_new_tokens=b, temperature=0.0)[0]
            for p, b in zip(prompts, budgets)]

    eng = _engine(model, slots=3).warmup()
    sched = ContinuousBatcher(eng, queue_capacity=16).start()
    try:
        reqs = [sched.submit(p, max_new_tokens=b, temperature=0.0)
                for p, b in zip(prompts, budgets)]
        got = [r.wait(timeout=60) for r in reqs]
        assert got == solo
        assert sched.extra_compiles() == 0
    finally:
        sched.stop(drain=False)


def test_vacated_slot_readmission_midbatch(model):
    """More requests than slots: early finishers vacate mid-batch and
    queued requests enter the vacancy (midbatch_admissions > 0), with
    every request completing."""
    from paddle_tpu import monitor

    eng = _engine(model, slots=2).warmup()
    sched = ContinuousBatcher(eng, queue_capacity=32).start()
    mid0 = monitor.counter("serving/gen_midbatch_admissions_total").value
    try:
        # one long request pins a slot while short ones cycle through
        # the other -> admissions MUST happen while a batch is running
        reqs = [sched.submit(p, max_new_tokens=b, temperature=0.0)
                for p, b in zip(_prompts(5, rng_seed=1),
                                [24, 2, 2, 2, 2])]
        outs = [r.wait(timeout=120) for r in reqs]
        assert [len(o) for o in outs] == [24, 2, 2, 2, 2]
        assert (monitor.counter(
            "serving/gen_midbatch_admissions_total").value - mid0) >= 1
        assert sched.live_slots == 0
    finally:
        sched.stop(drain=False)


def test_streaming_tokens_arrive_per_step(model):
    eng = _engine(model, slots=1).warmup()
    sched = ContinuousBatcher(eng, queue_capacity=4).start()
    try:
        seen = []
        req = sched.submit([5, 6, 7], max_new_tokens=5, temperature=0.0,
                           on_token=seen.append)
        out = req.wait(timeout=60)
        assert seen == out and len(out) == 5
    finally:
        sched.stop(drain=False)


def test_queue_full_and_closed_reject(model):
    eng = _engine(model, slots=1)  # NOT started: nothing drains the queue
    sched = ContinuousBatcher(eng, queue_capacity=2)
    sched.submit([1, 2], max_new_tokens=2)
    sched.submit([1, 2], max_new_tokens=2)
    with pytest.raises(QueueFullError):
        sched.submit([1, 2], max_new_tokens=2)
    sched.close(drain=False)
    with pytest.raises(ServingClosedError):
        sched.submit([1, 2], max_new_tokens=2)


def test_invalid_requests_rejected_at_submit(model):
    from paddle_tpu.errors import InvalidArgumentError

    eng = _engine(model, slots=1)
    sched = ContinuousBatcher(eng, queue_capacity=4)
    with pytest.raises(InvalidArgumentError):
        sched.submit([], max_new_tokens=2)          # empty prompt
    with pytest.raises(InvalidArgumentError):
        sched.submit([1] * 9, max_new_tokens=2)     # > largest bucket
    with pytest.raises(InvalidArgumentError):
        sched.submit([1, 2], max_new_tokens=0)      # no budget
    sched.close(drain=False)


def test_drain_completes_queued_work(model):
    """stop(drain=True) finishes everything queued AND active before the
    decode loop exits; no live slots remain."""
    eng = _engine(model, slots=2).warmup()
    sched = ContinuousBatcher(eng, queue_capacity=16).start()
    reqs = [sched.submit(p, max_new_tokens=4, temperature=0.0)
            for p in _prompts(5, rng_seed=2)]
    sched.stop(drain=True)
    for r in reqs:
        assert len(r.wait(timeout=1)) == 4
    assert sched.live_slots == 0 and sched.alive == 0


def test_stop_without_drain_fails_pending(model):
    eng = _engine(model, slots=1).warmup()
    sched = ContinuousBatcher(eng, queue_capacity=16)  # loop not started
    req = sched.submit([1, 2, 3], max_new_tokens=4)
    sched.stop(drain=False)
    with pytest.raises(ServingClosedError):
        req.wait(timeout=1)


def test_drain_stop_with_no_loop_fails_queued_instead_of_stranding(model):
    """stop(drain=True) when the decode loop never started must error
    the queued requests — there is nothing to drain them — not leave
    their waiters blocked forever."""
    eng = _engine(model, slots=1).warmup()
    sched = ContinuousBatcher(eng, queue_capacity=4)   # start() never ran
    req = sched.submit([1, 2, 3], max_new_tokens=4)
    sched.stop(drain=True)
    with pytest.raises(ServingClosedError):
        req.wait(timeout=1)


def test_server_stop_before_start_does_not_hang(model):
    """stop() on a constructed-but-never-started server must return
    (socketserver.shutdown() would otherwise block forever) — the
    conftest/atexit shutdown_all path hits exactly this."""
    srv = GenerationServer(_engine(model, slots=1), port=0)
    done = []
    t = threading.Thread(target=lambda: done.append(srv.stop(drain=True)))
    t.start()
    t.join(timeout=10)
    assert done, "stop() hung on a never-started server"


# -- one step ahead -----------------------------------------------------------
#
# A ring engine without a draft model lets the loop enqueue step n+1 from
# step n's device tokens before it fetches step n (engine.steps_ahead 1).
# The reference order (depth 0) is had by giving the engine a ``step`` of
# one's own: a replaced ``step`` is handed every step whole.

def _whole_steps(eng):
    """The same engine at depth 0: its ``step`` wrapped, not changed."""
    eng.step = lambda tokens, temps: GenerationEngine.step(eng, tokens, temps)
    assert eng.steps_ahead == 0
    return eng


def _served(eng, prompts, budgets, temperature, before_start=False):
    """Tokens and finish reasons of the requests through a scheduler.
    ``before_start`` queues them all before the loop runs, so that they
    are admitted in order ahead of the first step."""
    sched = ContinuousBatcher(eng, queue_capacity=32)
    if not before_start:
        sched.start()
    try:
        reqs = [sched.submit(p, max_new_tokens=b, temperature=temperature)
                for p, b in zip(prompts, budgets)]
        sched.start()
        out = [(r.wait(timeout=120), r.finish_reason) for r in reqs]
        assert sched.extra_compiles() == 0 and sched.live_slots == 0
        return out
    finally:
        sched.stop(drain=False)


@pytest.mark.parametrize("case", ["greedy_midbatch", "sampled_one_batch",
                                  "greedy_eos"])
def test_one_step_ahead_serves_the_tokens_of_whole_steps(model, case):
    """Depth 1 against depth 0, same engine seed: greedy across
    admissions mid-batch (look-ahead and drained iterations mixed, no
    compile); sampled for a batch admitted before the first step, where
    no admission reorders the sampling counters and no two enqueues
    share one; greedy with requests that end on EOS, whose one row of
    the step already in flight is never delivered and whose slot's next
    occupant reads what it reads alone."""
    from paddle_tpu import profiler

    temperature = 0.9 if case == "sampled_one_batch" else 0.0
    if case == "sampled_one_batch":
        prompts, budgets, slots = _prompts(3, rng_seed=4), [5, 9, 7], 3
    else:
        prompts = _prompts(7, rng_seed=1)
        budgets, slots = [9, 3, 6, 2, 8, 4, 5], 2
    eos = None
    if case == "greedy_eos":
        solo = _engine(model, slots=1).warmup().generate(
            prompts, max_new_tokens=9, temperature=0.0, stop_at_eos=False)
        # a token that some reply reads for the first time past its
        # first position and short of its budget: that request ends
        # there, on EOS, with its row of the next step in flight
        eos = next(t[i] for t, b in zip(solo, budgets)
                   for i in range(1, b - 1) if t[i] not in t[:i])

    def build():
        eng = _engine(model, slots=slots).warmup()
        if eos is not None:
            eng.eos_id = eos
        return eng

    ahead, ctrs = build(), []
    assert ahead.steps_ahead == 1
    bump = ahead._next_key_step
    ahead._next_key_step = lambda: ctrs.append(bump()) or ctrs[-1]
    compiles = profiler.counters().get("generation::compile", 0)
    before = case == "sampled_one_batch"
    got = _served(ahead, prompts, budgets, temperature, before_start=before)
    assert profiler.counters().get("generation::compile", 0) == compiles
    want = _served(_whole_steps(build()), prompts, budgets, temperature,
                   before_start=before)
    assert got == want
    assert [len(t) for t, _ in got] == budgets or eos is not None
    assert len(ctrs) == len(set(ctrs)) > len(prompts)
    if eos is not None:
        reasons = [r for _, r in got]
        assert "eos" in reasons and "length" in reasons
        assert all(t[-1] == eos and eos not in t[:-1]
                   for t, r in got if r == "eos")


def test_steps_ahead_is_what_the_engine_is(model):
    assert _engine(model).steps_ahead == 1
    assert _engine(model, kv_cache_layout="paged",
                   kv_page_size=8).steps_ahead == 0
    assert _engine(model, draft_model=model, draft_k=2).steps_ahead == 0
    assert _whole_steps(_engine(model)).steps_ahead == 0


@pytest.fixture()
def spans_on():
    from paddle_tpu import profiler

    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    yield profiler
    profiler.stop_profiler()
    profiler.reset_profiler()


def _iterations(profiler):
    """``[(steps_ahead sample, names of the loop's phases until the next
    sample)]``, one an iteration of the loop."""
    samples = sorted((s["ts"], s["args"]["value"])
                     for s in profiler.counter_samples()
                     if s["name"] == "serving::steps_ahead")
    phases = sorted((e["ts"], e["name"]) for e in profiler.host_events()
                    if e["name"].startswith(("serving::", "generation::"))
                    and e["name"] != "generation::args")
    out = []
    for (t, v), (t_next, _) in zip(samples, samples[1:] + [(1e30, None)]):
        out.append((v, [n for ts, n in phases if t <= ts < t_next]))
    return out


def test_steps_ahead_samples_read_1_ahead_and_0_after_an_admission(
        model, spans_on):
    eng = _engine(model, slots=2).warmup()
    spans_on.reset_profiler()
    _served(eng, _prompts(5, rng_seed=1), [12, 2, 3, 2, 9], 0.0)
    its = _iterations(spans_on)
    assert {v for v, _ in its} == {0, 1}
    admitted = False  # a prefill since the sample before this one
    for v, names in its:
        steps = [n for n in names if n.startswith("generation::decode")]
        if v:
            # enqueued ahead: the step goes out BEFORE the fetch of the
            # one in flight, and no admission came before the sample
            assert steps[:2] == ["generation::decode",
                                 "generation::decode_fetch"]
            assert not admitted
        else:
            # drained (fetch only), started again from the host's tokens
            # (enqueue only), or idle: never both halves
            assert len(steps) <= 1
        # a sample follows its iteration's admissions: the phases up to
        # the next sample end with the next iteration's
        admitted = "generation::prefill" in names
    assert any(v == 0 and "generation::decode_fetch" in n for v, n in its)


def test_speculative_rounds_keep_their_order(model, spans_on):
    plain = _engine(model, slots=1).warmup().generate(
        [[5, 6, 7]], max_new_tokens=6, temperature=0.0)
    eng = _engine(model, draft_model=model, draft_k=2).warmup()
    spans_on.reset_profiler()
    got = _served(eng, [[5, 6, 7]], [6], 0.0)
    assert [t for t, _ in got] == plain
    assert {v for v, _ in _iterations(spans_on)} == {0}


class _Tokens:
    """A decode step's device tokens, for a test's purpose: passed on to
    the next step as they are (``real``), while the fetch raises."""

    def __init__(self, real):
        self.real = real

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("fetch failed")


@pytest.mark.parametrize("half", ["enqueue", "fetch"])
def test_a_step_that_raises_with_one_in_flight_fails_live_once(model, half):
    from paddle_tpu import monitor
    from paddle_tpu.monitor import flight_recorder

    eng = _engine(model, slots=2).warmup()
    real, decodes = eng._dispatch, []

    def dispatch(label, jitted, make_args):
        if label != "decode":
            return real(label, jitted, make_args)
        decodes.append(1)
        if half == "enqueue" and len(decodes) == 3:
            raise RuntimeError("enqueue failed")  # step 2 is in flight
        out = real(label, jitted, lambda: tuple(
            a.real if isinstance(a, _Tokens) else a for a in make_args()))
        if half == "fetch" and len(decodes) == 2:
            return out[0], _Tokens(out[1])  # fetched with step 3 enqueued
        return out

    eng._dispatch = dispatch
    errors = monitor.counter("serving/gen_errors_total")
    e0 = errors.value
    flight_recorder.reset_recorder()
    sched = ContinuousBatcher(eng, queue_capacity=8)
    try:
        seen = []
        doomed = [sched.submit(p, max_new_tokens=12, temperature=0.0,
                               on_token=seen.append)
                  for p in ([3, 4, 5], [6, 7])]
        sched.start()
        for r in doomed:
            with pytest.raises(RuntimeError, match=half + " failed"):
                r.wait(timeout=60)
        delivered = len(seen)
        assert errors.value - e0 == 2  # once each
        assert sum(e["kind"] == "generation_step_error"
                   for e in flight_recorder.events()) == 1
        # the loop lives, and the next request reads what it reads alone
        nxt = sched.submit([9, 8, 7], max_new_tokens=5, temperature=0.0)
        assert nxt.wait(timeout=60) == _engine(model, slots=1).generate(
            [[9, 8, 7]], max_new_tokens=5, temperature=0.0)[0]
        assert len(seen) == delivered  # nothing of the dropped steps
        assert sched.live_slots == 0
    finally:
        sched.stop(drain=False)


@pytest.mark.parametrize("how", ["stop", "deadline"])
def test_nothing_is_delivered_to_a_request_that_has_ended(model, how):
    """``stop(drain=False)`` with a step in flight and another behind
    it, and a deadline that ran out in the queue: no token reaches a
    request after it was failed."""
    from paddle_tpu.serving import DeadlineExceededError

    eng = _engine(model, slots=1).warmup()
    sched = ContinuousBatcher(eng, queue_capacity=8).start()
    late, seen, box = [], [], {}

    def on_token(tok):
        (late if box["req"].finished else seen).append(tok)

    try:
        box["req"] = sched.submit([3, 4, 5], max_new_tokens=20,
                                  temperature=0.0, on_token=on_token)
        if how == "deadline":
            waiting = []
            queued = sched.submit([6, 7], max_new_tokens=4, deadline_ms=1,
                                  on_token=waiting.append)
            with pytest.raises(DeadlineExceededError):
                queued.wait(timeout=60)
            assert box["req"].wait(timeout=60) == seen and len(seen) == 20
            assert waiting == [] and queued.tokens == []
        else:
            while len(seen) < 3:
                time.sleep(0.001)
            sched.stop(drain=False)
            with pytest.raises(ServingClosedError):
                box["req"].wait(timeout=1)
            assert 3 <= len(seen) < 20 and box["req"].tokens == seen
        assert late == [] and sched.live_slots == 0
    finally:
        sched.stop(drain=False)


# -- HTTP frontend ------------------------------------------------------------

def _post(url, payload, timeout=120):
    body = json.dumps(payload).encode()
    try:
        r = urlopen(Request(url + "/generate", data=body), timeout=timeout)
        return r.status, json.loads(r.read())
    except HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def test_generate_http_end_to_end(model):
    ref_eng = _engine(model, slots=1).warmup()
    srv = GenerationServer(_engine(model, slots=2), port=0,
                           queue_capacity=16)
    try:
        srv.start(warmup=False)
        # readiness gates on warmup (prefill ladder + decode compiled)
        with pytest.raises(HTTPError) as ei:
            urlopen(srv.url + "/healthz")
        assert ei.value.code == 503
        status, _ = _post(srv.url, {"prompt": [5, 6, 7]})
        assert status == 503
        srv.warmup()
        hz = json.loads(urlopen(srv.url + "/healthz").read())
        assert hz["ready"] and hz["prefill_buckets"] == list(BUCKETS)

        prompt = [5, 6, 7, 8]
        ref = ref_eng.generate([prompt], max_new_tokens=6,
                               temperature=0.0)[0]
        status, out = _post(srv.url, {"prompt": prompt,
                                      "max_new_tokens": 6,
                                      "temperature": 0.0})
        assert status == 200 and out["tokens"] == ref
        assert out["finish_reason"] in ("length", "eos")
        assert out["prompt_tokens"] == 4

        # malformed requests answer 400, never 500
        for bad in ({}, {"prompt": []}, {"prompt": "abc"},
                    {"prompt": [1.5]}, [1, 2],
                    {"prompt": [1] * 9},            # > largest bucket
                    {"prompt": [1], "max_new_tokens": "x"}):
            status, _ = _post(srv.url, bad)
            assert status == 400, bad

        sz = json.loads(urlopen(srv.url + "/statz").read())
        assert sz["requests"]["completed"] >= 1
        assert sz["generation"]["tokens_generated"] >= 6
        assert sz["generation"]["tokens_per_sec"] > 0
        assert "slot_occupancy" in sz["generation"]
        assert sz["latency"]["token"]["p99_ms"] >= 0
        assert sz["compiles"]["unexpected"] == 0
        assert sz["compiles"]["prefill_buckets"] == len(BUCKETS)
        prom = urlopen(srv.url + "/metrics").read().decode()
        assert "serving_gen_tokens_total" in prom
    finally:
        srv.stop(drain=False)


def test_generate_http_streaming(model):
    srv = GenerationServer(_engine(model, slots=2), port=0,
                           queue_capacity=8)
    try:
        srv.start()
        body = json.dumps({"prompt": [5, 6, 7], "max_new_tokens": 5,
                           "temperature": 0.0, "stream": True}).encode()
        r = urlopen(Request(srv.url + "/generate", data=body), timeout=120)
        assert r.headers.get("Content-Type", "").startswith(
            "application/x-ndjson")
        lines = [json.loads(l) for l in r.read().decode().splitlines()]
        toks = [l["token"] for l in lines if "token" in l]
        final = lines[-1]
        assert final["done"] and final["tokens"] == toks
        assert len(toks) == 5
        # streamed greedy == non-streamed greedy
        status, out = _post(srv.url, {"prompt": [5, 6, 7],
                                      "max_new_tokens": 5,
                                      "temperature": 0.0})
        assert status == 200 and out["tokens"] == toks
    finally:
        srv.stop(drain=False)


def test_generate_http_429_and_drain(model):
    srv = GenerationServer(_engine(model, slots=1), port=0,
                           queue_capacity=1)
    try:
        srv.start()
        # wedge the queue: don't start draining it (pause by filling the
        # single slot with a long request, then one queued + one over)
        results = []

        def client(budget):
            results.append(_post(srv.url, {"prompt": [3, 4],
                                           "max_new_tokens": budget,
                                           "temperature": 0.0}))

        threads = [threading.Thread(target=client, args=(24,))
                   for _ in range(3)]
        for t in threads:
            t.start()
            time.sleep(0.05)
        for t in threads:
            t.join(timeout=120)
        codes = sorted(c for c, _ in results)
        assert codes.count(200) >= 2 and all(
            c in (200, 429) for c in codes), codes
        srv.stop(drain=True)
        assert srv.scheduler.live_slots == 0
        assert srv.scheduler.alive == 0
        with pytest.raises(OSError):  # the listener went with the loop
            urlopen(srv.url + "/healthz", timeout=2)
    finally:
        srv.stop(drain=False)

"""The ring KV cache has one owner and is written in place (PR 26).

Pins the ownership rule (every ring program is given the cache donated:
the arrays held before a call are deleted after it, ``engine._kv`` holds
live ones), the counter that says the mechanism engaged (the programs'
``CostRecord.alias_bytes`` cover the cache), the failure rule (a call
that raised after it consumed the cache loses every slot: a zeroed,
usable ring, ``generation::cache_lost`` counted, every live request
failed, the next one served), and ``CompiledStore.precompile`` with the
warm-up that is built on it.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.generation import (
    COMPILE_COUNTER,
    CacheLostError,
    GenerationEngine,
)
from paddle_tpu.generation.engine import CACHE_LOST_COUNTER
from paddle_tpu.models import GPTForCausalLM, gpt_tiny_config
from paddle_tpu.monitor import cost_model, flight_recorder
from paddle_tpu.runtime.compiled import CompiledStore
from paddle_tpu.serving import ContinuousBatcher

CACHE = 32
BUCKETS = (4, 8)


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = gpt_tiny_config()
    cfg.attention_window = CACHE
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _engine(model, slots=3, **kw):
    return GenerationEngine(model, slots=slots, cache_len=CACHE,
                            prefill_buckets=BUCKETS, seed=7, **kw)


def _leaves(engine):
    return jax.tree_util.tree_leaves(engine._kv)


def _lost():
    return profiler.counters().get(CACHE_LOST_COUNTER, 0)


# -- ownership ----------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("call", ["admit", "step"])
def test_call_consumes_the_cache_it_was_given(model, dtype, call):
    eng = _engine(model, kv_cache_dtype=dtype).warmup()
    eng.admit(0, [5, 6, 7])
    before = _leaves(eng)
    # one leaf per layer and plane, plus pos: nothing is stacked
    layers = model.cache_spec()[0]
    assert len(before) == layers * (4 if dtype == "int8" else 2) + 1
    if call == "admit":
        eng.admit(1, [9, 8, 7, 6, 5])
    else:
        eng.step(np.zeros(eng.slots, np.int32),
                 np.zeros(eng.slots, np.float32))
    assert all(a.is_deleted() for a in before)
    after = _leaves(eng)
    assert not any(a.is_deleted() for a in after)
    assert [a.shape for a in after] == [a.shape for a in before]


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_programs_alias_the_whole_cache(model, dtype):
    """The compiler's own account: input and output share the cache's
    buffers (``program_hbm_bytes.decode`` reads this record)."""
    eng = _engine(model, kv_cache_dtype=dtype).warmup()
    eng.generate([[5, 6, 7]], max_new_tokens=3)
    pos_bytes = eng.slots * 4
    for label in ("generation_decode", "generation_prefill"):
        rec = cost_model.latest_record(label)
        assert rec is not None and not rec.partial
        assert rec.alias_bytes >= eng.cache_nbytes() - pos_bytes, label


def test_speculative_programs_consume_both_rings(model):
    from paddle_tpu.generation.engine import GenerationEngine as GE

    draft = GPTForCausalLM(model.config)
    draft.eval()
    eng = GE(model, draft_model=draft, draft_k=2, slots=2, cache_len=CACHE,
             prefill_buckets=BUCKETS, seed=7).warmup()
    before = eng._cache_leaves()
    eng.admit(0, [5, 6, 7])
    assert all(a.is_deleted() for a in before)
    before = eng._cache_leaves()
    eng.spec_step(np.zeros(2, np.int32), np.zeros(2, np.float32))
    assert all(a.is_deleted() for a in before)
    assert not any(a.is_deleted() for a in eng._cache_leaves())


def test_position_zero_is_an_empty_ring(model):
    """With one owner, a ring whose positions are rewound is an empty
    ring whatever its arrays hold: the masks hide every entry at or
    beyond a slot's position and an admission overwrites its whole
    slot. What follows equals a never-used engine's output."""
    prompts = [[5, 6, 7], [9, 8, 7, 6, 5, 4], [3]]
    fresh = _engine(model)
    fresh.watch.arm()
    want = fresh.generate(prompts, max_new_tokens=CACHE + 5,
                          temperature=0.0, stop_at_eos=False)
    eng = _engine(model)
    eng.watch.arm()
    eng.generate([[7, 7, 7, 7], [2, 3], [4, 5, 6]], max_new_tokens=CACHE,
                 temperature=0.0, stop_at_eos=False)
    assert any(bool(jnp.any(a != 0)) for a in _leaves(eng)[:-1])
    eng._kv = eng._kv[:-1] + (jnp.zeros_like(eng._kv[-1]),)
    got = eng.generate(prompts, max_new_tokens=CACHE + 5,
                       temperature=0.0, stop_at_eos=False)
    assert got == want


def test_warmup_leaves_a_zeroed_ring(model):
    eng = _engine(model).warmup()
    assert not any(bool(jnp.any(a != 0)) for a in _leaves(eng))


# -- the failure rule ---------------------------------------------------------

def _fail_after_consuming(engine, label, times=1):
    """Make the next ``times`` dispatches of ``label`` run (so the cache
    is consumed) and then raise, as a device fault after launch would."""
    store = engine._stores[label]
    real = store.dispatch
    left = [times]

    def dispatch(entry, *args, **kw):
        out = real(entry, *args, **kw)
        if left[0] > 0:
            left[0] -= 1
            raise RuntimeError("injected fault after launch")
        return out

    store.dispatch = dispatch


@pytest.mark.parametrize("call", ["step", "admit"])
def test_engine_rebuilds_a_zeroed_ring(model, call):
    # first: the compile counter is the process's, not the engine's
    want = _engine(model).warmup().generate(
        [[5, 6, 7]], max_new_tokens=6, temperature=0.0)
    eng = _engine(model).warmup()
    eng.admit(0, [5, 6, 7])
    lost0 = _lost()
    _fail_after_consuming(eng, "decode" if call == "step" else "prefill")
    with pytest.raises(CacheLostError, match="injected fault"):
        if call == "step":
            eng.step(np.zeros(eng.slots, np.int32),
                     np.zeros(eng.slots, np.float32))
        else:
            eng.admit(1, [1, 2, 3])
    assert _lost() == lost0 + 1
    ev = [e for e in flight_recorder.events()
          if e.get("kind") == "generation_cache_lost"]
    assert ev and "injected fault" in ev[-1]["error"]
    leaves = _leaves(eng)
    assert not any(a.is_deleted() for a in leaves)
    assert not any(bool(jnp.any(a != 0)) for a in leaves)
    # usable, and no program compiled again
    assert eng.generate([[5, 6, 7]], max_new_tokens=6,
                        temperature=0.0) == want
    assert eng.extra_compiles() == 0


def test_error_that_consumed_nothing_is_not_a_lost_cache(model):
    eng = _engine(model).warmup()
    lost0 = _lost()
    store = eng._stores["decode"]

    def dispatch(entry, *args, **kw):
        raise RuntimeError("refused before launch")

    store.dispatch = dispatch
    held = _leaves(eng)
    with pytest.raises(RuntimeError, match="refused before launch"):
        eng.step(np.zeros(eng.slots, np.int32),
                 np.zeros(eng.slots, np.float32))
    assert _lost() == lost0
    assert _leaves(eng)[0] is held[0]


def _wait_for(cond, timeout=30.0):
    t0 = time.monotonic()
    while not cond():
        if time.monotonic() - t0 > timeout:
            raise AssertionError("condition not reached")
        time.sleep(0.005)


@pytest.mark.parametrize("call", ["step", "admit"])
def test_scheduler_fails_every_live_request(model, call):
    """A failing ``step``, or a failing ``admit`` with two other slots
    live, fails every request that holds a slot with the error; the
    next request is served with a fresh engine's tokens."""
    want = _engine(model).warmup().generate(
        [[5, 6, 7, 8]], max_new_tokens=6, temperature=0.0)[0]
    eng = _engine(model, slots=3).warmup()
    sched = ContinuousBatcher(eng, queue_capacity=16).start()
    lost0 = _lost()
    try:
        live = [sched.submit([3 + i, 4, 5], max_new_tokens=CACHE,
                             temperature=0.0) for i in range(2)]
        _wait_for(lambda: sched.live_slots == 2)
        if call == "step":
            _fail_after_consuming(eng, "decode")
            victims = live
        else:
            _fail_after_consuming(eng, "prefill")
            victims = live + [sched.submit([9, 9, 9], max_new_tokens=4,
                                           temperature=0.0)]
        for r in victims:
            with pytest.raises(CacheLostError):
                r.wait(timeout=60)
        _wait_for(lambda: sched.live_slots == 0)
        assert _lost() == lost0 + 1
        after = sched.submit([5, 6, 7, 8], max_new_tokens=6,
                             temperature=0.0)
        assert after.wait(timeout=60) == want
        assert sched.extra_compiles() == 0
    finally:
        sched.stop(drain=False)


# -- CompiledStore.precompile -------------------------------------------------

def test_first_dispatch_waits_for_the_worker_and_compiles_nothing():
    store = CompiledStore("precompile_test", miss_counter="pc_test::miss")
    gate = threading.Event()
    compiles = []
    real = CompiledStore._compile

    def slow(self, *a):
        gate.wait(30)
        compiles.append(threading.current_thread().name)
        real(self, *a)

    store._compile = slow.__get__(store)
    jitted = jax.jit(lambda x: x * 2.0)
    x = jnp.arange(4.0)
    miss0 = profiler.counters().get("pc_test::miss", 0)
    done = store.precompile("sig", lambda: (jitted, None), (x,))
    assert not done.done()
    entry, disposition = store.get_or_build("sig", lambda: (jitted, None))
    assert disposition == "hit" and not entry.attempted
    out = []
    t = threading.Thread(
        target=lambda: out.append(store.dispatch(entry, x)))
    t.start()
    time.sleep(0.1)
    assert t.is_alive() and not out  # waiting for the executable
    gate.set()
    t.join(30)
    assert done.result(30) is entry and entry.attempted
    np.testing.assert_allclose(np.asarray(out[0]), np.arange(4.0) * 2)
    assert compiles == [f"precompile-{entry.cache_key}"]
    assert profiler.counters().get("pc_test::miss", 0) == miss0 + 1
    assert entry.record is not None  # the capture ran on the worker too
    # a second precompile of a compiled entry is done at once
    assert store.precompile("sig", lambda: (jitted, None), (x,)).done()
    assert len(compiles) == 1


def test_failing_compile_raises_at_the_dispatch():
    store = CompiledStore("precompile_fail")
    real = CompiledStore._compile
    calls = []

    def broken(self, *a):
        calls.append(threading.current_thread().name)
        if len(calls) == 1:
            raise RuntimeError("compiler said no")
        real(self, *a)

    store._compile = broken.__get__(store)
    jitted = jax.jit(lambda x: x + 1.0)
    x = jnp.ones(3)
    done = store.precompile("sig", lambda: (jitted, None), (x,))
    entry = done.result(30)  # done, though the compile failed
    assert not entry.attempted and entry.aot is None
    # the dispatch compiles again, on its own thread, and would raise
    # there what the compiler raises; here the second attempt passes
    out = store.dispatch(entry, x)
    assert calls[1] == threading.current_thread().name
    np.testing.assert_allclose(np.asarray(out), 2.0)


def test_failing_compile_error_surfaces_on_the_dispatching_thread():
    store = CompiledStore("precompile_fail2")

    def broken(*a):
        raise RuntimeError("compiler said no")

    store._compile = broken
    jitted = jax.jit(lambda x: x + 1.0)
    x = jnp.ones(3)
    entry = store.precompile("sig", lambda: (jitted, None), (x,)).result(30)
    with pytest.raises(RuntimeError, match="compiler said no"):
        store.dispatch(entry, x)
    assert not entry.attempted


@pytest.mark.parametrize("kind,extra", [
    ("generate", {}), ("prefill", {}), ("decode", {}),
    ("generate", {"kv_cache_dtype": "int8"}),
    ("generate", {"kv_cache_layout": "paged", "kv_page_size": 4}),
])
def test_warmup_counts_exactly_its_programs(model, kind, extra):
    eng = _engine(model, **extra)
    c0 = profiler.counters().get(COMPILE_COUNTER, 0)
    eng.warmup(kind=kind)
    assert (profiler.counters().get(COMPILE_COUNTER, 0) - c0
            == eng.expected_compiles(kind))
    if kind == "generate":
        eng.generate([[5, 6, 7], [1, 2, 3, 4, 5, 6, 7]], max_new_tokens=5)
    elif kind == "prefill":
        eng.prefill_export([5, 6, 7])
    else:
        eng.step(np.zeros(eng.slots, np.int32),
                 np.zeros(eng.slots, np.float32))
    assert eng.extra_compiles() == 0
    # every program was compiled by a precompile worker, none by a
    # dispatch (the paged layout compiles at its dispatches, as before)
    entries = [e for s in eng._stores.values()
               for e in s.entries().values()]
    assert len(entries) == eng.expected_compiles(kind)
    assert all(e.attempted and e.aot is not None for e in entries)


def test_warmup_compiles_on_workers_not_at_dispatch(model, monkeypatch):
    threads = []
    real = CompiledStore._compile

    def spy(self, *a):
        threads.append(threading.current_thread().name)
        real(self, *a)

    monkeypatch.setattr(CompiledStore, "_compile", spy)
    eng = _engine(model).warmup()
    assert len(threads) == eng.expected_compiles()
    # a worker of its own for each program
    assert len(set(threads)) == len(threads)
    assert all(t.startswith("precompile-generation_") for t in threads)


def test_overlapping_precompiles_book_their_compile_time_once(monkeypatch):
    """The goodput ledger counts wall time once: two programs that
    compile at the same time on their workers cost the caller its wait
    at the first dispatch, not the sum of the two compiles."""
    from paddle_tpu.monitor import goodput

    real = CompiledStore._compile

    def slow(self, entry, lowered, meta, span):
        with span:
            time.sleep(0.4)
        real(self, entry, lowered, meta, span)

    monkeypatch.setattr(CompiledStore, "_compile", slow)
    goodput.stop_ledger()
    ledger = goodput.start_ledger()
    try:
        store = CompiledStore("precompile_goodput")
        x = jnp.ones(3)
        t0 = time.perf_counter()
        jits = [jax.jit(lambda x: x + 1.0), jax.jit(lambda x: x * 3.0)]
        for i, j in enumerate(jits):
            store.precompile(i, lambda j=j: (j, None), (x,))
        for i, j in enumerate(jits):
            entry, _ = store.get_or_build(i, lambda j=j: (j, None))
            store.dispatch(entry, x)
        wall = time.perf_counter() - t0
        booked = ledger.phase_s["compile"]
    finally:
        goodput.stop_ledger()
    assert 0.3 < booked <= wall + 0.01, (booked, wall)
    assert wall < 0.8 + 0.4  # the two compiles did overlap


def test_rng_key_first_asked_for_inside_a_trace_is_the_eager_key():
    """An engine traced before any eager RNG use makes the default
    generator's key inside that trace: it must be a concrete key, and
    the one an eager first use gives (BERT's dropout masks hang on it)."""
    from paddle_tpu.framework.random import Generator

    want = np.asarray(jax.random.key_data(Generator(1234).get_state()))
    traced = Generator(1234)
    keys = []

    @jax.jit
    def f(x):
        keys.append(traced.get_state())  # what _swapped_model saves
        return x + jax.random.normal(traced.split(), x.shape)

    f(jnp.zeros(3))
    assert not isinstance(keys[0], jax.core.Tracer)
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(keys[0])), want)


# -- equal layers are traced once ---------------------------------------------

def _layer_forwards(model, monkeypatch):
    """Count python runs of the decoder layers' forward."""
    from paddle_tpu.nn.transformer import TransformerDecoderLayer

    calls = []
    real = TransformerDecoderLayer.forward

    def spy(self, *a, **k):
        calls.append(self)
        return real(self, *a, **k)

    monkeypatch.setattr(TransformerDecoderLayer, "forward", spy)
    return calls


@pytest.mark.parametrize("extra", [
    {}, {"kv_cache_dtype": "int8"},
    {"kv_cache_layout": "paged", "kv_page_size": 4},
])
def test_a_program_traces_one_layer_and_serves_the_same_tokens(
        model, monkeypatch, extra):
    prompts = [[5, 6, 7], [9, 8, 7, 6, 5, 4], [3]]
    calls = _layer_forwards(model, monkeypatch)
    eng = _engine(model, **extra).warmup()
    # one python forward per program, of layer 0, whatever the depth
    assert len(calls) == eng.expected_compiles()
    assert all(c is model.gpt.layers[0] for c in calls)
    got = eng.generate(prompts, max_new_tokens=CACHE + 5,
                       temperature=0.0, stop_at_eos=False)
    monkeypatch.setattr(type(model.gpt), "_layers_alike", lambda self: False)
    del calls[:]
    loop = _engine(model, **extra).warmup()
    assert len(calls) == loop.expected_compiles() * len(model.gpt.layers)
    assert got == loop.generate(prompts, max_new_tokens=CACHE + 5,
                                temperature=0.0, stop_at_eos=False)


def test_layers_that_differ_are_each_traced(model, monkeypatch):
    gpt = model.gpt
    assert gpt._layers_alike()
    seen = []
    handle = gpt.layers[1].linear1.register_forward_post_hook(
        lambda layer, inputs, out: seen.append(layer))
    try:
        assert not gpt._layers_alike()  # the hook is layer 1's alone
        _engine(model).warmup()
        assert seen and all(s is gpt.layers[1].linear1 for s in seen)
    finally:
        handle.remove()
    assert gpt._layers_alike()
    gpt.layers[0].train()
    try:
        assert not gpt._layers_alike()  # dropout would draw per layer
    finally:
        gpt.layers[0].eval()

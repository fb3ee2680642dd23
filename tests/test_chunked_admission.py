"""A long prompt admitted a chunk at a time between decode steps (PR 45):
the seam in `CachedGQAttention` (a chunk from the ring equals the
whole-prompt forward), in the engine (chunked admission serves the whole
admission's tokens with other slots' decode steps run between the chunks,
through ONE more program) and in the scheduler (live streams get a token
every iteration, the look-ahead survives a chunk that is not the last, a
request given up between chunks frees its slot, a cache with a state kind
is admitted whole as ever). The toy decoder is `test_exaone_moe`'s: four
window layers of 8 rows and one full layer of 32."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_exaone_moe as toy
from paddle_tpu import profiler
from paddle_tpu.errors import PreconditionNotMetError
from paddle_tpu.generation import cache as gcache
from paddle_tpu.nn import ContinuedCache, StaticCache
from paddle_tpu.serving import ContinuousBatcher
from paddle_tpu.serving.batcher import (DeadlineExceededError,
                                        ServingClosedError)

LADDER = (4, 8, 16, 32)
CHUNK = 8  # the ladder's second bucket


@pytest.fixture(scope="module")
def model():
    return toy._model()


# -- (a) the layer -----------------------------------------------------------


def _by_chunks(m, x, kind, ring, chunk, steps_between):
    """``x [1, T, hidden]`` through ``m`` a chunk at a time into one
    slot's ring, the last chunk right-padded; with ``steps_between`` a
    decode step of a garbage token runs over the half-filled ring after
    every chunk but the last. Returns (outputs of the real rows, the
    ring's K)."""
    t, dtype = x.shape[1], x.dtype
    arrays = kind.arrays(1, ring, dtype)
    outs = []
    for lo in range(0, t, chunk):
        n = min(chunk, t - lo)
        xc = jnp.concatenate(
            [x[:, lo:lo + n], jnp.ones((1, chunk - n, x.shape[2]), dtype)], 1)
        mask = jnp.where(jnp.arange(chunk) < n, 0.0, gcache.NEG_INF)[
            None, None, None].astype(jnp.float32)
        y, cache = m(xc, cache=ContinuedCache(
            *arrays, jnp.asarray([lo], jnp.int32)), mask=mask,
            positions=(lo + jnp.arange(chunk))[None])
        assert type(cache) is StaticCache
        outs.append(y[:, :n])
        arrays = tuple(cache)[:2]
        if steps_between and lo + n < t:
            pos = jnp.asarray([lo + n], jnp.int32)
            _, cache = m(jnp.full((1, 1, x.shape[2]), 3.0, dtype),
                         cache=StaticCache(*arrays, pos),
                         mask=gcache.decode_mask(pos, arrays[0].shape[2]),
                         positions=pos[:, None])
            arrays = tuple(cache)[:2]
    return jnp.concatenate(outs, 1), arrays[0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window,ring,t,chunk,key_chunk", [
    (None, 32, 29, 8, None),   # full ring, the last chunk 5 of 8
    (None, 32, 32, 8, None),   # the prompt fills the ring to its last row
    (None, 64, 50, 16, 16),    # the ring read 16 keys at a time
    (None, 48, 41, 8, 32),     # a ring that is no whole number of pieces
    (None, 40, 37, 8, None),   # five chunks to the ring, four and a bit used
    (8, 32, 29, 8, None),      # window ring, wrapped three times, ring == chunk
    (8, 32, 27, 16, None),     # a chunk of two rings
    (8, 32, 29, 4, None),      # a ring of two chunks
])
def test_a_prompt_by_chunks_from_the_ring_is_the_whole_forward(
        window, ring, t, chunk, key_chunk, dtype):
    """Rotary in the window layers as the family has it. Float32 to
    rounding; bfloat16 to three of its steps at the outputs' size (the
    sums run in another order; the rows the ring ends with are the same
    bits)."""
    m = toy.CachedGQAttention(
        32, 4, 2, 8, qk_norm=True, rope_theta=None if window is None else 1e4,
        window=window, prefill_block=4, key_chunk=key_chunk,
        initializer_range=0.3, dtype=dtype)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, t, 32)).astype(dtype)
    want = m(x, positions=jnp.arange(t)[None]).astype(jnp.float32)
    kind = gcache.kv(2, 8, window=window)
    _, whole = m(x, cache=kind.wrap(kind.arrays(1, ring, dtype),
                                    jnp.zeros((1,), jnp.int32)),
                 mask=jnp.zeros((1, 1, 1, t), jnp.float32),
                 positions=jnp.arange(t)[None])
    live = np.arange(max(t - whole.k.shape[2], 0), t) % whole.k.shape[2]
    tol = 2e-6 if dtype == "float32" else 3 * 2.0 ** -8 * float(
        jnp.abs(want).max())
    for steps_between in (False, True):
        got, k = _by_chunks(m, x, kind, ring, chunk, steps_between)
        np.testing.assert_allclose(got.astype(jnp.float32), want, atol=tol)
        np.testing.assert_array_equal(
            np.asarray(k[:, :, live].astype(jnp.float32)),
            np.asarray(whole.k[:, :, live].astype(jnp.float32)))


def test_only_a_cache_of_rings_says_it_continues():
    kinds = [gcache.kv(2, 8), gcache.kv(2, 8, window=4)]
    assert gcache.kinds_continue(kinds)
    assert not gcache.kinds_continue(
        kinds + [gcache.state([(4,)], ["float32"])])
    assert not gcache.kinds_continue(kinds + [gcache.latent(8, 4)])
    assert not gcache.kinds_continue([])


# -- (b) the engine ----------------------------------------------------------


def _drive(eng, short, long, chunked, steps=40):
    """Slot 1 streams from ``short`` while ``long`` goes into slot 0: by
    chunks with one decode step between two of them, or whole after as
    many steps. Returns both slots' greedy tokens."""
    temps = np.zeros(2, np.float32)
    last = np.zeros(2, np.int32)
    out = {0: [], 1: [eng.admit(1, short)]}
    last[1] = out[1][0]

    def step(slots):
        nxt = eng.step(last, temps)
        for s in slots:
            out[s].append(int(nxt[s]))
            last[s] = nxt[s]

    between = -(-len(long) // CHUNK) - 1
    if chunked:
        adm = eng.begin_admission(0, long)
        while True:
            eng.enqueue_chunk(adm)
            assert eng._pos_host[0] == adm.lo
            if adm.done:
                break
            step([1])
        assert adm.chunks == between + 1
        with pytest.raises(PreconditionNotMetError, match="not fetched"):
            step([1])
        tok = eng.fetch_admission(adm)
    else:
        assert eng.begin_admission(0, long) is None
        for _ in range(between):
            step([1])
        tok = eng.admit(0, long)
    out[0].append(tok)
    last[0] = tok
    for _ in range(steps):
        step([0, 1])
        np.testing.assert_array_equal(eng._pos_host, np.asarray(eng._kv[-1]))
    return out


@pytest.fixture(scope="module")
def engines(model):
    """The toy decoder's engine as it is (chunks of 8) and one made to
    admit whole; both warmed before either serves (the compile counter
    is the process's)."""
    m, _ = model
    eng, whole = (toy._engine(m, prefill_buckets=LADDER) for _ in range(2))
    whole.chunk_len = None
    assert eng.chunk_len == CHUNK
    # the 4 and 8 buckets, the chunk program, the decode program | the
    # ladder + 1
    assert (eng.expected_compiles(), whole.expected_compiles()) == (4, 5)
    before = profiler.counters().get("generation::compile", 0)
    eng.warmup()
    assert profiler.counters()["generation::compile"] - before == 4
    whole.warmup()
    return eng, whole


def _compiled(eng):
    """Programs the engine holds (the compile counter is the process's,
    and a profiler reset zeroes it)."""
    return sum(len(store) for store in eng._stores.values())


@pytest.mark.parametrize("n", [29, 32, 17, 9])
def test_chunked_admission_serves_the_whole_admissions_tokens(engines, n):
    """Greedy, float32: the same tokens in both slots, with the other
    slot's decode step run over the half-filled slot between every two
    chunks: the row it leaves is rewritten (the full ring) or out of the
    band (the window rings, which wrap under the prompt and again under
    the 40 steps, as the full ring does). A last chunk of 5, of 8, of 1."""
    eng, whole = engines
    short, long = toy._tokens(5, seed=1).tolist(), toy._tokens(n, n).tolist()
    for e in engines:
        e.reset()
    assert _drive(eng, short, long, True) == _drive(whole, short, long, False)
    assert (_compiled(eng), _compiled(whole)) == (4, 5)


def test_a_second_step_between_two_chunks_is_refused(engines):
    eng, _ = engines
    eng.reset()
    zeros = np.zeros(2, np.int32), np.zeros(2, np.float32)
    adm = eng.begin_admission(0, toy._tokens(20).tolist())
    with pytest.raises(PreconditionNotMetError, match="one prompt"):
        eng.begin_admission(1, toy._tokens(20).tolist())
    eng.enqueue_chunk(adm)
    eng.step(*zeros)
    with pytest.raises(PreconditionNotMetError, match="second decode step"):
        eng.step(*zeros)
    with pytest.raises(PreconditionNotMetError, match="not in yet"):
        eng.fetch_admission(adm)
    eng.enqueue_chunk(adm)  # the refused step took nothing
    assert (adm.lo, adm.chunks, adm.done) == (16, 2, False)
    eng.abandon_admission(adm)
    with pytest.raises(PreconditionNotMetError, match="abandoned"):
        eng.enqueue_chunk(adm)
    eng.step(*zeros)
    eng.step(*zeros)
    assert _compiled(eng) == 4


# -- (c) the scheduler -------------------------------------------------------


def _samples():
    return [(s["name"], s["args"]["value"])
            for s in profiler.counter_samples()
            if s["name"] in ("generation::prefill_chunks",
                             "serving::steps_ahead")]


class _Streaming:
    """A request that streams from a short prompt; ``wait_tokens(n)``
    returns once ``n`` have come."""

    def __init__(self, sched, budget=24):
        self.got, self.cond = [], threading.Condition()
        self.req = sched.submit(toy._tokens(5, seed=1).tolist(),
                                max_new_tokens=budget, temperature=0.0,
                                on_token=self._on)

    def _on(self, tok):
        with self.cond:
            self.got.append(tok)
            self.cond.notify_all()

    def wait_tokens(self, n):
        with self.cond:
            assert self.cond.wait_for(lambda: len(self.got) >= n, 60)


@pytest.fixture()
def served(engines):
    eng, _ = engines
    eng.reset()
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    sched = ContinuousBatcher(eng, queue_capacity=8).start()
    yield eng, sched
    sched.stop(drain=False)
    profiler.stop_profiler()
    profiler.reset_profiler()


def test_live_streams_get_a_token_in_every_iteration_of_a_chunk(
        served, engines):
    """A streams; B's prompt of 29 goes in as four chunks, one an
    iteration: each chunk but the last is followed by a decode step that
    is enqueued AHEAD (the look-ahead stays 1) and by A's token; the
    last one drains, and A's token of the step in flight is delivered
    before B's first is waited for. Both serve what the whole admission
    serves."""
    eng, sched = served
    _, whole = engines
    a = _Streaming(sched)
    a.wait_tokens(4)
    profiler.reset_profiler()
    long = toy._tokens(29, 29).tolist()
    b = sched.submit(long, max_new_tokens=6, temperature=0.0)
    b.wait(60)
    a.req.wait(60)
    chunks = [v for n, v in _samples() if n == "generation::prefill_chunks"]
    assert chunks == [[1, 0], [2, 0], [3, 0], [4, 1]]
    # the iteration's own sample follows its chunk's
    ahead = [nxt[1] for cur, nxt in zip(_samples(), _samples()[1:])
             if cur[0] == "generation::prefill_chunks"]
    assert ahead == [1, 1, 1, 0]
    spans = sorted((e["ts"], e["name"]) for e in profiler.host_events())
    names = [n for _, n in spans if n in (
        "generation::prefill", "generation::prefill_fetch",
        "generation::decode", "serving::deliver", "serving::install")]
    first = names.index("generation::prefill")
    assert names[first:first + 13] == [
        "generation::prefill", "generation::decode", "serving::deliver",
    ] * 3 + ["generation::prefill", "serving::deliver",
             "generation::prefill_fetch", "serving::install"]
    whole.reset()
    assert b.tokens == whole.generate([long], max_new_tokens=6,
                                      stop_at_eos=False)[0]
    assert a.req.tokens == whole.generate(
        [toy._tokens(5, seed=1).tolist()], max_new_tokens=24,
        stop_at_eos=False)[0]
    assert _compiled(eng) == 4


def test_one_prompts_chunks_behind_anothers_never_put_two_in_a_gap(
        served, engines):
    """Under load a prompt's first chunk goes in the iteration after
    the last chunk of the prompt before it, and no step is in flight
    there (that admission drained the look-ahead): the live stream's
    step is enqueued BEFORE the chunk, from the host's tokens, and the
    iteration looks ahead at once. Between two chunk programs there is
    always a decode step, so no gap of A's holds two chunks."""
    eng, sched = served
    _, whole = engines
    a = _Streaming(sched, budget=40)
    a.wait_tokens(4)
    profiler.reset_profiler()
    prompts = [toy._tokens(20, 20).tolist(), toy._tokens(29, 29).tolist()]
    # two slots: the first prompt's request ends with its first token
    # and leaves its slot to the second
    reqs = [sched.submit(p, max_new_tokens=n, temperature=0.0)
            for p, n in zip(prompts, (1, 4))]
    for r in reqs:
        r.wait(60)
    spans = sorted((e["ts"], e["name"]) for e in profiler.host_events())
    names = [n for _, n in spans if n in (
        "generation::prefill", "generation::prefill_fetch",
        "generation::decode", "serving::install")]
    first = names.index("generation::prefill")
    assert names[first:first + 16] == [
        # 20 tokens: three chunks, the last one's token waited for
        "generation::prefill", "generation::decode",
        "generation::prefill", "generation::decode",
        "generation::prefill", "generation::prefill_fetch",
        "serving::install",
        # 29 tokens behind it: the step first, then a chunk and a step
        "generation::decode", "generation::prefill", "generation::decode",
        "generation::prefill", "generation::decode",
        "generation::prefill", "generation::decode",
        "generation::prefill", "generation::prefill_fetch"]
    a.req.wait(60)
    for r, p, n in zip(reqs, prompts, (1, 4)):
        whole.reset()
        assert r.tokens == whole.generate([p], max_new_tokens=n,
                                          stop_at_eos=False)[0]
    whole.reset()
    assert a.req.tokens == whole.generate(
        [toy._tokens(5, seed=1).tolist()], max_new_tokens=40,
        stop_at_eos=False)[0]
    assert _compiled(eng) == 4


@pytest.mark.parametrize("how", ["failed from outside", "deadline"])
def test_a_request_given_up_between_chunks_frees_its_slot(
        engines, how, monkeypatch):
    eng, whole = engines
    eng.reset()
    now = [100.0]
    sched = ContinuousBatcher(eng, queue_capacity=8,
                              clock=lambda: now[0]).start()
    try:
        a = _Streaming(sched)
        a.wait_tokens(3)
        real, given_up = eng.enqueue_chunk, []

        def enqueue_chunk(adm):
            real(adm)
            if not given_up:
                given_up.append(adm)
                if how == "deadline":
                    now[0] += 5.0
                else:
                    b.done(error=ServingClosedError("gone"))

        monkeypatch.setattr(eng, "enqueue_chunk", enqueue_chunk)
        long = toy._tokens(29, 29).tolist()
        b = sched.submit(long, max_new_tokens=6, temperature=0.0,
                         deadline_ms=1000.0)
        with pytest.raises(DeadlineExceededError if how == "deadline"
                           else ServingClosedError):
            b.wait(60)
        c = sched.submit(long, max_new_tokens=6, temperature=0.0)
        assert c.wait(60) and a.req.wait(60)
    finally:
        sched.stop(drain=False)
    assert given_up[0].lo == CHUNK and eng._admission is None
    assert sched._chunked is None and sched.live_slots == 0
    whole.reset()
    assert c.tokens == whole.generate([long], max_new_tokens=6,
                                      stop_at_eos=False)[0]
    assert a.req.tokens == whole.generate(
        [toy._tokens(5, seed=1).tolist()], max_new_tokens=24,
        stop_at_eos=False)[0]


def test_a_cache_with_a_state_kind_is_admitted_whole_as_ever():
    """The hybrid decoder (K/V rings beside recurrent states): no chunk
    length, the whole ladder warmed, one program a prompt."""
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.models import HybridMoEConfig, HybridMoEForCausalLM

    m = HybridMoEForCausalLM(HybridMoEConfig(
        vocab_size=97, vocab_held=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        gqa_layers=(0,), linear_attn_config=dict(
            short_conv_kernel_size=4, head_dim=8, num_heads=4),
        kda_gate_rank=8, moe_intermediate_size=16, n_routed_experts=16,
        num_experts_per_tok=4, experts_held=(4, 8)))
    eng = GenerationEngine(m, slots=2, cache_len=32, prefill_buckets=(8, 32),
                           temperature=0.0, seed=7)
    assert eng.chunk_len is None and eng.expected_compiles() == 2 + 1
    assert eng.begin_admission(0, list(range(3, 30))) is None
    eng.warmup()
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    sched = ContinuousBatcher(eng, queue_capacity=8).start()
    try:
        sched.submit(list(range(3, 30)), max_new_tokens=3).wait(60)
        chunks = [v for n, v in _samples()
                  if n == "generation::prefill_chunks"]
    finally:
        sched.stop(drain=False)
        profiler.stop_profiler()
        profiler.reset_profiler()
    assert chunks == [[1, 1]] and eng.extra_compiles() == 0

"""The mixed sparse / Lightning decoder (models/minicpm_sala.py) and
what it forced: Lightning attention with a chunked prefill beside a
one-token step (nn/linear_attention.py), block-sparse attention whose
cache kind keeps a pooled ring (tests/test_sparse_attention.py has the
layer and the kind), a state kind with no tail, and through the
generation engine and continuous batching slots on either side of
dense_len in one batched step. Tiny widths with the real ratios (the
sparse sizes of tests/test_sparse_attention.py: stride 2, kernel 4, block
8, window 16, top-6, dense_len 64), float32, seeded; the plain reference
is the benchmark's (benchmark/configs/minicpm-sala-9b/reference.py),
which imports nothing of the program, runs the recurrence token by token
and writes the selection query by query.

Tolerances: program and reference are both float32 here and differ in
the order of their sums only (chunks, key pieces): 2e-4 on logits whose
standard deviation is 0.3, as the other kinds models' tests; a padded
position let into a state, a decay applied twice, a query that attends
everything or the rotation left out moves a logit by 1e-2 to 1 (the
planted faults at the end of this file)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.errors import InvalidArgumentError
from paddle_tpu.generation import GenerationEngine
from paddle_tpu.generation import cache as gcache
from paddle_tpu.models import MiniCPMSALAConfig, MiniCPMSALAForCausalLM
from paddle_tpu.nn import LightningAttention, StateCache
from paddle_tpu.nn import linear_attention as la
from paddle_tpu.serving.continuous import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmark", "configs", "minicpm-sala-9b",
                        "reference.py")
    spec = importlib.util.spec_from_file_location("sala_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
CACHE_LEN = 128
SC = dict(kernel_size=4, kernel_stride=2, block_size=8, init_blocks=1,
          window_size=16, topk=6, dense_len=64)
# the reference's configuration keys at a toy size: a window of the
# published order that begins at layer 9 (sparse, two Lightning, sparse),
# 8 heads on 2 K/V heads, 4 Lightning heads, all of 8 channels
CFG = dict(
    hidden_size=32, intermediate_size=64, num_hidden_layers=4,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=8, rope_theta=10000,
    rms_norm_eps=1e-6, scale_emb=12, scale_depth=1.4, dim_model_base=8,
    vocab_size=64, layer_offset=9, sparse_config=SC,
    published=dict(num_hidden_layers=32),
    assumed_sizes=dict(initializer_range=0.2))
_OWN = ("published", "assumed_sizes", "mixer_types")


def _config(cfg=CFG, **kw):
    keys = {k: v for k, v in cfg.items() if k not in _OWN}
    return MiniCPMSALAConfig(**dict(dict(
        keys, mixer_types=tuple(cfg["mixer_types"]),
        published_layers=cfg["published"]["num_hidden_layers"],
        initializer_range=cfg["assumed_sizes"]["initializer_range"]), **kw))


def _model(seed=5, cfg=CFG):
    m = MiniCPMSALAForCausalLM(_config(cfg))
    w = REF.weights(cfg, jax.random.PRNGKey(seed))
    named = dict(m.named_parameters())
    assert set(named) == set(w)
    for name, p in named.items():
        assert tuple(p._array.shape) == tuple(w[name].shape), name
        p._array = w[name].astype(jnp.float32)
    m.eval()
    return m, w


@pytest.fixture(scope="module")
def model():
    return _model()


def _engine(m, **kw):
    kw = dict(dict(slots=2, cache_len=CACHE_LEN,
                   prefill_buckets=(16, 32, 64, 128), temperature=0.0,
                   top_k=0, kv_cache_layout="ring",
                   kv_cache_dtype="float32"), **kw)
    return GenerationEngine(m, **kw)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, 64, size=n)


_WANT = {}


def _want(w, toks):
    """The reference's full forward, one compiled program a length."""
    n = len(toks)
    if n not in _WANT:
        _WANT[n] = jax.jit(lambda w, t: REF.forward(w, t, CFG))
    return np.asarray(_WANT[n](w, jnp.asarray(toks)))


# -- (b) Lightning attention: chunks, steps, padding, positions ---------------

def _mixer(seed=3, layer=11):
    from paddle_tpu.framework.random import seed as set_seed

    set_seed(seed)
    mix = LightningAttention(32, 4, 8, la.lightning_slopes(4, layer, 32),
                             chunk=8, initializer_range=0.2)
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    for key, name in zip(k, ("q_norm", "k_norm", "o_norm")):
        p = getattr(mix, name)
        p._array = 1.0 + 0.2 * jax.random.normal(key, p._array.shape)
    return mix


def _positions(b, t, start=0):
    return jnp.broadcast_to(start + jnp.arange(t, dtype=jnp.int32), (b, t))


def _fresh(rows):
    return StateCache(jnp.zeros((rows, 4, 8, 8), jnp.float32),
                      jnp.zeros((rows,), jnp.int32))


def _reference_mixer(mix, x, layer=11):
    """The benchmark reference's Lightning layer on one sequence, given
    the mixer's own weights."""
    w = {n: p._array.astype(jnp.float32) for n, p in mix.named_parameters()}
    cfg = dict(CFG, layer_offset=layer)
    with jax.default_matmul_precision("highest"):
        return REF._lightning(x, w, REF._widths(CFG), REF._mm(False), 1e-6,
                              REF.slopes(cfg, 0), 10000)


def test_the_slopes_are_lightning_attentions():
    s = la.lightning_slopes(32, 9, 32)
    assert s.shape == (32,) and s.dtype == np.float32
    np.testing.assert_allclose(s[0], 2 ** -0.25 * (1 - 9 / (31 + 1e-5) + 1e-5),
                               rtol=1e-6)
    np.testing.assert_allclose(s[31], 2 ** -8 * (1 - 9 / (31 + 1e-5) + 1e-5),
                               rtol=1e-6)
    np.testing.assert_allclose(la.lightning_slopes(4, 11, 32),
                               REF.slopes(dict(CFG, layer_offset=11), 0))
    # the last published layer's decay is nearly none, not none
    assert 0 < la.lightning_slopes(32, 31, 32)[0] < 1e-4


@pytest.mark.parametrize("t", [16, 8, 37, 5, 1])
def test_chunked_prefill_is_the_recurrence_and_the_reference(t):
    mix = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(t), (2, t, 32))
    pos = _positions(2, t)
    y, cache = mix(x, pos, cache=_fresh(2))
    want = jnp.stack([_reference_mixer(mix, x[i]) for i in range(2)])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    assert float(jnp.abs(want).max()) > 0.1
    # the recurrence itself, token by token, ends in the same state
    state, _ = la.lightning_recurrent(*_streams(mix, x, pos))
    np.testing.assert_allclose(np.asarray(cache.state), np.asarray(state),
                               atol=2e-5)
    assert cache.state.dtype == jnp.float32


def _streams(mix, x, pos):
    """(zero state, q, k, v, g) as the mixer's forward makes them."""
    from paddle_tpu.nn.gqa import apply_rotary, rms_norm

    b, t, _ = x.shape
    q, k, v = (jnp.matmul(x, m._array).reshape(b, t, 4, 8)
               for m in (mix.wq, mix.wk, mix.wv))
    q = apply_rotary(rms_norm(q, mix.q_norm._array, 1e-6), pos, 10000.0)
    k = apply_rotary(rms_norm(k, mix.k_norm._array, 1e-6), pos, 10000.0)
    g = jnp.broadcast_to(-jnp.asarray(mix.slopes), (b, t, 4))
    return jnp.zeros((b, 4, 8, 8)), q, k, v, g


@pytest.mark.parametrize("n,m", [(13, 8), (8, 3), (16, 16), (1, 6)])
def test_prefill_then_steps_is_one_longer_prefill(n, m):
    mix = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(n), (2, n + m, 32))
    want, end = mix(x, _positions(2, n + m), cache=_fresh(2))
    y, cache = mix(x[:, :n], _positions(2, n), cache=_fresh(2))
    out = [y]
    for i in range(n, n + m):
        y, cache = mix(x[:, i:i + 1], _positions(2, 1, i), cache=cache)
        out.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(out, 1)),
                               np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(cache.state), np.asarray(end.state),
                               atol=2e-5)


@pytest.mark.parametrize("real,bucket", [(13, 16), (5, 32), (2, 8), (16, 16)])
def test_right_padding_does_not_advance_the_state(real, bucket):
    mix = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(real), (1, bucket, 32))
    valid = (jnp.arange(bucket) < real)[None]
    y, cache = mix(x, _positions(1, bucket), cache=_fresh(1), valid=valid)
    want, end = mix(x[:, :real], _positions(1, real), cache=_fresh(1))
    np.testing.assert_allclose(np.asarray(y[:, :real]), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(cache.state), np.asarray(end.state),
                               atol=2e-6)


def test_positions_enter_through_the_rotation():
    """The same tokens further along the sequence give other outputs
    (after the first, which sees itself alone: a rotation of q and k
    by one angle leaves q . k as it was)."""
    mix = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 32))
    here, there = mix(x, _positions(1, 12)), mix(x, _positions(1, 12, 7))
    np.testing.assert_allclose(np.asarray(here[:, 0]), np.asarray(there[:, 0]),
                               atol=2e-5)
    # relative positions only: a shift of the whole prompt changes nothing
    np.testing.assert_allclose(np.asarray(here), np.asarray(there), atol=2e-5)
    # but positions that grow twice as fast do
    twice = mix(x, 2 * _positions(1, 12))
    assert float(jnp.abs(twice - here)[:, 1:].max()) > 1e-2


# -- the model ----------------------------------------------------------------

def test_cache_spec_is_one_kind_a_layer_in_mixer_order(model):
    m, _ = model
    kinds = m.cache_spec()
    assert gcache.is_layer_kinds(kinds) and len(kinds) == 4
    assert [type(k).__name__ for k in kinds] == [
        "SparseKVKind", "StateKind", "StateKind", "SparseKVKind"]
    assert kinds[0].heads == 2 and kinds[0].sparse.topk == 6
    assert kinds[1].shapes == ((4, 8, 8),) and kinds[1].dtypes == ("float32",)
    assert not gcache.kinds_continue(kinds)
    # one array and no tail: the state kind hands a StateCache on
    assert isinstance(kinds[1].wrap(kinds[1].arrays(2, 128, "float32"),
                                    jnp.zeros((2,), jnp.int32)), StateCache)
    # the Lightning decays are the published layers' (10 and 11 of 32)
    np.testing.assert_allclose(m.layers[1].mixer.slopes,
                               la.lightning_slopes(4, 10, 32))
    assert m.layers[0].branch == pytest.approx(1.4 / 32 ** 0.5)


def test_a_mixer_list_is_checked_against_the_depth_and_the_names():
    with pytest.raises(InvalidArgumentError, match="num_hidden_layers"):
        MiniCPMSALAForCausalLM(_config(num_hidden_layers=3))
    with pytest.raises(InvalidArgumentError, match="mixer"):
        MiniCPMSALAForCausalLM(_config(
            mixer_types=("minicpm4", "mamba", "minicpm4", "minicpm4")))


@pytest.mark.parametrize("t", [40, 64, 100])
def test_full_forward_matches_the_plain_reference(model, t):
    m, w = model
    toks = _tokens(t, seed=t)
    want = _want(w, toks)
    assert want.std() > 0.2
    got = np.asarray(jax.jit(lambda t: m(t[None])._array[0])(
        jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_a_long_prompts_feed_forward_runs_in_chunks(model, monkeypatch):
    from paddle_tpu.models import minicpm_sala

    m, _ = model
    toks = jnp.asarray(_tokens(32)[None])
    want = np.asarray(jax.jit(lambda t: m(t)._array)(toks))
    monkeypatch.setattr(minicpm_sala, "_FFN_CHUNK", 8)
    got = np.asarray(jax.jit(lambda t: m(t)._array)(toks))
    np.testing.assert_allclose(got, want, atol=2e-5)


def _cached_logits(m, toks, n_prompt, bucket):
    """Logits of positions ``n_prompt-1 ..`` of ``toks`` as the engine
    computes them: one right-padded prefill of the first ``n_prompt``
    into fresh caches (the last real row's logits), then one cached
    decode step a token."""
    kinds = m.cache_spec()

    @jax.jit
    def prefill(padded):
        fresh = gcache.init_kinds_cache(kinds, 1, CACHE_LEN, "float32")
        mask = jnp.where(jnp.arange(bucket) < n_prompt, 0.0,
                         gcache.NEG_INF).astype(jnp.float32)[None, None, None]
        logits, caches = m(
            padded[None], attention_mask=mask,
            position_ids=jnp.arange(bucket, dtype=jnp.int32)[None],
            caches=gcache.kinds_layer_caches(kinds, fresh))
        assert logits._array.shape[1] == 1 and len(caches) == len(kinds)
        return logits._array[0], gcache.unzip_kinds_caches(caches)

    @jax.jit
    def step(tok, kv):
        logits, caches = m(tok[None, None], position_ids=kv[-1][:, None],
                           caches=gcache.kinds_layer_caches(kinds, kv))
        return logits._array[0], \
            gcache.unzip_kinds_caches(caches) + (kv[-1] + 1,)

    padded = np.full(bucket, 2, np.int32)
    padded[:n_prompt] = toks[:n_prompt]
    logits, kept = prefill(jnp.asarray(padded))
    out = [np.asarray(logits)]
    kv = kept + (jnp.asarray([n_prompt], jnp.int32),)
    for i in range(n_prompt, len(toks)):
        logits, kv = step(jnp.asarray(toks[i], jnp.int32), kv)
        out.append(np.asarray(logits))
    return np.concatenate(out)


@pytest.mark.parametrize("n_prompt,bucket,total", [
    (5, 16, 30), (50, 64, 80), (64, 64, 75), (70, 128, 100)])
def test_prefill_then_decode_matches_full_forward_across_dense_len(
        model, n_prompt, bucket, total):
    """A padded prefill into rings, pooled rings and states, then
    one-token steps: under dense_len, across it while decoding, and over
    it from the prompt on. Every logit within 2e-4 of the reference's
    full forward pass (recurrence token by token, selection query by
    query, no cache)."""
    m, w = model
    toks = _tokens(total, seed=n_prompt)
    got = _cached_logits(m, toks, n_prompt, bucket)
    np.testing.assert_allclose(got, _want(w, toks)[n_prompt - 1:], atol=2e-4)


# -- (c) the engine and continuous batching -----------------------------------

def _served_gaps(w, prompt, out):
    """Per served token the reference's largest logit less its logit of
    the served token, teacher-forced on prompt + served."""
    seq = np.asarray(list(prompt) + list(out))
    logits = _want(w, seq)
    own = logits[np.arange(len(seq) - 1), seq[1:]]
    return (logits.max(-1)[:-1] - own)[len(prompt) - 1:]


def test_continuous_batching_serves_the_references_tokens_whatever_the_neighbours(
        model):
    """Six slots, eleven requests through ContinuousBatcher: slots fill
    at once, requests finish at different steps, the queue's rest is
    admitted mid-batch into slots that were used before; prompts of 5 to
    100 tokens, so that slots under dense_len (64) decode beside slots
    over it, and those of 52-60 tokens cross it while they decode. Every
    served token is the reference's argmax at its position to 2e-4 of
    the largest logit: no request saw a neighbour's rings or state, a
    previous tenant's, or a padded position; nothing compiles after
    warm-up."""
    from paddle_tpu import monitor

    m, w = model
    eng = _engine(m, slots=6).warmup()
    assert eng.expected_compiles() == 4 + 1 and eng.extra_compiles() == 0
    assert eng.chunk_len is None       # state layers: prompts go in whole
    lengths = [5, 60, 100, 52, 31, 70, 17, 58, 90, 9, 64]
    budgets = [9, 30, 12, 20, 6, 25, 12, 10, 20, 8, 5]
    prompts = [_tokens(n, seed=n).tolist() for n in lengths]
    mid0 = monitor.counter("serving/gen_midbatch_admissions_total").value
    sched = ContinuousBatcher(eng, queue_capacity=32).start()
    try:
        reqs = [sched.submit(p, max_new_tokens=b, temperature=0.0)
                for p, b in zip(prompts, budgets)]
        outs = [r.wait(timeout=300) for r in reqs]
    finally:
        sched.stop(drain=False)
    assert sched.extra_compiles() == 0
    assert monitor.counter(
        "serving/gen_midbatch_admissions_total").value - mid0 >= 1
    for p, o, b in zip(prompts, outs, budgets):
        stop = o.index(1) + 1 if 1 in o else b     # EOS ends a request
        assert len(o) == stop
        assert _served_gaps(w, p, o).max() <= 2e-4


def test_the_cache_is_accounted_and_donated(model):
    m, _ = model
    eng = _engine(m, slots=3, kv_cache_dtype="bfloat16").warmup()
    rings = 2 * (CACHE_LEN * 2 * 2 * 8 * 2 + (CACHE_LEN // 2) * 2 * 8 * 2)
    state = 2 * 4 * 8 * 8 * 4
    assert eng.slot_nbytes() == rings + state + 4
    assert eng.cache_bytes_by_kind() == (3 * rings, 0, 3 * state, 0)
    assert eng.cache_nbytes() == 3 * (rings + state) + 3 * 4
    assert eng.hbm_required_bytes() == eng.param_nbytes() \
        + 3 * eng.slot_nbytes()
    assert eng.kv_bytes_per_token() == 2 * (2 * 2 * 8 * 2 + 2 * 8 * 2 // 2)
    before = jax.tree_util.tree_leaves(eng._kv)
    assert len(before) == 2 * 3 + 2 + 1
    eng.admit(0, _tokens(70).tolist())
    assert all(a.is_deleted() for a in before)
    before = jax.tree_util.tree_leaves(eng._kv)
    eng.step(np.zeros(3, np.int32), np.zeros(3, np.float32))
    assert all(a.is_deleted() for a in before)
    assert [int(p) for p in eng._kv[-1]] == [71, 1, 1]
    # slot 0 is over dense_len at 72 live rows: 6 blocks of 9, the newest
    # as far as its 8th row, and 35 pooled keys; slots 1 and 2 hold 2 rows
    assert eng.sparse_blocks() == (2 * (6 + 1 + 1), 2 * (9 + 1 + 1), 2)
    assert eng.kv_rows_read() == (2 * (5 * 8 + 8 + 18 + 2 + 2), 0, 0)
    assert eng.kv_rows_fetched() == (2 * 3 * (8 * 8 + 32), 0, 0)


def test_counters_are_sampled_only_while_the_profiler_is_on(model):
    from paddle_tpu import profiler

    m, _ = model
    eng = _engine(m).warmup()
    profiler.reset_profiler()
    eng.reset()
    eng.admit(0, _tokens(13).tolist())
    eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
    assert not profiler.counter_samples()
    profiler.start_profiler(state="CPU")
    try:
        eng.reset()
        eng.admit(1, _tokens(80).tolist())
        for _ in range(2):
            eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
        got = {}
        for ev in profiler.counter_samples():
            got.setdefault(ev["name"], []).append(ev["args"]["value"])
    finally:
        profiler.stop_profiler()
        profiler.reset_profiler()
    # once an iteration, from pos alone
    assert got["sparse::blocks_read"] == [2 * (1 + 6), 2 * (1 + 6)]
    assert got["sparse::blocks_live"] == [2 * (1 + 11), 2 * (1 + 11)]
    assert got["sparse::slots_dense"] == [1, 1]
    assert len(got["generation::kv_rows_read"]) == 2
    assert got["generation::kv_rows_read"][0][0] \
        < got["generation::kv_rows_fetched"][0][0]
    assert got["generation::state_bytes"] == [eng.state_nbytes()] * 2
    assert "moe::expert_load" not in got      # no experts, no statistics


def test_the_scopes_are_in_the_programs(model):
    m, _ = model
    eng = _engine(m)

    def text(call):
        _, fn, make = call
        return fn.lower(*make()).as_text(debug_info=True)

    decode = text(eng._decode_call(np.zeros(2, np.int32),
                                   np.zeros(2, np.float32), 0))
    prefill = text(eng._prefill_call(0, np.zeros(64, np.int32), 50, 0.0, 0))
    for scope in ("sparse_pool", "sparse_select", "sparse_attend"):
        # a prompt's selection and attention lie in its scan's body
        assert "sparse_attn/" + scope in decode and scope in prefill, scope
    assert "sparse_attn/sparse_pool" in prefill
    assert "lightning/lightning_step" in decode
    assert "lightning_scan" not in decode
    assert "lightning/lightning_scan" in prefill
    assert "lightning_step" not in prefill


# -- planted faults -----------------------------------------------------------

def _padding_advances_the_state(monkeypatch, m):
    sound = LightningAttention.forward
    monkeypatch.setattr(
        LightningAttention, "forward",
        lambda self, x, positions, cache=None, valid=None: sound(
            self, x, positions, cache=cache))


def _step_decays_twice(monkeypatch, m):
    sound = la.lightning_step
    monkeypatch.setattr(la, "lightning_step",
                        lambda s, q, k, v, g: sound(s, q, k, v, 2.0 * g))


def _state_rounded_to_bfloat16(monkeypatch, m):
    sound = la.lightning_step

    def rounded(s, q, k, v, g):
        s, o = sound(s, q, k, v, g)
        return s.astype(jnp.bfloat16).astype(jnp.float32), o

    monkeypatch.setattr(la, "lightning_step", rounded)


def _no_rotation(monkeypatch, m):
    from paddle_tpu.nn import gqa

    monkeypatch.setattr(gqa, "apply_rotary", lambda x, p, theta, **kw: x)


def _dense_for_sparse(monkeypatch, m):
    from paddle_tpu.nn import sparse_attention as sa

    monkeypatch.setattr(sa, "select_blocks", lambda q, pooled, t, cfg, s: (
        jnp.arange(pooled.shape[-2] * cfg.stride // cfg.block)
        <= t[..., None] // cfg.block))


@pytest.mark.parametrize("plant,least", [
    (_padding_advances_the_state, 1e-2), (_step_decays_twice, 1e-2),
    (_state_rounded_to_bfloat16, 5e-4), (_no_rotation, 1e-2),
    (_dense_for_sparse, 1e-2)])
def test_a_planted_fault_is_far_outside_the_tolerance(plant, least,
                                                      monkeypatch):
    """Each fault in the program alone moves some logit of the cached
    path by ``least`` or more, over twice the tolerance and up: a prompt of
    70 (over dense_len, padded to its bucket), 30 tokens decoded."""
    m, w = _model()
    plant(monkeypatch, m)
    toks = _tokens(100, seed=1)
    got = _cached_logits(m, toks, 70, 128)
    assert np.abs(got - _want(w, toks)[69:]).max() > least

"""The double-layer latent-attention decoder (models/longcat_flash.py)
and what it forced: a one-plane latent ring as a cache kind, two rings a
layer, an absorbed decode beside an expanded prefill, an interleaved
rotary over a part of the head, zero-compute experts and a selection
bias in RoutedExperts, an expert branch beside the dense path. Tiny
widths with the real ratios, float32, seeded; the plain reference is the
benchmark's (benchmark/configs/longcat-flash-omni/reference.py), which
imports nothing of the program.

Tolerances: program and reference are both float32 here and differ in
the order of their sums only (blocks, chunks, the absorbed products'
association): 2e-4 on logits whose standard deviation is over 0.5, as
the other two kinds models' tests; a wrong mask, position, ring row,
expert weight or topology moves a logit by 1e-2 to 1 (the planted
faults at the end of this file)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.errors import InvalidArgumentError
from paddle_tpu.generation import GenerationEngine
from paddle_tpu.generation import cache as gcache
from paddle_tpu.models import (GPTForCausalLM, LongcatFlashConfig,
                               LongcatFlashForCausalLM, gpt_tiny_config)
from paddle_tpu.nn import LatentCache
from paddle_tpu.nn.gqa import apply_rotary
from paddle_tpu.nn.mla import CachedLatentAttention
from paddle_tpu.parallel.moe import RoutedExperts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmark", "configs", "longcat-flash-omni",
                        "reference.py")
    spec = importlib.util.spec_from_file_location("longcat_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
CACHE_LEN = 32
# the reference's configuration keys at a toy size: two double layers,
# this member holds experts 4..7 of 16 routed (+ 8 zero-compute) and 64
# of 97 vocabulary rows; 6 of 24 router outputs a token
CFG = dict(
    hidden_size=48, ffn_hidden_size=64, expert_ffn_hidden_size=24,
    num_layers=2, num_attention_heads=4, kv_lora_rank=16, q_lora_rank=24,
    qk_rope_head_dim=8, v_head_dim=12, qk_nope_head_dim=12,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, routed_scaling_factor=6.0,
    n_routed_experts=4, experts_held=[4, 4], zero_expert_num=8, moe_topk=6,
    rms_norm_eps=1e-5, rope_theta=1e7, vocab_size=64,
    published=dict(num_layers=28, n_routed_experts=16, vocab_size=97),
    assumed_sizes=dict(initializer_range=0.2))


def _config(cfg=CFG, **kw):
    return LongcatFlashConfig(**dict(dict(
        vocab_size=cfg["published"]["vocab_size"],
        vocab_held=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        ffn_hidden_size=cfg["ffn_hidden_size"],
        expert_ffn_hidden_size=cfg["expert_ffn_hidden_size"],
        num_layers=cfg["num_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=cfg["q_lora_rank"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        zero_expert_num=cfg["zero_expert_num"], moe_topk=cfg["moe_topk"],
        experts_held=tuple(cfg["experts_held"]),
        rope_theta=cfg["rope_theta"]), **kw))


def _model(seed=5, cfg=CFG):
    m = LongcatFlashForCausalLM(_config(cfg))
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
    kw = dict(dict(slots=2, cache_len=CACHE_LEN, prefill_buckets=(8, 16, 32),
                   temperature=0.0, top_k=0, kv_cache_layout="ring",
                   kv_cache_dtype="float32"), **kw)
    return GenerationEngine(m, **kw)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, 64, size=n)


def _want(w, toks, cfg=CFG):
    """The reference's full forward; past CACHE_LEN tokens a layer sees
    what a ring of CACHE_LEN rows keeps."""
    return np.asarray(REF.forward(w, jnp.asarray(toks), cfg,
                                  context=CACHE_LEN))


# -- the cache kind -----------------------------------------------------------

def test_cache_spec_is_two_latent_rings_a_layer(model):
    m, _ = model
    kinds = m.cache_spec()
    assert kinds == [gcache.latent(16, 8)] * 4
    assert gcache.is_layer_kinds(kinds)
    kv = gcache.init_kinds_cache(kinds, 3, CACHE_LEN, "bfloat16")
    assert [[a.shape for a in arrays] for arrays in kv[:-1]] == [
        [(3, CACHE_LEN, 24)]] * 4
    caches = gcache.kinds_layer_caches(kinds, kv)
    assert all(isinstance(c, LatentCache) for c in caches)
    assert gcache.unzip_kinds_caches(caches)[0][0] is kv[0][0]
    assert gcache.kinds_ring_lengths(kinds, CACHE_LEN) == [CACHE_LEN]
    np.testing.assert_array_equal(
        gcache.kinds_decode_mask(kinds, kv[-1], CACHE_LEN),
        gcache.decode_mask(kv[-1], CACHE_LEN))


@pytest.mark.parametrize("dtype,itemsize", [("bfloat16", 2), ("float32", 4)])
def test_the_kinds_bytes_equal_its_arrays(dtype, itemsize):
    """A row is rank + rope wide and has no heads: 576 x 2 B = 1,152 B
    at the published widths in bfloat16."""
    kind = gcache.latent(512, 64)
    row = 576 * itemsize
    assert kind.row_nbytes(dtype) == kind.bytes_per_token(dtype) == row
    assert kind.slot_nbytes(40, dtype) == 40 * row
    arrays = kind.arrays(3, 40, dtype)
    assert gcache.cache_nbytes(arrays) == 3 * kind.slot_nbytes(40, dtype)
    mixed = [kind, gcache.kv(2, 16, window=8), gcache.state([(4,)],
                                                            ["float32"])]
    assert gcache.kinds_bytes_per_token(mixed, dtype) == row
    assert gcache.kinds_slot_nbytes(mixed, 40, dtype) == 40 * row \
        + 8 * 2 * 2 * 16 * itemsize + 16
    assert gcache.kinds_ring_lengths(mixed, 40) == [40, 8]


def test_capacity_accounting_counts_the_latent_rings(model):
    m, _ = model
    eng = _engine(m, slots=3)
    row = 24 * 4
    assert eng.slot_nbytes() == 4 * CACHE_LEN * row + 4
    assert eng.kv_bytes_per_token() == 4 * row
    assert eng.cache_nbytes() == 3 * eng.slot_nbytes()
    assert eng.cache_bytes_by_kind() == (0, 0, 0, 3 * 4 * CACHE_LEN * row)
    assert eng.state_nbytes() == 0
    assert eng.hbm_required_bytes() == eng.param_nbytes() \
        + eng.cache_nbytes()
    assert eng.hbm_required_bytes(slots=5) - eng.hbm_required_bytes() \
        == 2 * eng.slot_nbytes()
    assert eng.suggest_decode_slots(
        eng.param_nbytes() + 7 * eng.slot_nbytes() + 11) == 7


def test_what_a_latent_cache_cannot_use_refuses_by_name(model):
    m, _ = model
    for kw in (dict(kv_cache_layout="paged"), dict(kv_cache_dtype="int8"),
               dict(draft_model=GPTForCausalLM(gpt_tiny_config()))):
        with pytest.raises(InvalidArgumentError, match="LatentKind"):
            _engine(m, **kw)
    eng = _engine(m)
    with pytest.raises(InvalidArgumentError, match="prefill_export"):
        eng.prefill_export([3, 4, 5])
    with pytest.raises(InvalidArgumentError, match="admit_prefilled"):
        eng.admit_prefilled(0, (), 3, 7)
    with pytest.raises(InvalidArgumentError, match="backend kind 'decode'"):
        eng.warmup(kind="decode")


# -- the model against the reference ------------------------------------------

def test_full_forward_matches_the_plain_reference(model):
    m, w = model
    toks = _tokens(29)
    want = np.asarray(REF.forward(w, jnp.asarray(toks), CFG))
    got = np.asarray(m(jnp.asarray(toks[None]))._array[0])
    assert want.std() > 0.5
    np.testing.assert_allclose(got, want, atol=2e-4)


def _cached_logits(m, toks, n_prompt, bucket):
    """Logits of positions ``n_prompt-1 ..`` of ``toks`` as the engine
    computes them: one right-padded prefill of the first ``n_prompt``
    into fresh latent rings (the last real row's logits; the expanded
    path), then one cached decode step a token (the absorbed path).
    Both are compiled here, as the engine compiles them (the model's
    weights are constants of the two programs)."""
    kinds = m.cache_spec()

    @jax.jit
    def prefill(padded):
        fresh = gcache.init_kinds_cache(kinds, 1, CACHE_LEN, "float32")
        mask = jnp.where(jnp.arange(bucket) < n_prompt, 0.0,
                         gcache.NEG_INF).astype(jnp.float32)[None, None, None]
        logits, caches = m(padded[None],
                           position_ids=jnp.arange(bucket)[None],
                           attention_mask=mask,
                           caches=gcache.kinds_layer_caches(kinds, fresh))
        assert logits._array.shape[1] == 1 and len(caches) == len(kinds)
        return logits._array[0], gcache.unzip_kinds_caches(caches)

    @jax.jit
    def step(tok, kv):
        mask = gcache.kinds_decode_mask(kinds, kv[-1], CACHE_LEN)
        logits, caches = m(tok[None, None], position_ids=kv[-1][:, None],
                           attention_mask=mask,
                           caches=gcache.kinds_layer_caches(kinds, kv))
        return logits._array[0], \
            gcache.unzip_kinds_caches(caches) + (kv[-1] + 1,)

    padded = np.full(bucket, 2, np.int32)
    padded[:n_prompt] = toks[:n_prompt]
    logits, rings = prefill(jnp.asarray(padded))
    out = [np.asarray(logits)]
    kv = rings + (jnp.asarray([n_prompt], jnp.int32),)
    for i in range(n_prompt, len(toks)):
        logits, kv = step(jnp.asarray(toks[i], jnp.int32), kv)
        out.append(np.asarray(logits))
    return np.concatenate(out)


@pytest.mark.parametrize("n_prompt,bucket", [(5, 8), (8, 8), (13, 16),
                                             (27, 32)])
def test_prefill_then_decode_matches_full_forward_through_the_wrap(
        model, n_prompt, bucket):
    """An expanded prefill into the latent rings, then absorbed decode
    steps to 70 tokens: the rings (32 rows) wrap once and are
    overwritten once more. Every logit within 2e-4 of the reference's
    full forward pass (expanded attention, no cache): the row `[c ;
    rotated k_rot]` was rotated by its absolute position when written,
    the absorbed products give the expanded ones' numbers, and padding
    is never seen."""
    m, w = model
    toks = _tokens(70, seed=n_prompt)
    got = _cached_logits(m, toks, n_prompt, bucket)
    np.testing.assert_allclose(got, _want(w, toks)[n_prompt - 1:], atol=2e-4)


def test_engine_serves_the_references_own_tokens_two_slots_at_once(model):
    """Through GenerationEngine (admit + step): three prompts over two
    slots, so two are in one batch at different positions and a slot
    turns over; every served token is the reference's argmax at its
    position to 2e-4 of its largest logit, past the rings' wrap; warm-up
    is the ladder + 1 programs and nothing compiles after it."""
    m, w = model
    eng = _engine(m)
    eng.warmup()
    assert eng.extra_compiles() == 0
    prompts = [_tokens(n, seed=n).tolist() for n in (5, 13, 20)]
    outs = eng.generate(prompts, max_new_tokens=30, stop_at_eos=False)
    assert eng.extra_compiles() == 0
    for p, o in zip(prompts, outs):
        seq = np.asarray(p + o)
        logits = _want(w, seq)
        own = logits[np.arange(len(seq) - 1), seq[1:]]
        gap = (logits.max(-1)[:-1] - own)[len(p) - 1:]
        assert gap.max() <= 2e-4


def test_the_latent_rings_are_one_donated_pytree(model):
    m, _ = model
    eng = _engine(m)
    eng.warmup()
    before = jax.tree_util.tree_leaves(eng._kv)
    assert len(before) == 4 + 1
    eng.admit(0, _tokens(13).tolist())
    assert all(a.is_deleted() for a in before)
    before = jax.tree_util.tree_leaves(eng._kv)
    eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
    assert all(a.is_deleted() for a in before)
    assert [int(p) for p in eng._kv[-1]] == [14, 1]
    assert eng._pos_host.tolist() == [14, 1]
    assert eng.kv_rows_read() == (0, 0, 4 * (15 + 2))


def test_counters_are_sampled_only_while_the_profiler_is_on(model):
    from paddle_tpu import profiler

    m, _ = model
    eng = _engine(m)
    eng.warmup()
    profiler.reset_profiler()
    eng.reset()
    eng.admit(0, _tokens(13).tolist())
    eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
    assert not profiler.counter_samples()
    profiler.start_profiler(state="CPU")
    try:
        eng.reset()
        eng.admit(1, _tokens(20).tolist())
        eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
        got = {}
        for ev in profiler.counter_samples():
            got.setdefault(ev["name"], []).append(ev["args"]["value"])
    finally:
        profiler.stop_profiler()
        profiler.reset_profiler()
    assert got["generation::cache_bytes"] == [
        list(eng.cache_bytes_by_kind())]
    assert got["generation::kv_rows_read"] == [[0, 0, 4 * (1 + 21)]]
    zero, pairs = got["moe::zero_pairs"][0], got["moe::pairs_here"][0]
    assert len(zero) == len(pairs) == 2       # one value an expert layer
    # two tokens, 6 choices each: the pairs are zero experts', this
    # chip's, or another chip's
    assert all(0 <= z <= 12 and 0 <= p <= 12 - z
               for z, p in zip(zero, pairs))
    assert len(got["moe::expert_load"]) == 2  # the prompt's and the step's


# -- the decode kernel in the model's absorbed step ---------------------------

def _with_the_kernel(monkeypatch, block=8):
    """The absorbed step as a TPU traces it: the Mosaic kernel
    (interpret mode here), ``block`` keys a block so that the toy ring
    of 32 rows is four blocks."""
    from paddle_tpu.nn import mla

    monkeypatch.setattr(mla, "decode_key_block", lambda shape, dtype: block)


def _greedy(eng, prompts, steps, watch=None):
    """Admit ``prompts`` into slots 0.. and take ``steps`` greedy decode
    steps; ``watch(eng)`` is called before each."""
    toks = np.asarray([eng.admit(i, p) for i, p in enumerate(prompts)],
                      np.int32)
    out = [toks]
    for _ in range(steps):
        if watch is not None:
            watch(eng)
        toks = eng.step(toks, np.zeros(len(toks), np.float32))
        out.append(toks)
    return np.stack(out)


def test_greedy_tokens_are_the_same_with_the_kernel_and_without(
        model, monkeypatch):
    """A prompt a slot (5 and 13 tokens) and 2 x ring decode steps: the
    rings fill, wrap and are overwritten once more; the tokens with the
    kernel forced are XLA's path's, and every attention of the decode
    program was traced the way the engine says."""
    from paddle_tpu import profiler

    m, _ = model
    prompts = [_tokens(5, seed=1).tolist(), _tokens(13, seed=2).tolist()]
    layers = len(m.layers)

    def traced():
        before = profiler.counters()
        eng = _engine(m)
        out = _greedy(eng, prompts, 2 * CACHE_LEN)
        after = profiler.counters()
        return out, [after.get(k, 0) - before.get(k, 0) for k in
                     ("mla::absorbed_kernel", "mla::absorbed_xla")]

    want, counts = traced()
    assert counts == [0, 2 * layers]
    _with_the_kernel(monkeypatch)
    got, counts = traced()
    assert counts == [2 * layers, 0]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kernel", [False, True])
def test_rows_fetched_are_counted_beside_the_rows_read(
        model, monkeypatch, kernel):
    """`generation::kv_rows_fetched` at every step of a run through the
    wrap: never under `kv_rows_read`; the whole rings on XLA's path
    (slots x ring x attentions), the live rows rounded up to blocks of 8
    with the kernel, so under a block a slot and attention over."""
    from paddle_tpu import profiler

    m, _ = model
    if kernel:
        _with_the_kernel(monkeypatch)
    eng = _engine(m)
    attentions, slots = 2 * len(m.layers), 2
    seen = []
    _greedy(eng, [_tokens(5, seed=1).tolist(), _tokens(13, seed=2).tolist()],
            CACHE_LEN + 4,
            watch=lambda e: seen.append((e.kv_rows_read(),
                                         e.kv_rows_fetched())))
    for read, fetched in seen:
        assert fetched[:2] == read[:2] == (0, 0)
        assert read[2] <= fetched[2] <= slots * CACHE_LEN * attentions
        if kernel:
            assert fetched[2] % (8 * attentions) == 0
            assert fetched[2] - read[2] < 8 * slots * attentions
        else:
            assert fetched[2] == slots * CACHE_LEN * attentions
    assert seen[0][0][2] == attentions * (6 + 14)
    if kernel:
        assert seen[0][1][2] == attentions * (8 + 16)
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    try:
        want = eng.kv_rows_read(), eng.kv_rows_fetched()
        eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
        got = {ev["name"]: ev["args"]["value"]
               for ev in profiler.counter_samples()}
    finally:
        profiler.stop_profiler()
        profiler.reset_profiler()
    assert got["generation::kv_rows_read"] == list(want[0])
    assert got["generation::kv_rows_fetched"] == list(want[1])


# -- the attention layer -------------------------------------------------------

def _attention(**kw):
    return CachedLatentAttention(**dict(dict(
        hidden_size=32, num_heads=4, q_rank=16, kv_rank=12, nope_dim=8,
        rope_dim=4, v_dim=6, rope_theta=1e4, scale_q=True, scale_kv=True,
        prefill_block=4, initializer_range=0.3), **kw))


def test_absorbed_step_equals_the_expanded_one_on_the_same_cache():
    """One layer alone: at each of 22 positions (a ring of 12 rows wraps)
    the absorbed step over the ring is the expanded causal attention's
    row at that position, recomputed from the same latent rows: the
    same weights, `W_kvb` folded into the query and the output."""
    m = _attention()
    t, ring = 22, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (2, t, 32))
    pos = jnp.broadcast_to(jnp.arange(t)[None], (2, t))
    q_nope, q_rot, row = m._query_and_row(x, pos)
    kind = gcache.latent(12, 4)
    cache = kind.wrap(kind.arrays(2, ring, "float32"),
                      jnp.zeros((2,), jnp.int32))
    for i in range(t):
        # the expanded path over what the ring holds at step i: the last
        # min(i + 1, ring) rows, the query last
        lo = max(i + 1 - ring, 0)
        want = m.expanded(q_nope[:, lo:i + 1], q_rot[:, lo:i + 1],
                          row[:, lo:i + 1], None)[:, -1]
        p = jnp.full((2,), i, jnp.int32)
        y, cache = m(x[:, i:i + 1], cache=LatentCache(cache.c, p),
                     mask=gcache.decode_mask(p, ring), positions=p[:, None])
        np.testing.assert_allclose(
            y[:, 0], jnp.matmul(want, m.wo._array), atol=2e-5)
    # the ring holds the last 12 rows, each where its position looks
    np.testing.assert_allclose(
        cache.c[:, jnp.arange(t - ring, t) % ring], row[:, t - ring:],
        atol=1e-6)


def test_key_chunks_and_blocks_share_the_grouped_query_code():
    """The decode step with its keys 8 at a time, and the prefill in
    blocks of 4 queries with keys 8 at a time, are the one-piece
    softmax (nn/gqa.py's functions, which CachedGQAttention runs too)."""
    m = _attention()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 21, 32))
    mask = jnp.where(jnp.arange(21)[None] < jnp.asarray([21, 13])[:, None],
                     0.0, gcache.NEG_INF)[:, None, None, :]
    whole = m(x, mask=mask)
    m.key_chunk = 8
    np.testing.assert_allclose(m(x, mask=mask)[0], whole[0], atol=2e-6)
    np.testing.assert_allclose(m(x, mask=mask)[1, :13], whole[1, :13],
                               atol=2e-6)
    ring = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 16))
    pos = jnp.asarray([30, 11], jnp.int32)
    step = [m(x[:, :1], cache=LatentCache(ring, pos), positions=pos[:, None],
              mask=gcache.decode_mask(pos, 24))[0]
            for m.key_chunk in (None, 8)]
    np.testing.assert_allclose(step[1], step[0], atol=2e-6)


def test_interleaved_rotary_pairs_neighbours_and_keeps_the_rest():
    """Channel 2i pairs with 2i+1 at angle p * theta^(-2i/D); position 0
    is the identity; a rotation keeps each pair's norm and the dot
    product of two rotated vectors depends on their distance only."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 5, 3, 8))
    pos = jnp.asarray([[0, 1, 2, 7, 100]])
    y = apply_rotary(x, pos, 1e4, interleaved=True)
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)
    ang = 7 * 1e4 ** (-jnp.arange(0, 8, 2) / 8)
    np.testing.assert_allclose(
        y[0, 3, 1, 0::2], x[0, 3, 1, 0::2] * jnp.cos(ang)
        - x[0, 3, 1, 1::2] * jnp.sin(ang), atol=1e-5)
    np.testing.assert_allclose(
        y[0, 3, 1, 1::2], x[0, 3, 1, 1::2] * jnp.cos(ang)
        + x[0, 3, 1, 0::2] * jnp.sin(ang), atol=1e-5)
    a = jnp.broadcast_to(x[:, :1], x.shape)
    d3 = (apply_rotary(a, pos + 3, 1e4, True)
          * apply_rotary(a, pos, 1e4, True)).sum(-1)
    np.testing.assert_allclose(d3, jnp.broadcast_to(d3[:, :1], d3.shape),
                               rtol=1e-4, atol=1e-4)
    # the half-split form is another pairing of the same head
    assert float(jnp.abs(y - apply_rotary(x, pos, 1e4)).max()) > 0.1


# -- the expert branch ----------------------------------------------------------

def _experts(held, **kw):
    m = RoutedExperts(16, 8, 16, 6, held=held, score="softmax",
                      norm_topk_prob=False, routed_scaling_factor=6.0,
                      zero_experts=8, selection_bias=True,
                      initializer_range=0.5, **kw)
    return m


def _moe_cfg(held):
    return dict(experts_held=list(held), moe_topk=6,
                routed_scaling_factor=6.0)


def _moe_weights(m, first=0, count=16):
    w = {k: getattr(m, k)._array for k in (
        "router", "select_bias", "w_gate", "w_up", "w_down")}
    return dict(w, **{k: w[k][first:first + count]
                      for k in ("w_gate", "w_up", "w_down")})


def test_the_shares_add_up_to_the_uncut_layer():
    """16 routed + 8 zero experts in 4 shares of 4: the four partial
    results, the zero-expert term counted once, are the uncut
    reference's layer; each share's own result is the reference's share
    (held experts' terms + the zero term)."""
    whole = _experts((0, 16))
    x = jax.random.normal(jax.random.PRNGKey(7), (37, 16))
    n = dict(held=16, routed=16, zero=8)
    mm = REF._mm(False)
    want = REF.moe(x, _moe_weights(whole), n, _moe_cfg((0, 16)), mm)
    np.testing.assert_allclose(whole(x), want, rtol=1e-5, atol=1e-5)
    idx, w = whole.route(x)
    zero = jnp.where(idx >= 16, w, 0.0).sum(-1, keepdims=True) * x
    assert float(jnp.abs(zero).max()) > 0.1    # the term is not nothing
    parts = []
    for first in (0, 4, 8, 12):
        share = _experts((first, 4))
        for k in ("router", "select_bias"):
            getattr(share, k)._array = getattr(whole, k)._array
        for k in ("w_gate", "w_up", "w_down"):
            getattr(share, k)._array = getattr(whole, k)._array[
                first:first + 4]
        parts.append(share(x))
        np.testing.assert_allclose(parts[-1], REF.moe(
            x, _moe_weights(whole, first, 4), dict(n, held=4),
            _moe_cfg((first, 4)), mm), rtol=1e-5, atol=1e-5)
        assert int(share.last_zero) == int((idx >= 16).sum())
    np.testing.assert_allclose(sum(parts) - 3 * zero, want, rtol=1e-5,
                               atol=2e-5)
    np.testing.assert_allclose(sum(parts) - 4 * zero, REF.moe(
        x, _moe_weights(whole), n, _moe_cfg((0, 16)), mm, zero_term=False),
        rtol=1e-5, atol=2e-5)


def test_zero_experts_take_no_row_of_the_grouped_products():
    """The pairs of zero experts sort with those of other chips'
    experts: `last_load` counts the held experts' pairs alone, and a
    router that sends everything to zero experts returns the token
    times the sum of its weights."""
    m = _experts((0, 16))
    x = jax.random.normal(jax.random.PRNGKey(8), (9, 16))
    idx, w = m.route(x)
    m(x)
    assert int(m.last_load.sum()) == int((idx < 16).sum())
    assert int(m.last_zero) == int((idx >= 16).sum()) > 0
    m(x, valid=jnp.arange(9) < 4)
    assert int(m.last_zero) == int((idx[:4] >= 16).sum())
    m.select_bias._array = jnp.where(jnp.arange(24) >= 16, 10.0, 0.0)
    idx, w = m.route(x)
    assert bool((idx >= 16).all())
    np.testing.assert_allclose(m(x), w.sum(-1, keepdims=True) * x,
                               rtol=1e-5, atol=1e-6)
    assert int(m.last_load.sum()) == 0


def test_selection_bias_moves_the_choice_and_not_the_weight():
    m = _experts((0, 16))
    x = jax.random.normal(jax.random.PRNGKey(9), (11, 16))
    idx0, w0 = m.route(x)
    scores = jax.nn.softmax(x @ m.router._array, axis=-1)
    np.testing.assert_allclose(
        w0, 6.0 * jnp.take_along_axis(scores, idx0, -1), rtol=1e-5)
    # the output fewest tokens chose is chosen by all once the bias
    # lifts it
    loser = int(jnp.argmin(jnp.asarray([(idx0 == e).sum()
                                        for e in range(24)])))
    m.select_bias._array = jnp.zeros(24).at[loser].set(5.0)
    idx1, w1 = m.route(x)
    assert bool((idx1 == loser).any(-1).all())
    np.testing.assert_allclose(
        w1, 6.0 * jnp.take_along_axis(scores, idx1, -1), rtol=1e-5)
    # the reference chooses and weighs the same way
    n = dict(held=16, routed=16, zero=8)
    np.testing.assert_allclose(m(x), REF.moe(
        x, _moe_weights(m), n, _moe_cfg((0, 16)), REF._mm(False)),
        rtol=1e-5, atol=1e-5)
    # without the options the layer is what it was: no leaf, no term
    plain = RoutedExperts(16, 8, 16, 6)
    assert plain.select_bias is None and plain.zero_experts == 0
    assert "select_bias" not in dict(plain.named_parameters())


def test_a_long_prompts_expert_branch_runs_in_chunks(model, monkeypatch):
    """Past `_MOE_CHUNK` tokens the branch runs chunk by chunk and its
    counts are the chunks' sums: the same logits and the same
    statistics as in one piece."""
    from paddle_tpu.models import longcat_flash

    m, _ = model
    toks = jnp.asarray(_tokens(32, seed=3)[None])
    mask = jnp.where(jnp.arange(32) < 27, 0.0, gcache.NEG_INF)[
        None, None, None].astype(jnp.float32)
    want = np.asarray(m(toks, attention_mask=mask)._array)
    stats = jax.device_get(m.routing_stats())
    monkeypatch.setattr(longcat_flash, "_MOE_CHUNK", 8)
    got = np.asarray(m(toks, attention_mask=mask)._array)
    np.testing.assert_allclose(got[0, :27], want[0, :27], atol=1e-5)
    for key, value in jax.device_get(m.routing_stats()).items():
        np.testing.assert_array_equal(value, stats[key])
    assert int(stats["zero_pairs"].sum()) > 0


# -- the layer's topology ---------------------------------------------------------

def test_the_double_layer_is_the_six_lines(model):
    """One layer of the model against the six lines written out here
    with the layer's own parts: the expert branch reads the first half's
    normalised state and is added at the end."""
    m, _ = model
    layer = m.layers[0]
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 9, 48))
    from paddle_tpu.nn.gqa import rms_norm

    def norm(y, name):
        return rms_norm(y, getattr(layer, name)._array, 1e-5)

    a = x + layer.attn[0](norm(x, "input_norm_0"))
    u = norm(a, "post_norm_0")
    s = layer.moe(u)
    b = a + layer.mlp[0](u)
    c = b + layer.attn[1](norm(b, "input_norm_1"))
    want = c + layer.mlp[1](norm(c, "post_norm_1")) + s
    np.testing.assert_allclose(layer(x), want, atol=1e-5)
    assert float(jnp.abs(s).max()) > 1e-2


def _served_gap(m, w):
    toks = _tokens(40, seed=21)
    got = _cached_logits(m, toks, 13, 16)
    return float(np.abs(got - _want(w, toks)[12:]).max())


def _branch_reads_the_wrong_state(monkeypatch, m):
    real = RoutedExperts.in_chunks
    monkeypatch.setattr(
        RoutedExperts, "in_chunks",
        lambda self, u, *a: real(self, u * 1.05, *a))


def _row_rotated_by_the_wrong_position(monkeypatch, m):
    from paddle_tpu.nn import mla

    real = mla.apply_rotary
    monkeypatch.setattr(mla, "apply_rotary", lambda x, p, **kw: real(
        x, p + (x.shape[1] == 1 and x.ndim == 3), **kw))


def _zero_term_left_out(monkeypatch, m):
    for layer in m.layers:   # no chosen output counts as a zero expert
        monkeypatch.setattr(layer.moe, "num_experts", 10 ** 6)


@pytest.mark.parametrize("plant", [_branch_reads_the_wrong_state,
                                   _row_rotated_by_the_wrong_position,
                                   _zero_term_left_out])
def test_a_planted_fault_is_far_outside_the_tolerance(plant, monkeypatch):
    """What 2e-4 is set against: a branch that reads a state 5 % off, a
    decode step's ring row rotated one position late, and the zero
    experts' term left out each move a logit by 100 x the tolerance or
    more."""
    m, w = _model()
    assert _served_gap(m, w) <= 2e-4
    plant(monkeypatch, m)
    assert _served_gap(m, w) > 2e-2

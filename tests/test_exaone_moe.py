"""The window/full attention decoder (models/exaone_moe.py) and what it
forced: K/V rings of the layer's own length in one cache, rotary
positions on a ring, a banded prefill, a dense leading layer, the
prediction module. Tiny widths with the real ratios, float32, seeded;
the plain reference is the benchmark's
(benchmark/configs/k-exaone-236b/reference.py), which imports nothing of
the program."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.errors import InvalidArgumentError
from paddle_tpu.generation import GenerationEngine
from paddle_tpu.generation import cache as gcache
from paddle_tpu.models import (ExaoneMoEConfig, ExaoneMoEForCausalLM,
                               GPTForCausalLM, gpt_tiny_config)
from paddle_tpu.nn.gqa import CachedGQAttention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmark", "configs", "k-exaone-236b",
                        "reference.py")
    spec = importlib.util.spec_from_file_location("exaone_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
WINDOW, CACHE_LEN = 8, 32
# the reference's configuration keys at a toy size: layers 0-4 of the
# pattern (0 sliding + dense, 1-2 sliding, 3 full, 4 sliding), this
# member holds experts 4..7 of 16 and 64 of 97 vocabulary rows
CFG = dict(
    hidden_size=64, num_hidden_layers=5, num_attention_heads=4, head_dim=16,
    num_key_value_heads=2, vocab_size=64,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"],
    sliding_window=WINDOW, rope_parameters=dict(rope_theta=1e6),
    first_k_dense_replace=1, intermediate_size=96, moe_intermediate_size=32,
    num_experts=4, experts_held=[4, 4], num_experts_per_tok=2,
    routed_scaling_factor=2.5, rms_norm_eps=1e-5,
    num_nextn_predict_layers=0,
    published=dict(num_experts=16, vocab_size=97),
    assumed_sizes=dict(shared_expert_width=32, initializer_range=0.2))


def _model(seed=5, cfg=CFG):
    m = ExaoneMoEForCausalLM(ExaoneMoEConfig(
        vocab_size=97, vocab_held=64, hidden_size=64,
        num_hidden_layers=cfg["num_hidden_layers"], num_attention_heads=4,
        num_key_value_heads=2, head_dim=16,
        layer_types=tuple(cfg["layer_types"]), sliding_window=WINDOW,
        rope_theta=1e6, first_k_dense_replace=1, intermediate_size=96,
        moe_intermediate_size=32, num_experts=16, num_experts_per_tok=2,
        experts_held=tuple(cfg["experts_held"]), routed_scaling_factor=2.5,
        num_nextn_predict_layers=cfg["num_nextn_predict_layers"]))
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
    """The reference's full forward; past CACHE_LEN tokens a full layer
    sees what a ring of CACHE_LEN rows keeps."""
    return np.asarray(REF.forward(w, jnp.asarray(toks), cfg,
                                  context=CACHE_LEN))


def test_cache_spec_keeps_a_window_of_rows_in_sliding_layers(model):
    m, _ = model
    kinds = m.cache_spec()
    assert [k.window for k in kinds] == [8, 8, 8, None, 8]
    kv = gcache.init_kinds_cache(kinds, 3, CACHE_LEN, "float32")
    assert [a[0].shape for a in kv[:-1]] == [
        (3, 2, 8, 16)] * 3 + [(3, 2, CACHE_LEN, 16), (3, 2, 8, 16)]
    assert gcache.kinds_ring_lengths(kinds, CACHE_LEN) == [CACHE_LEN, 8]
    masks = gcache.kinds_decode_mask(kinds, kv[-1], CACHE_LEN)
    assert {n: a.shape for n, a in masks.items()} == {
        CACHE_LEN: (3, 1, 1, CACHE_LEN), 8: (3, 1, 1, 8)}
    # rings all as long as the store: one mask, as a model whose layers
    # are alike takes it
    alike = [gcache.kv(2, 16), gcache.state([(4,)], ["float32"])]
    one = gcache.kinds_decode_mask(alike, kv[-1], CACHE_LEN)
    np.testing.assert_array_equal(
        one, gcache.decode_mask(kv[-1], CACHE_LEN))


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
    into fresh caches of every kind (the last real row's logits), then
    one cached decode step a token."""
    kinds = m.cache_spec()
    fresh = gcache.init_kinds_cache(kinds, 1, CACHE_LEN, "float32")
    padded = np.full(bucket, 2, np.int64)
    padded[:n_prompt] = toks[:n_prompt]
    mask = jnp.where(jnp.arange(bucket) < n_prompt, 0.0,
                     gcache.NEG_INF).astype(jnp.float32)[None, None, None]
    logits, caches = m(jnp.asarray(padded[None]),
                       position_ids=jnp.arange(bucket)[None],
                       attention_mask=mask,
                       caches=gcache.kinds_layer_caches(kinds, fresh))
    assert logits._array.shape[1] == 1
    out = [np.asarray(logits._array[0])]
    kv = gcache.unzip_kinds_caches(caches) + (
        jnp.asarray([n_prompt], jnp.int32),)
    for i in range(n_prompt, len(toks)):
        mask = gcache.kinds_decode_mask(kinds, kv[-1], CACHE_LEN)
        logits, caches = m(jnp.asarray(toks[i:i + 1][None]),
                           position_ids=kv[-1][:, None], attention_mask=mask,
                           caches=gcache.kinds_layer_caches(kinds, kv))
        out.append(np.asarray(logits._array[0]))
        kv = gcache.unzip_kinds_caches(caches) + (kv[-1] + 1,)
    return np.concatenate(out)


@pytest.mark.parametrize("n_prompt,bucket", [(5, 8), (8, 8), (8, 16),
                                             (13, 16), (27, 32)])
def test_prefill_then_decode_matches_full_forward_across_the_wraps(
        model, n_prompt, bucket):
    """A prompt shorter than the window, equal to it (in a bucket as
    long and in a longer one), and longer, then decode to 70 tokens: the
    window rings (8 rows) wrap eight times and the full ring (32 rows)
    once. Every logit within 2e-4 of the reference's full forward pass:
    a ring row read after the wrap was rotated by its absolute position
    when it was written, the prefill left the last min(length, 8) rows
    where `position mod 8` looks for them, and padding is never seen."""
    m, w = model
    toks = _tokens(70, seed=n_prompt)
    got = _cached_logits(m, toks, n_prompt, bucket)
    np.testing.assert_allclose(got, _want(w, toks)[n_prompt - 1:], atol=2e-4)


def test_engine_serves_the_references_own_tokens_two_slots_at_once(model):
    """Through GenerationEngine (admit + step): three prompts of
    different lengths over two slots, so two are in one batch at
    different positions and a slot turns over; every served token is the
    reference's argmax at its position, to 2e-4 of its largest logit,
    past the window's wraps and the full ring's."""
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


def _attention(window, rope=True):
    m = CachedGQAttention(32, 4, 2, 8, qk_norm=True,
                          rope_theta=1e4 if rope else None, window=window,
                          prefill_block=4, initializer_range=0.3)
    return m


@pytest.mark.parametrize("distance,seen", [(7, True), (8, False)])
def test_the_band_is_exact(distance, seen):
    """Window 8: a change to the key at distance 7 from the last query
    changes its output, at distance 8 it changes nothing; by blocks of 4
    queries the band crosses three blocks."""
    m = _attention(8)
    t = 20
    x = jax.random.normal(jax.random.PRNGKey(0), (1, t, 32))
    pos = jnp.arange(t)[None]
    y0 = m(x, positions=pos)
    x1 = x.at[0, t - 1 - distance].add(1.0)
    y1 = m(x1, positions=pos)
    moved = float(jnp.abs(y1[0, -1] - y0[0, -1]).max())
    assert (moved > 1e-3) if seen else (moved == 0.0)


def test_key_chunks_give_the_whole_rows_softmax():
    """A full layer's prefill with its keys 16 at a time (running maxima
    and sums joined) is the one-piece softmax, right-padding and all."""
    m = _attention(None, rope=False)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 50, 32))
    mask = jnp.where(jnp.arange(50)[None] < jnp.asarray([50, 37])[:, None],
                     0.0, gcache.NEG_INF)[:, None, None, :]
    whole = m(x, mask=mask)
    m.key_chunk = 16
    np.testing.assert_allclose(m(x, mask=mask)[0], whole[0], atol=2e-6)
    np.testing.assert_allclose(m(x, mask=mask)[1, :37], whole[1, :37],
                               atol=2e-6)


def test_rotary_on_the_ring_is_the_uncached_forward():
    """One rotary window layer alone: prefill 11 of 16 into a ring of 8
    rows, then 30 decode steps (the ring wraps four times); each step's
    output is the uncached banded forward's at that position."""
    m = _attention(8)
    t, n = 41, 11
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, 32))
    want = m(x, positions=jnp.arange(t)[None])
    kind = gcache.kv(2, 8, window=8)
    padded = jnp.concatenate([x[:, :n], jnp.zeros((1, 5, 32))], 1)
    mask = jnp.where(jnp.arange(16) < n, 0.0, gcache.NEG_INF)[
        None, None, None].astype(jnp.float32)
    cache = kind.wrap(kind.arrays(1, CACHE_LEN, "float32"),
                      jnp.zeros((1,), jnp.int32))
    y, cache = m(padded, cache=cache, mask=mask,
                 positions=jnp.arange(16)[None])
    np.testing.assert_allclose(y[:, :n], want[:, :n], atol=1e-5)
    pos = jnp.asarray([n], jnp.int32)
    for i in range(n, t):
        cache = kind.wrap(tuple(cache)[:2], pos)
        y, cache = m(x[:, i:i + 1], cache=cache,
                     mask=gcache.decode_mask(pos, 8), positions=pos[:, None])
        np.testing.assert_allclose(y[0, 0], want[0, i], atol=1e-5)
        pos = pos + 1


def test_the_prediction_module_matches_the_reference():
    cfg = dict(CFG, num_nextn_predict_layers=1)
    m, w = _model(seed=9, cfg=cfg)
    assert "mtp_block.moe.w_gate" in dict(m.named_parameters())
    toks = _tokens(23, seed=4)
    want = np.asarray(REF.predict_ahead(w, jnp.asarray(toks), cfg))
    got = np.asarray(m.predict_ahead(jnp.asarray(toks[None]))._array[0])
    assert got.shape == (22, 64) and want.std() > 0.5
    np.testing.assert_allclose(got, want, atol=2e-4)
    # the served model is the one without it: the same main stack
    np.testing.assert_allclose(
        np.asarray(m(jnp.asarray(toks[None]))._array[0]),
        np.asarray(REF.forward(w, jnp.asarray(toks), cfg)), atol=2e-4)


def test_what_a_kinds_cache_cannot_use_refuses_by_name(model):
    m, _ = model
    for kw in (dict(kv_cache_layout="paged"), dict(kv_cache_dtype="int8"),
               dict(draft_model=GPTForCausalLM(gpt_tiny_config()))):
        with pytest.raises(InvalidArgumentError, match="KVKind"):
            _engine(m, **kw)
    eng = _engine(m)
    with pytest.raises(InvalidArgumentError, match="prefill_export"):
        eng.prefill_export([3, 4, 5])
    with pytest.raises(InvalidArgumentError, match="admit_prefilled"):
        eng.admit_prefilled(0, (), 3, 7)
    with pytest.raises(InvalidArgumentError, match="admit_prefilled_pages"):
        eng.admit_prefilled_pages(0, [], 3, 7)
    with pytest.raises(InvalidArgumentError, match="backend kind 'prefill'"):
        eng.warmup(kind="prefill")


def test_capacity_accounting_sums_each_ring_at_its_own_length(model):
    """One full layer (2 heads x 16, 32 rows, float32) and four window
    layers (8 rows): the plan equals the arrays byte for byte, a window
    layer costs the same at any cache_len and adds nothing a token, and
    suggest_decode_slots divides by that slot."""
    m, _ = model
    eng = _engine(m, slots=3)
    row = 2 * 2 * 16 * 4
    full_slot, window_slot = CACHE_LEN * row, 4 * WINDOW * row
    assert eng.slot_nbytes() == full_slot + window_slot + 4
    assert eng.kv_bytes_per_token() == row
    assert eng.cache_nbytes() == 3 * eng.slot_nbytes()
    assert eng.cache_bytes_by_kind() == (3 * full_slot, 3 * window_slot, 0,
                                         0)
    assert eng.hbm_required_bytes() == eng.param_nbytes() \
        + eng.cache_nbytes()
    assert eng.hbm_required_bytes(slots=5) - eng.hbm_required_bytes() \
        == 2 * eng.slot_nbytes()
    budget = eng.param_nbytes() + 7 * eng.slot_nbytes() + 11
    assert eng.suggest_decode_slots(budget) == 7
    longer = _engine(m, slots=3, cache_len=64, prefill_buckets=(8,))
    assert longer.slot_nbytes() - eng.slot_nbytes() == full_slot
    assert longer.cache_bytes_by_kind()[1] == 3 * window_slot
    with pytest.raises(Exception, match="cannot fit"):
        eng.check_memory_budget("strict", budget_bytes=eng.param_nbytes())


def test_mixed_rings_are_one_donated_pytree(model):
    m, _ = model
    eng = _engine(m)
    eng.warmup()
    before = jax.tree_util.tree_leaves(eng._kv)
    assert len(before) == 2 * 5 + 1
    eng.admit(0, _tokens(13).tolist())
    assert all(a.is_deleted() for a in before)
    before = jax.tree_util.tree_leaves(eng._kv)
    eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
    assert all(a.is_deleted() for a in before)
    assert [int(p) for p in eng._kv[-1]] == [14, 1]
    assert eng._pos_host.tolist() == [14, 1]


def test_cache_counters_are_sampled_only_while_the_profiler_is_on(model):
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
        eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
        got = {}
        for s in profiler.counter_samples():
            got.setdefault(s["name"], []).append(s["args"]["value"])
    finally:
        profiler.stop_profiler()
        profiler.reset_profiler()
    assert got["generation::cache_bytes"] == [
        list(eng.cache_bytes_by_kind())]
    # slot 0 vacant at positions 0 then 1, slot 1 at 20 then 21: the
    # full layer reads pos + 1 rows, each of four window layers 8 at most
    assert got["generation::kv_rows_read"] == [
        [1 + 21, 4 * (1 + 8), 0], [2 + 22, 4 * (2 + 8), 0]]
    assert len(got["moe::experts_hit"][0]) == 4      # the sparse layers
    assert len(got["moe::expert_load"]) == 3          # prompt and two steps

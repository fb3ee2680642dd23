"""The hybrid MoE decoder (models/solar_open2.py) and what it forced:
per-layer cache kinds, routed experts as one member of an expert-
parallel group, gated-delta-rule linear attention. Tiny widths,
float32, seeded; the plain reference is the benchmark's
(benchmark/configs/solar-open2-250b/reference.py), which imports
nothing of the program."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.errors import InvalidArgumentError
from paddle_tpu.generation import GenerationEngine
from paddle_tpu.generation import cache as gcache
from paddle_tpu.models import (GPTForCausalLM, HybridMoEConfig,
                               HybridMoEForCausalLM, gpt_tiny_config)
from paddle_tpu.nn import RecurrentCache, StaticCache
from paddle_tpu.nn.linear_attention import (GatedDeltaAttention,
                                            gated_delta_chunked,
                                            gated_delta_recurrent)
from paddle_tpu.parallel.moe import RoutedExperts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmark", "configs", "solar-open2-250b",
                        "reference.py")
    spec = importlib.util.spec_from_file_location("solar_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
# the reference's configuration keys, at a toy size: 4 layers (one
# period), this member holds experts 4..11 of 16 and 64 of 97 rows
CFG = dict(
    hidden_size=32, num_hidden_layers=4, num_attention_heads=4, head_dim=8,
    num_key_value_heads=2, vocab_size=64, gqa_layers=[0],
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=8,
                            num_heads=4),
    moe_intermediate_size=16, rms_norm_eps=1e-5, n_routed_experts=8,
    experts_held=[4, 8], num_experts_per_tok=4, routed_scaling_factor=1.0,
    published=dict(n_routed_experts=16, vocab_size=97),
    assumed_sizes=dict(kda_gate_rank=8, shared_expert_width=16,
                       initializer_range=0.2))
CACHE_LEN = 32


def _model(seed=5):
    m = HybridMoEForCausalLM(HybridMoEConfig(
        vocab_size=97, vocab_held=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        gqa_layers=(0,), linear_attn_config=CFG["linear_attn_config"],
        kda_gate_rank=8, moe_intermediate_size=16, n_routed_experts=16,
        num_experts_per_tok=4, experts_held=(4, 8)))
    w = REF.weights(CFG, jax.random.PRNGKey(seed))
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


def test_full_forward_matches_the_plain_reference(model):
    m, w = model
    toks = _tokens(45)
    want = np.asarray(REF.forward(w, jnp.asarray(toks), CFG))
    got = np.asarray(m(jnp.asarray(toks[None]))._array[0])
    assert want.std() > 0.5
    np.testing.assert_allclose(got, want, atol=1e-4)


def _cached_logits(m, toks, n_prompt, bucket):
    """Logits of every position of ``toks`` as the engine computes them:
    one right-padded prefill of the first ``n_prompt`` into fresh caches
    of every kind, then one cached decode step a token."""
    kinds = m.cache_spec()
    fresh = gcache.init_kinds_cache(kinds, 1, CACHE_LEN, "float32")
    padded = np.full(bucket, 2, np.int64)
    padded[:n_prompt] = toks[:n_prompt]
    mask = jnp.where(jnp.arange(bucket) < n_prompt, 0.0,
                     gcache.NEG_INF).astype(jnp.float32)[None, None, None]
    logits, caches = m(jnp.asarray(padded[None]), attention_mask=mask,
                       caches=gcache.kinds_layer_caches(kinds, fresh))
    out = [np.asarray(logits._array[0, :n_prompt])]
    kv = gcache.unzip_kinds_caches(caches) + (
        jnp.asarray([n_prompt], jnp.int32),)
    for i in range(n_prompt, len(toks)):
        mask = gcache.decode_mask(kv[-1], CACHE_LEN)
        logits, caches = m(jnp.asarray(toks[i:i + 1][None]),
                           attention_mask=mask,
                           caches=gcache.kinds_layer_caches(kinds, kv))
        out.append(np.asarray(logits._array[0]))
        kv = gcache.unzip_kinds_caches(caches) + (kv[-1] + 1,)
    return np.concatenate(out)


def test_prefill_then_decode_matches_full_forward_across_a_ring_wrap(model):
    """Right-padded prompt (11 real tokens in a bucket of 16), then 40
    cached steps, 19 of them past the ring's 32 rows: logits within 1e-4
    of the reference's full forward pass (whose attention then sees what
    a ring of 32 rows keeps). The padding advanced no state, the tail of
    the convolution is the prompt's, and a ring row is where the mask
    looks for it."""
    m, w = model
    toks = _tokens(51, seed=1)
    got = _cached_logits(m, toks, 11, 16)
    want = np.asarray(REF.forward(w, jnp.asarray(toks), CFG,
                                  window=CACHE_LEN))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_engine_serves_the_references_own_tokens(model):
    """Through GenerationEngine (admit + step, two slots turning over):
    every served token is the reference's argmax at its position, to
    1e-4 of its largest logit, also past the ring's wrap."""
    m, w = model
    eng = _engine(m)
    eng.warmup()
    assert eng.extra_compiles() == 0
    prompts = [_tokens(n, seed=n).tolist() for n in (5, 13, 20)]
    outs = eng.generate(prompts, max_new_tokens=30, stop_at_eos=False)
    assert eng.extra_compiles() == 0
    for p, o in zip(prompts, outs):
        seq = np.asarray(p + o)
        logits = np.asarray(REF.forward(w, jnp.asarray(seq), CFG,
                                        window=CACHE_LEN))
        own = logits[np.arange(len(seq) - 1), seq[1:]]
        gap = (logits.max(-1)[:-1] - own)[len(p) - 1:]
        assert gap.max() <= 1e-4


@pytest.mark.parametrize("t", [64, 150, 7])
def test_chunked_linear_attention_ends_in_the_recurrences_state(t):
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    b, h, d = 2, 3, 16
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, h, d))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, d))
    # decays down to exp(-250) a token: exp(-cumsum) would overflow
    g = -jnp.exp(jax.random.normal(ks[3], (b, t, h, d)) * 1.5)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    s0 = jax.random.normal(ks[5], (b, h, d, d))
    s1, o1 = gated_delta_recurrent(s0, q, k, v, g, beta)
    s2, o2 = jax.jit(gated_delta_chunked)(s0, q, k, v, g, beta)
    np.testing.assert_allclose(s2, s1, atol=2e-5)
    np.testing.assert_allclose(o2, o1, atol=2e-4)


def test_right_padding_does_not_advance_state_or_tail():
    m = GatedDeltaAttention(32, 4, 8, initializer_range=0.3)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 20, 32))
    zero = RecurrentCache(jnp.zeros((1, 4, 8, 8)), jnp.zeros((1, 3, 96)),
                          jnp.zeros((1,), jnp.int32))
    n = 13
    y, c = m(x, cache=zero, valid=(jnp.arange(20) < n)[None])
    step, ys = zero, []
    for i in range(n):
        yi, step = m(x[:, i:i + 1], cache=step)
        ys.append(yi)
    np.testing.assert_allclose(y[:, :n], jnp.concatenate(ys, 1), atol=1e-5)
    np.testing.assert_allclose(c.state, step.state, atol=1e-5)
    np.testing.assert_allclose(c.conv_tail, step.conv_tail, atol=1e-6)


def _share(full, first, count):
    m = RoutedExperts(16, 24, 32, 8, held=(first, count), shared_width=24,
                      routed_scaling_factor=full.routed_scaling_factor)
    m.router._array = full.router._array
    for n in ("w_gate", "w_up", "w_down"):
        getattr(m, n)._array = getattr(full, n)._array[first:first + count]
    for n in ("shared_gate", "shared_up", "shared_down"):
        getattr(m, n)._array = getattr(full, n)._array
    return m


def _dense_experts(m, x):
    """The layer the plain way: every held expert for every token."""
    x2 = x.reshape(-1, x.shape[-1])
    idx, w = m.route(x2)
    y = jnp.zeros_like(x2)
    for e in range(m.count):
        h = jax.nn.silu(x2 @ m.w_gate._array[e]) * (x2 @ m.w_up._array[e])
        y = y + jnp.where(idx == e + m.first, w, 0).sum(-1)[:, None] \
            * (h @ m.w_down._array[e])
    shared = (jax.nn.silu(x2 @ m.shared_gate._array)
              * (x2 @ m.shared_up._array)) @ m.shared_down._array
    return y.reshape(x.shape), shared.reshape(x.shape)


@pytest.mark.parametrize("scaling", [
    pytest.param(1.0, id="solar_open2"), pytest.param(2.5, id="exaone_moe")])
def test_the_shares_add_up_to_the_uncut_layer(scaling):
    """Eight members hold 4 of 32 experts each: their parts of the
    result, the shared expert counted once, sum to the whole layer, with
    the router's weights scaled as either served family scales them."""
    full = RoutedExperts(16, 24, 32, 8, shared_width=24,
                         routed_scaling_factor=scaling,
                         initializer_range=0.5)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 7, 16))
    whole = full(x)
    routed, shared = _dense_experts(full, x)
    np.testing.assert_allclose(whole, routed + shared, atol=2e-5)
    total = shared
    for c in range(8):
        member = _share(full, 4 * c, 4)
        total = total + jax.jit(lambda x, m=member: m(x))(x) - shared
    np.testing.assert_allclose(total, whole, atol=5e-5)


def test_routing_is_dropless_under_a_skewed_router():
    """A router that sends every token to experts 0 and 1 first: both
    take all 64 tokens (Switch's capacity would drop most), and the
    result is the plain loop's."""
    m = RoutedExperts(16, 24, 8, 2, held=(0, 4), shared_width=0,
                      initializer_range=0.5)
    bias = jnp.zeros((16, 8)).at[:, 0].set(50.0).at[:, 1].set(40.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (64, 16))) + 0.1
    m.router._array = bias
    y = m(x)
    assert m.last_load.tolist() == [64, 64, 0, 0]
    idx, w = m.route(x)
    want = jnp.zeros_like(x)
    for e in range(4):
        h = jax.nn.silu(x @ m.w_gate._array[e]) * (x @ m.w_up._array[e])
        want = want + jnp.where(idx == e, w, 0).sum(-1)[:, None] \
            * (h @ m.w_down._array[e])
    np.testing.assert_allclose(y, want, atol=5e-5)


def test_what_a_state_layer_cannot_use_refuses_by_name(model):
    m, _ = model
    for kw in (dict(kv_cache_layout="paged"), dict(kv_cache_dtype="int8"),
               dict(draft_model=GPTForCausalLM(gpt_tiny_config()))):
        with pytest.raises(InvalidArgumentError, match="StateKind"):
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


def test_capacity_accounting_sums_over_the_kinds(model):
    """One K/V layer (2 heads x 8, 32 rows, float32) and three state
    layers (4 x 8 x 8 float32 state, 3 x 96 float32 tail): the plan
    equals the arrays byte for byte, a state layer costs the same at any
    cache_len, and suggest_decode_slots divides by that slot."""
    m, _ = model
    eng = _engine(m, slots=3)
    kv_slot = CACHE_LEN * 2 * 2 * 8 * 4
    state_slot = 3 * (4 * 8 * 8 * 4 + 3 * 96 * 4)
    assert eng.slot_nbytes() == kv_slot + state_slot + 4
    assert eng.kv_bytes_per_token() == 2 * 2 * 8 * 4
    assert eng.cache_nbytes() == 3 * eng.slot_nbytes()
    assert eng.state_nbytes() == 3 * state_slot
    assert eng.hbm_required_bytes() == eng.param_nbytes() \
        + eng.cache_nbytes()
    assert eng.hbm_required_bytes(slots=5) - eng.hbm_required_bytes() \
        == 2 * eng.slot_nbytes()
    budget = eng.param_nbytes() + 7 * eng.slot_nbytes() + 11
    assert eng.suggest_decode_slots(budget) == 7
    longer = _engine(m, slots=3, cache_len=64, prefill_buckets=(8,))
    assert longer.slot_nbytes() - eng.slot_nbytes() == kv_slot
    assert longer.state_nbytes() == eng.state_nbytes()
    with pytest.raises(Exception, match="cannot fit"):
        eng.check_memory_budget("strict", budget_bytes=eng.param_nbytes())


def test_bfloat16_ring_halves_the_rows_and_keeps_the_state(model):
    m, _ = model
    f32, bf16 = _engine(m), _engine(m, kv_cache_dtype="bfloat16")
    assert f32.kv_bytes_per_token() == 2 * bf16.kv_bytes_per_token()
    assert bf16.state_nbytes() == f32.state_nbytes()
    k = bf16._kv[0][0]
    assert k.dtype == jnp.bfloat16 and k.shape == (2, 2, CACHE_LEN, 8)
    assert bf16._kv[1][0].dtype == jnp.float32


def test_kinds_cache_is_one_donated_pytree(model):
    """Every leaf of the cache goes into a ring program donated and
    comes back new: none is held across a call."""
    m, _ = model
    eng = _engine(m)
    eng.warmup()
    before = jax.tree_util.tree_leaves(eng._kv)
    assert len(before) == 2 * 4 + 1
    eng.admit(0, [3, 4, 5, 6])
    assert all(a.is_deleted() for a in before)
    before = jax.tree_util.tree_leaves(eng._kv)
    eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
    assert all(a.is_deleted() for a in before)
    assert [int(p) for p in eng._kv[-1]] == [5, 1]


def test_routing_counters_are_sampled_only_while_the_profiler_is_on(model):
    from paddle_tpu import profiler

    m, _ = model
    eng = _engine(m)
    eng.warmup()
    profiler.reset_profiler()
    eng.admit(0, [3, 4, 5, 6])
    eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
    assert not [s for s in profiler.counter_samples()
                if s["name"].startswith("moe::")]
    profiler.start_profiler(state="CPU")
    try:
        eng.admit(1, [7, 8, 9])
        eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
        got = {}
        for s in profiler.counter_samples():
            got.setdefault(s["name"], []).append(s["args"]["value"])
    finally:
        profiler.stop_profiler()
        profiler.reset_profiler()
    assert len(got["moe::expert_load"]) == 2           # prompt and step
    pairs, hit = got["moe::pairs_here"][0], got["moe::experts_hit"][0]
    assert len(pairs) == len(hit) == 4                  # a value a layer
    assert all(0 <= h <= 8 and h <= p <= 2 * 4 for h, p in zip(hit, pairs))
    assert sum(got["moe::expert_load"][1]) == sum(pairs)
    assert got["generation::state_bytes"] == [eng.state_nbytes()]


def test_gpt_engine_cache_and_signatures_are_what_they_were():
    """A model whose cache_spec() is (layers, heads, head_dim) takes the
    old path: the cache is (k, v, pos) with a tuple of [S, H, C, D]
    arrays a plane, the decode program's arguments are the state's
    leaves, those 2 L + 1 cache leaves, tokens, temperatures and the
    counter, and it returns (cache, tokens) and nothing else."""
    m = GPTForCausalLM(gpt_tiny_config())
    eng = GenerationEngine(m, slots=3, cache_len=16, prefill_buckets=(8,),
                           temperature=0.0, top_k=0,
                           kv_cache_layout="ring", kv_cache_dtype="float32")
    assert eng._kinds is None
    k, v, pos = eng._kv
    assert len(k) == len(v) == 2 and k[0].shape == (3, 4, 16, 16)
    assert pos.shape == (3,)
    _, jitted, make = eng._decode_call(np.zeros(3, np.int32),
                                       np.zeros(3, np.float32), 0)
    args = make()
    n_state = len(jax.tree_util.tree_leaves(args[0]))
    assert n_state == len(list(m.named_parameters()))
    assert len(jax.tree_util.tree_leaves(args)) == n_state + 5 + 3
    out = jax.eval_shape(jitted, *args)
    assert len(out) == 2 and len(jax.tree_util.tree_leaves(out[0])) == 5
    assert eng.kv_bytes_per_token() == 2 * 2 * 4 * 16 * 4
    assert eng.slot_nbytes() == 16 * eng.kv_bytes_per_token() + 4

"""Generative inference: GPT decoder, static ring KV cache, compile-once
decode, sampling/stopping.

Pins the PR's production contracts:
- mask normalization: bool/float x rank-2/3/4 masks compose identically
  (the causal+cache composition depends on it);
- KV-cache parity goldens: decode-with-cache token-by-token equals the
  full-sequence forward logits, INCLUDING ring-buffer wraparound past
  the cache window (sliding-window equivalence);
- compile-bound generation: warmup costs exactly len(prefill ladder) + 1
  programs, mixed traffic afterwards costs zero (``extra_compiles()``);
- sampling (greedy/top-k/temperature) and EOS/length stopping.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.generation import (
    COMPILE_COUNTER,
    GenerationEngine,
    StaticCache,
    causal_mask,
    decode_mask,
    prefill_mask,
    sample_logits,
    top_k_filter,
)
from paddle_tpu.models import GPTForCausalLM, gpt_tiny_config
from paddle_tpu.nn.transformer import (
    MultiHeadAttention,
    TransformerDecoderLayer,
    _convert_attention_mask,
)


def _tiny_lm(window=None, seed=3):
    paddle.seed(seed)
    cfg = gpt_tiny_config()
    cfg.attention_window = window
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


# -- mask conversion goldens (satellite) -------------------------------------

def test_convert_attention_mask_bool_float_rank_parity():
    """Bool (True=keep) and additive float masks, at every accepted
    rank, must land on the SAME [B,1|H,Lq,Lk]-broadcastable additive
    mask."""
    rng = np.random.RandomState(0)
    keep = rng.rand(2, 5, 5) > 0.4            # [B, Lq, Lk] bool
    add = np.where(keep, 0.0, -1e9).astype("float32")

    got_bool = _convert_attention_mask(paddle.to_tensor(keep), "float32")
    got_float = _convert_attention_mask(paddle.to_tensor(add), "float32")
    assert list(got_bool.shape) == [2, 1, 5, 5]  # rank 3 -> rank 4
    np.testing.assert_allclose(np.asarray(got_bool.numpy()),
                               np.asarray(got_float.numpy()))

    # rank 2 gains [1, 1, ...]; rank 4 passes through untouched
    got2 = _convert_attention_mask(paddle.to_tensor(keep[0]), "float32")
    assert list(got2.shape) == [1, 1, 5, 5]
    np.testing.assert_allclose(np.asarray(got2.numpy())[0, 0], add[0])
    got4 = _convert_attention_mask(
        paddle.to_tensor(add[:, None]), "float32")
    assert list(got4.shape) == [2, 1, 5, 5]


def test_attention_same_under_bool_and_float_masks():
    """The attention OUTPUT is identical whichever mask form the caller
    composed — encoder/decoder call sites may mix them freely."""
    paddle.seed(0)
    mha = MultiHeadAttention(16, 2)
    mha.eval()
    x = paddle.to_tensor(np.random.RandomState(1).randn(2, 5, 16)
                         .astype("float32"))
    keep = np.tril(np.ones((5, 5), bool))
    out_bool = mha(x, x, x, attn_mask=paddle.to_tensor(keep))
    out_float = mha(x, x, x, attn_mask=paddle.to_tensor(
        np.where(keep, 0.0, -1e9).astype("float32")))
    np.testing.assert_allclose(np.asarray(out_bool.numpy()),
                               np.asarray(out_float.numpy()),
                               rtol=1e-6, atol=1e-6)


def test_causal_mask_window_golden():
    m = np.asarray(causal_mask(4, window=2).numpy())
    keep = m == 0.0
    expect = np.array([
        [1, 0, 0, 0],
        [1, 1, 0, 0],
        [0, 1, 1, 0],
        [0, 0, 1, 1],
    ], bool)
    np.testing.assert_array_equal(keep, expect)
    # no window = standard causal
    full = np.asarray(causal_mask(4).numpy()) == 0.0
    np.testing.assert_array_equal(full, np.tril(np.ones((4, 4), bool)))


def test_composed_causal_plus_cache_masks():
    """prefill_mask == causal ∧ valid-entries; decode_mask keeps exactly
    the written window (incl. after wraparound)."""
    pm = np.asarray(prefill_mask(4, 6, jnp.asarray(3)))[0, 0]  # [4, 6]
    keep = pm == 0.0
    expect = np.zeros((4, 6), bool)
    for t in range(4):
        for j in range(6):
            expect[t, j] = (j <= t) and (j < 3)
    np.testing.assert_array_equal(keep, expect)

    dm = np.asarray(decode_mask(jnp.asarray([0, 2, 7]), 4))[:, 0, 0]
    keep = dm == 0.0
    np.testing.assert_array_equal(
        keep, np.array([[1, 0, 0, 0],      # pos 0: only the write
                        [1, 1, 1, 0],      # pos 2: entries 0..2
                        [1, 1, 1, 1]],     # wrapped: whole window
                       bool))


# -- static-cache incremental path ------------------------------------------

def test_static_cache_ring_write_shapes_and_wrap():
    paddle.seed(0)
    mha = MultiHeadAttention(16, 2)
    mha.eval()
    cache = mha.gen_static_cache(2, 4)
    assert cache.k.shape == (2, 2, 4, 8)
    x = paddle.to_tensor(np.random.RandomState(0).randn(2, 1, 16)
                         .astype("float32"))
    # write at pos 5 -> ring index 1; shapes unchanged
    cache = StaticCache(cache.k, cache.v, jnp.asarray([5, 5], jnp.int32))
    mask = paddle.to_tensor(np.zeros((1, 1, 1, 4), "float32"))
    out, new = mha(x, x, x, attn_mask=mask, cache=cache)
    assert new.k.shape == cache.k.shape
    changed = np.where(np.abs(np.asarray(new.k - cache.k)).sum(
        axis=(0, 1, 3)) > 0)[0]
    np.testing.assert_array_equal(changed, [1])  # only ring slot 5 % 4


def test_decoder_layer_decoder_only_has_no_cross_attention():
    lay = TransformerDecoderLayer(16, 2, 32, with_cross_attention=False)
    names = [n for n, _ in lay.named_parameters()]
    assert not any("cross_attn" in n for n in names)
    with_cross = TransformerDecoderLayer(16, 2, 32)
    assert any("cross_attn" in n
               for n, _ in with_cross.named_parameters())
    # memory stays required when cross-attention exists
    x = paddle.to_tensor(np.zeros((1, 3, 16), "float32"))
    with pytest.raises(ValueError):
        with_cross(x)


# -- KV-cache parity goldens --------------------------------------------------

def _full_forward_logits(m, ids):
    """[T, V] full-sequence forward logits (model's own causal mask)."""
    out = m(np.asarray(ids)[None].astype("int32"))
    return np.asarray(out.numpy())[0]


def _incremental_logits(m, ids, cache_len):
    """Token-by-token decode through StaticCache; logits per position."""
    from paddle_tpu.generation import cache as C

    spec = m.cache_spec()
    ck, cv, pos = C.init_cache(spec[0], 1, spec[1], cache_len, spec[2])
    outs = []
    for t, tok in enumerate(ids):
        caches = C.layer_caches(ck, cv, pos)
        mask = C.decode_mask(pos, cache_len)
        logits, new_caches = m(
            np.asarray([[tok]], "int32"),
            position_ids=np.asarray([[t]], "int32"),
            attention_mask=jnp.asarray(mask), caches=caches)
        ck, cv = C.unzip_layer_caches(new_caches)
        pos = pos + 1
        outs.append(np.asarray(logits.numpy())[0, 0])
    return np.stack(outs)


def test_cache_parity_no_wraparound():
    """Within the window the cached decode must reproduce the plain
    full-forward logits exactly (same function, different program)."""
    m = _tiny_lm(window=None)
    ids = np.random.RandomState(5).randint(3, 200, size=10)
    full = _full_forward_logits(m, ids)
    inc = _incremental_logits(m, ids, cache_len=16)  # 10 < 16: no wrap
    np.testing.assert_allclose(inc, full, rtol=2e-4, atol=2e-4)


def test_cache_parity_ring_wraparound():
    """Past the window the ring keeps the last C tokens — numerically
    identical to the full forward under a width-C sliding window."""
    C = 6
    m = _tiny_lm(window=C)
    ids = np.random.RandomState(7).randint(3, 200, size=17)  # 17 >> 6
    full = _full_forward_logits(m, ids)  # model mask has window=C
    inc = _incremental_logits(m, ids, cache_len=C)
    np.testing.assert_allclose(inc, full, rtol=2e-4, atol=2e-4)


# -- compile-once engine ------------------------------------------------------

def _compiles():
    return profiler.counters().get(COMPILE_COUNTER, 0)


def test_engine_steady_state_is_compile_bound():
    """Warmup = len(prefill ladder) + 1 decode compile; any mixed
    traffic afterwards costs ZERO more — the serving bucket-ladder
    guarantee on the sequence axis."""
    m = _tiny_lm(window=32)
    eng = GenerationEngine(m, slots=2, cache_len=32,
                           prefill_buckets=(4, 8), seed=1)
    from paddle_tpu.errors import PreconditionNotMetError

    with pytest.raises(PreconditionNotMetError):
        eng.extra_compiles()  # before warmup: nothing to compare
    before = _compiles()
    eng.warmup()
    assert _compiles() - before == len(eng.prefill_buckets) + 1
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(3, 200, size=n))
               for n in (1, 3, 8, 5, 2, 7, 4, 6)]
    outs = eng.generate(prompts, max_new_tokens=5)
    assert all(1 <= len(o) <= 5 for o in outs)
    assert eng.extra_compiles() == 0
    assert _compiles() - before == len(eng.prefill_buckets) + 1
    # warmup is idempotent
    eng.warmup()
    assert _compiles() - before == len(eng.prefill_buckets) + 1


def test_warmup_traces_the_largest_program_first_and_decode_last():
    """The warm-up traces its programs one after another on the caller's
    thread while the ones before load on theirs, so what it waits for at
    its end is the last program's load: the largest bucket goes first
    and the decode program, the quickest to load, last (PERF.md, PR 47);
    each run follows its program in the same order."""
    eng = GenerationEngine(_tiny_lm(window=32), slots=2, cache_len=32,
                           prefill_buckets=(4, 8, 16), seed=1)
    plan = eng._warmup_plan("generate")
    # a prefill call's fourth argument is its padded prompt, [1, bucket]
    sizes = [calls[0][2]()[3].shape[1] if calls[0][0] == "prefill" else 0
             for calls, _ in plan]
    assert [calls[0][0] for calls, _ in plan] \
        == ["prefill", "prefill", "prefill", "decode"]
    assert sizes == [16, 8, 4, 0]


def test_engine_greedy_matches_full_forward():
    """Greedy engine tokens == the argmax chain of repeated full
    forwards (bucket padding and slot co-batching are numerically
    inert)."""
    m = _tiny_lm(window=16)
    eng = GenerationEngine(m, slots=2, cache_len=16,
                           prefill_buckets=(4, 8), seed=2).warmup()
    prompt = [5, 9, 4]
    got = eng.generate([prompt], max_new_tokens=8, temperature=0.0)[0]
    ref, ids = [], list(prompt)
    for _ in range(8):
        nxt = int(_full_forward_logits(m, ids)[-1].argmax())
        ref.append(nxt)
        ids.append(nxt)
    assert got == ref


def test_engine_validation():
    m = _tiny_lm()
    eng = GenerationEngine(m, slots=1, cache_len=16, prefill_buckets=(4, 8))
    from paddle_tpu.errors import InvalidArgumentError

    with pytest.raises(InvalidArgumentError):
        eng.validate([], 4)                     # empty prompt
    with pytest.raises(InvalidArgumentError):
        eng.validate([1] * 9, 4)                # exceeds largest bucket
    with pytest.raises(InvalidArgumentError):
        eng.validate([1, 2], 0)                 # no budget
    with pytest.raises(InvalidArgumentError):
        eng.validate([1, 2], 10 ** 6)           # past max positions
    assert eng.validate([1, 2, 3], 4) == 3
    with pytest.raises(InvalidArgumentError):
        GenerationEngine(m, slots=1, cache_len=4, prefill_buckets=(8,))


# -- sampling / stopping ------------------------------------------------------

def test_sampling_greedy_topk_temperature():
    logits = jnp.asarray(np.random.RandomState(0).randn(3, 50), jnp.float32)
    key = jax.random.PRNGKey(0)
    # temperature 0 => argmax, any key
    greedy = sample_logits(logits, key, 0.0)
    np.testing.assert_array_equal(np.asarray(greedy),
                                  np.asarray(logits).argmax(-1))
    # top-k filter keeps exactly k finite entries
    filt = np.asarray(top_k_filter(logits, 5))
    assert (np.isfinite(filt).sum(-1) == 5).all()
    assert np.asarray(top_k_filter(logits, 0)).shape == (3, 50)
    # sampled tokens always come from the top-k support
    for s in range(5):
        toks = np.asarray(sample_logits(
            logits, jax.random.PRNGKey(s), 1.5, top_k=5))
        for row, tok in enumerate(toks):
            assert np.isfinite(filt[row, tok])
    # per-row temperature: row 0 greedy, rows 1-2 sampled (still valid ids)
    mixed = np.asarray(sample_logits(
        logits, key, jnp.asarray([0.0, 1.0, 2.0])))
    assert mixed[0] == np.asarray(logits).argmax(-1)[0]
    assert ((0 <= mixed) & (mixed < 50)).all()


def test_engine_stopping_eos_and_length():
    m = _tiny_lm(window=16)
    eng = GenerationEngine(m, slots=1, cache_len=16,
                           prefill_buckets=(4,), seed=0).warmup()
    # find the greedy continuation, then declare one of its tokens "EOS"
    free = eng.generate([[5, 9, 4]], max_new_tokens=6, stop_at_eos=False)[0]
    assert len(free) == 6
    eng.eos_id = free[2]
    first = free.index(eng.eos_id)  # generation must stop at the FIRST hit
    stopped = eng.generate([[5, 9, 4]], max_new_tokens=6)[0]
    assert stopped == free[:first + 1] and stopped[-1] == eng.eos_id
    # stop_at_eos=False ignores it again
    again = eng.generate([[5, 9, 4]], max_new_tokens=6,
                         stop_at_eos=False)[0]
    assert again == free


def test_seq2seq_greedy_routes_through_shared_decode_loop(monkeypatch):
    """models/seq2seq.py must delegate to generation.sampling.decode_loop
    (one decode-loop implementation in the codebase)."""
    from paddle_tpu.generation import sampling as S
    from paddle_tpu.models import TransformerSeq2Seq

    calls = []
    orig = S.decode_loop

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(S, "decode_loop", spy)
    paddle.seed(0)
    m = TransformerSeq2Seq(16, 16, d_model=16, nhead=2, num_layers=1,
                           dim_feedforward=32, dropout=0.0)
    m.eval()
    src = paddle.to_tensor(np.random.RandomState(0).randint(
        3, 16, size=(2, 4)).astype("int64"))
    ys = m.greedy_decode(src, max_len=5)
    assert calls and list(ys.shape) == [2, 5]

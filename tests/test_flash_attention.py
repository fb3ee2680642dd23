"""Flash attention tests (CPU fallback path; the pallas kernel itself is
exercised on TPU by chip_smoke.py)."""
import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.ops.pallas.flash_attention import (
    _flash,
    _plain_attention,
    flash_attention,
)


def _qkv(b=2, h=2, l=64, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: rng.randn(b, h, l, d).astype("float32")
    return mk(), mk(), mk()


def test_matches_reference_no_bias():
    q, k, v = _qkv()
    out = flash_attention(q, k, v)
    ref = _plain_attention(q, k, v, None, False, q.shape[-1] ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_causal_and_bias():
    q, k, v = _qkv()
    bias = np.random.RandomState(1).randn(2, 1, 64, 64).astype("float32")
    out = flash_attention(q, k, v, bias=bias, causal=True)
    ref = _plain_attention(q, k, v, bias, True, q.shape[-1] ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_eager_tensor_backward():
    q, k, v = _qkv(l=32)
    qt = paddle.to_tensor(q, stop_gradient=False)
    kt = paddle.to_tensor(k, stop_gradient=False)
    vt = paddle.to_tensor(v, stop_gradient=False)
    out = flash_attention(qt, kt, vt, causal=True)
    out.sum().backward()
    assert qt.grad is not None
    assert np.isfinite(qt.grad.numpy()).all()
    assert kt.grad is not None and vt.grad is not None


def test_mha_flash_flag(monkeypatch):
    from paddle_tpu.nn import transformer as _tf

    monkeypatch.setattr(_tf, "FLASH_ATTENTION_MIN_SEQ", 1)
    paddle.seed(0)
    mha = nn.MultiHeadAttention(32, 4, dropout=0.0, use_flash_attention=True)
    x = paddle.to_tensor(np.random.RandomState(0).randn(2, 16, 32).astype("float32"))
    out = mha(x, x, x)
    assert list(out.shape) == [2, 16, 32]
    # matches the plain path numerically
    paddle.seed(0)
    mha2 = nn.MultiHeadAttention(32, 4, dropout=0.0)
    mha2.set_state_dict(mha.state_dict())
    ref = mha2(x, x, x)
    np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-4, atol=1e-5)


def test_ring_dropout_conflict_raises():
    """Ring attention still rejects dropout; flash now supports it."""
    try:
        nn.MultiHeadAttention(32, 4, dropout=0.1, use_ring_attention=True)
        assert False
    except ValueError:
        pass
    nn.MultiHeadAttention(32, 4, dropout=0.1, use_flash_attention=True)


def test_dropout_forward_stats():
    """Dropout drops ~rate of attention probs and rescales survivors, so
    the output mean stays in the same ballpark and some outputs change."""
    q, k, v = _qkv(l=64)
    key = jax.random.PRNGKey(7)
    out0 = np.asarray(flash_attention(q, k, v))
    outd = np.asarray(
        flash_attention(q, k, v, dropout_rate=0.5, dropout_key=key)
    )
    assert not np.allclose(out0, outd)
    # upscale-in-train keeps expectation: means agree loosely
    np.testing.assert_allclose(out0.mean(), outd.mean(), atol=0.05)


def test_dropout_deterministic_per_key():
    q, k, v = _qkv(l=64)
    key = jax.random.PRNGKey(3)
    a = np.asarray(flash_attention(q, k, v, dropout_rate=0.3, dropout_key=key))
    b = np.asarray(flash_attention(q, k, v, dropout_rate=0.3, dropout_key=key))
    np.testing.assert_array_equal(a, b)
    c = np.asarray(
        flash_attention(q, k, v, dropout_rate=0.3,
                        dropout_key=jax.random.PRNGKey(4))
    )
    assert not np.array_equal(a, c)


def test_dropout_backward_consistent_mask():
    """The recompute backward must see the same mask as the forward:
    grad via custom_vjp == grad of the seeded plain implementation."""
    q, k, v = _qkv(l=32, d=8)
    key = jax.random.PRNGKey(11)
    seed = jax.random.bits(key, (), "uint32").astype(jnp.int32)
    scale = q.shape[-1] ** -0.5

    def loss_custom(q, k, v):
        return jnp.sum(_flash(q, k, v, seed, False, scale, 0.4) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            _plain_attention(q, k, v, None, False, scale, 0.4, seed) ** 2
        )

    gc = jax.grad(loss_custom, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gc, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_mha_flash_dropout_trains(monkeypatch):
    """Flash attention with dropout under the eager autograd tape."""
    from paddle_tpu.nn import transformer as _tf

    monkeypatch.setattr(_tf, "FLASH_ATTENTION_MIN_SEQ", 1)
    paddle.seed(0)
    mha = nn.MultiHeadAttention(32, 4, dropout=0.2, use_flash_attention=True)
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(2, 16, 32).astype("float32"),
        stop_gradient=False,
    )
    out = mha(x, x, x)
    out.sum().backward()
    g = mha.q_proj.weight.grad
    assert g is not None and np.isfinite(g.numpy()).all()


def test_bert_flash_config_matches_plain_eval(monkeypatch):
    """BertModel(use_flash_attention=True) in eval mode (dropout off)
    matches the plain-attention model with identical weights."""
    from paddle_tpu.models import BertConfig, BertModel
    from paddle_tpu.nn import transformer as _tf

    monkeypatch.setattr(_tf, "FLASH_ATTENTION_MIN_SEQ", 1)
    paddle.seed(0)
    cfg = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, intermediate_size=128,
               max_position_embeddings=64)
    m1 = BertModel(BertConfig(**cfg))
    m2 = BertModel(BertConfig(**cfg, use_flash_attention=True))
    m2.set_state_dict(m1.state_dict())
    m1.eval(), m2.eval()
    ids = paddle.to_tensor(
        np.random.RandomState(1).randint(1, 256, (2, 16)).astype("int64"))
    s1, p1 = m1(ids)
    s2, p2 = m2(ids)
    np.testing.assert_allclose(s1.numpy(), s2.numpy(), rtol=1e-4, atol=1e-5)


def test_block_adaptation_for_non_multiple_lengths():
    """Seq lengths that are 128-multiples but not 256-multiples (384,
    640) must shrink the tile to the 128 base block — the grids FLOOR-
    divide, and with block 256 the tail rows were silently dropped
    (garbage forward, NaN gradients; caught on-chip at L=384)."""
    from paddle_tpu.ops.pallas.flash_attention import _effective_blocks

    assert _effective_blocks(512, 512, 256, 256) == (256, 256)
    assert _effective_blocks(384, 384, 256, 256) == (128, 128)
    assert _effective_blocks(640, 640, 256, 256) == (128, 128)
    assert _effective_blocks(128, 128, 256, 256) == (128, 128)
    assert _effective_blocks(256, 256, 256, 256) == (256, 256)
    assert _effective_blocks(384, 512, 256, 256) == (128, 256)  # lq != lk
    # every gate-admitted length divides its effective block
    for l in range(128, 2049, 128):
        bq, _ = _effective_blocks(l, l, 256, 256)
        assert l % bq == 0, (l, bq)


def test_bwd_small_vmem_gate_shared_between_fwd_and_bwd():
    """The one-pass kernels hold h*(7 l d bf16 + 3 l^2 f32) per program;
    at BERT-base geometry they fit at L=128 and must NOT be chosen at
    L>=256 (observed 18.5MB scoped-vmem OOM on chip). The predicate is
    SHARED by forward and backward dispatch: a small-forward with a
    tiled-backward would regenerate different dropout masks (per-batch
    vs per-head PRNG seeding) for every head but the first."""
    from paddle_tpu.ops.pallas.flash_attention import (
        _bwd_small_fits_vmem, _use_small_path)

    assert _bwd_small_fits_vmem(12, 128, 128, 64)
    assert not _bwd_small_fits_vmem(12, 256, 256, 64)
    assert _bwd_small_fits_vmem(1, 256, 256, 64)  # single head fits

    # dispatch agreement: whatever the shape, the one predicate decides
    assert _use_small_path(12, 128, 128, 64, 256, 256)
    assert not _use_small_path(12, 256, 256, 64, 256, 256)
    assert not _use_small_path(12, 384, 384, 64, 128, 128)  # > block

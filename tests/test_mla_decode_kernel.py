"""The absorbed latent-attention decode kernel (ops/pallas/mla_decode.py)
in interpret mode against `CachedLatentAttention.absorbed`'s XLA path
(`nn.gqa.attend_keys` under `generation.cache.decode_mask`) at small
widths: every per-slot length around a block's and the ring's edges in
one batch, the wrapped ring, dead rows that hold anything, both ring
dtypes, and the function that rounds live rows to fetched rows against
the grid's own count of live blocks.

Tolerances: float32 differs in the order of the sums only (blocks,
running maximum): 2e-6 on outputs of order 1. bfloat16 rounds the
probabilities and the output once each on both paths: 2 ulp of an
output of order 1 (2^-7)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.generation import cache as gcache
from paddle_tpu.nn import mla
from paddle_tpu.nn.gqa import attend_keys

kd = importlib.import_module("paddle_tpu.ops.pallas.mla_decode")

HEADS, WIDTH, RING, BLOCK, SCALE = 4, 24, 64, 16, 0.3
# a slot each: the first row alone, one short of a block, a block, one
# over, one short of the ring, the ring, and twice wrapped
POSITIONS = (0, BLOCK - 2, BLOCK - 1, BLOCK, RING - 2, RING - 1,
             2 * RING + 5)
TOL = {"float32": 2e-6, "bfloat16": 2 ** -6}


def _inputs(dtype, seed=0):
    rng = np.random.default_rng(seed)
    b = len(POSITIONS)
    q = jnp.asarray(rng.standard_normal((b, HEADS, WIDTH)), dtype)
    ring = jnp.asarray(rng.standard_normal((b, RING, WIDTH)), dtype)
    return q, ring, jnp.asarray(POSITIONS, jnp.int32)


def _xla(q, ring, pos):
    mask = gcache.decode_mask(pos, ring.shape[1])
    return attend_keys(q[:, None, :, None], ring[:, None], ring[:, None],
                       mask[:, :, None], SCALE)[:, 0, :, 0]


def _kernel(q, ring, pos, block=BLOCK):
    return kd.mla_decode(q, jnp.swapaxes(ring, 1, 2),
                         jnp.minimum(pos + 1, ring.shape[1]), SCALE,
                         block=block, interpret=True)


def _f32(a):
    return np.asarray(a, np.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("slot", range(len(POSITIONS)))
def test_kernel_equals_the_xla_path_at_every_length(dtype, slot):
    """All lengths run mixed in ONE batch (a grid of 7 slots x 4
    blocks); each case looks at its own slot."""
    q, ring, pos = _inputs(dtype)
    got, want = _f32(_kernel(q, ring, pos)), _f32(_xla(q, ring, pos))
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.abs(got[slot] - want[slot]).max() <= TOL[dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("poison", [np.inf, np.nan])
def test_dead_rows_are_never_read(dtype, poison):
    """Rows at and above a slot's length hold inf / nan: a dead block is
    not fetched and the last live block's tail is masked in both
    products, so the result is finite and bit-equal to the clean
    ring's (XLA's path gives 0 x inf = nan there)."""
    q, ring, pos = _inputs(dtype, seed=1)
    length = np.minimum(np.asarray(POSITIONS) + 1, RING)
    dead = np.arange(RING)[None, :, None] >= length[:, None, None]
    assert dead.any(axis=(1, 2)).sum() == 5     # two slots are full
    dirty = jnp.where(dead, jnp.asarray(poison, ring.dtype), ring)
    got = _f32(_kernel(q, dirty, pos))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, _f32(_kernel(q, ring, pos)))
    assert not np.isfinite(_f32(_xla(q, dirty, pos))[:5]).all()


@pytest.mark.parametrize("block", [16, 32, 64])
def test_any_block_gives_the_same_attention(block):
    q, ring, pos = _inputs("float32", seed=2)
    assert np.abs(_f32(_kernel(q, ring, pos, block))
                  - _f32(_xla(q, ring, pos))).max() <= TOL["float32"]


@pytest.mark.parametrize("block", [16, 32, 64])
def test_rows_fetched_is_the_grid_s_own_count_of_live_blocks(block):
    """The blocks whose body runs, counted by the kernel's own predicate
    (a block is live where its first key lies below the length), are
    `live_blocks`; the clamp never points past them."""
    for length in range(1, RING + 1):
        ran = sum(j * block < length for j in range(RING // block))
        assert kd.live_blocks(length, block) == ran
        assert kd.rows_fetched(length, block) == ran * block >= length
    lengths = np.arange(1, RING + 1)
    np.testing.assert_array_equal(
        kd.rows_fetched(lengths, block),
        np.asarray(kd.rows_fetched(jnp.asarray(lengths), block)))


def test_supported_asks_for_whole_blocks_and_an_unaligned_width():
    ok = kd.mla_decode_supported
    assert ok((32, 8192, 576), "bfloat16")       # the served cell's ring
    assert ok((2, 32, 24), "float32")            # the tests' toy model
    assert kd.key_block(8192) == 512 and kd.key_block(32) == 32
    assert not ok((32, 8192, 512), "bfloat16")   # rows kept minor: a copy
    assert not ok((32, 8192 + 256, 576), "bfloat16")   # a ragged block
    assert not ok((32, 8192, 576), "int8")
    assert not ok((32, 8, 8192, 128), "bfloat16")      # a K/V ring


def test_the_layer_takes_the_kernel_where_it_may(monkeypatch):
    """`absorbed` with the step's `pos`: the kernel where
    `decode_key_block` says so (forced here, interpret off the TPU), the
    same numbers as XLA's path, one counter bump an attention."""
    from paddle_tpu import profiler

    m = mla.CachedLatentAttention(
        hidden_size=32, num_heads=4, q_rank=16, kv_rank=12, nope_dim=8,
        rope_dim=4, v_dim=6, initializer_range=0.3)
    rng = np.random.default_rng(3)
    q_nope = jnp.asarray(rng.standard_normal((3, 1, 4, 8)), jnp.float32)
    q_rot = jnp.asarray(rng.standard_normal((3, 1, 4, 4)), jnp.float32)
    ring = jnp.asarray(rng.standard_normal((3, 32, 16)), jnp.float32)
    pos = jnp.asarray([0, 17, 40], jnp.int32)
    mask = gcache.decode_mask(pos, 32)
    assert mla.decode_key_block(ring.shape, ring.dtype) is None   # a CPU
    before = profiler.counters()
    want = m.absorbed(q_nope, q_rot, ring, mask, pos)
    monkeypatch.setattr(mla, "can_emit_mosaic", lambda: True)
    assert mla.decode_key_block(ring.shape, ring.dtype) == 32
    got = m.absorbed(q_nope, q_rot, ring, mask, pos)
    no_pos = m.absorbed(q_nope, q_rot, ring, mask)
    after = profiler.counters()
    assert np.abs(_f32(got) - _f32(want)).max() <= 2e-6
    np.testing.assert_array_equal(_f32(no_pos), _f32(want))
    grown = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("mla::absorbed_kernel", "mla::absorbed_xla")}
    assert grown == {"mla::absorbed_kernel": 1, "mla::absorbed_xla": 2}

"""Flash attention (TPU pallas kernel) with in-kernel dropout + backward.

Reference parity: operators/fused/multihead_matmul_op.cu fuses BERT
attention into one CUDA kernel; this is the TPU equivalent with the
flash-attention online-softmax construction so the [L, L] score matrix
never materializes in HBM — only [BQ, BK] tiles live in VMEM.

Design (per /opt/skills/guides/pallas_guide.md):
- grid = (B*H, L/BQ): one program per query tile per head.
- K/V for the head stay as VMEM blocks; the kernel walks K-tiles with a
  fori_loop, keeping running max m, denominator l, and an f32 accumulator
  in VMEM scratch (MXU matmuls via jnp.dot with
  preferred_element_type=f32).
- causal masking prunes fully-masked K-tiles by bounding the loop.
- dropout runs INSIDE the kernel via the per-core TPU PRNG: each
  (bh, q-tile, k-tile) re-seeds with pltpu.prng_seed(seed, bh, qi, ki)
  so forward and backward regenerate bit-identical masks in any grid
  order — no [B, H, L, L] mask ever touches HBM.
- backward: two pallas kernels (dQ over q-tiles; dK/dV over k-tiles)
  using the saved per-row logsumexp, recomputing probability tiles on
  the fly (standard FlashAttention backward).
- bias gradient: exact on the jnp fallback path and on the pallas path
  with dropout == 0. On the pallas path with dropout > 0 the bias is
  treated as NON-TRAINABLE (gradient is zeros) — attention masks in
  every reference model derive from integer inputs and carry no
  gradient; use dropout=0.0 for a trainable attention bias.

Falls back to a pure-jnp path off-TPU (CPU tests) and for dtypes/shapes
the kernel does not support; the fallback implements dropout from the
same integer seed via jax.random, so its recompute backward sees the
same mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..._internal_tuning import register_schedule, resolve_schedule
from ._platform import can_emit_mosaic, on_tpu_platform

__all__ = ["flash_attention"]

_NEG_INF = -1e30
_BLOCK = 256  # default q/k tile (the historical hardcoded geometry)


def _schedule_blocks(b, h, lq, lk, d, dtype) -> tuple:
    """(block_q, block_k, unroll) through the autotuner; the default
    point is the historical (256, 256, unroll=1) — byte-identical when
    untuned. ``_effective_blocks`` still applies downstream, so a tuned
    block that does not divide the sequence degrades to the 128 base
    tile exactly as the defaults always have."""
    params = resolve_schedule("flash_attention", b=int(b), h=int(h),
                              lq=int(lq), lk=int(lk), d=int(d),
                              dtype=str(dtype))
    return (int(params["block_q"]), int(params["block_k"]),
            max(1, int(params.get("unroll", 1))))


def _flash_vmem_ok(info, c) -> bool:
    # per-program residents (tiled fwd): q/o tiles [BQ, D] + whole-head
    # K/V [LK, D] (2 bytes each at bf16-min) + the f32 [BQ, BK] score
    # tile; keep under ~12 MB of the 16 MB core budget
    d, lk = int(info["d"]), int(info["lk"])
    tiles = 2 * (2 * c["block_q"] * d + 2 * lk * d)
    score = 4 * c["block_q"] * c["block_k"]
    return (c["block_q"] % 128 == 0 and c["block_k"] % 128 == 0
            and c.get("unroll", 1) in (1, 2, 4)
            and tiles + score <= 12 * (1 << 20))


def _tuning_bench(info):
    b, h = int(info["b"]), int(info["h"])
    lq, lk, d = int(info["lq"]), int(info["lk"]), int(info["d"])
    dtype = str(info.get("dtype", "float32"))
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, lq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (b, h, lk, d), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (b, h, lk, d), jnp.float32).astype(dtype)
    scale = float(d) ** -0.5

    def builder(params):
        bq, bk = int(params["block_q"]), int(params["block_k"])
        unroll = max(1, int(params.get("unroll", 1)))
        fn = jax.jit(lambda q, k, v: _pallas_fwd(
            q, k, v, None, jnp.int32(0), True, scale, 0.0,
            block_q=bq, block_k=bk, unroll=unroll)[0])

        def run():
            jax.block_until_ready(fn(q, k, v))

        return run

    return builder


register_schedule(
    name="flash_attention",
    version=1,
    params={"block_q": (128, 256, 512),
            "block_k": (128, 256, 512),
            "unroll": (1, 2)},
    default=lambda info: {"block_q": _BLOCK, "block_k": _BLOCK,
                          "unroll": 1},
    supported=_flash_vmem_ok,
    bench=_tuning_bench,
)


def _drop_threshold(rate: float) -> jnp.ndarray:
    """uint32 cutoff: drop where random bits < rate * 2**32."""
    return jnp.uint32(min(int(rate * 2**32), 2**32 - 1))


def _seed_tile(pltpu, seed_ref, bh, qi, ki, num_q, num_k):
    """Re-seed the per-core PRNG for one (bh, qi, ki) tile. The TPU
    accepts at most two seed values, so the tile coordinates fold into
    one unique int32; fwd and both bwd kernels call this with the same
    arguments, giving bit-identical masks in any grid order."""
    tile_id = (bh * num_q + qi) * num_k + ki
    pltpu.prng_seed(seed_ref[0], tile_id)


def _plain_attention(q, k, v, bias, causal, scale, rate=0.0, seed=None):
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        lq, lk = scores.shape[-2], scores.shape[-1]
        iq = jnp.arange(lq)[:, None] + (lk - lq)
        ik = jnp.arange(lk)[None, :]
        scores = jnp.where(iq >= ik, scores, _NEG_INF)
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    w = jax.nn.softmax(scores, axis=-1)
    if rate > 0.0:
        # mask derived deterministically from the integer seed so the
        # recompute-based backward regenerates the identical mask
        key = jax.random.PRNGKey(seed)
        keep = jax.random.bernoulli(key, 1.0 - rate, w.shape)
        w = jnp.where(keep, w / (1.0 - rate), 0.0)
    w = w.astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# -- forward kernel -----------------------------------------------------------


def _fwd_core(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref, lse_ref, *,
              scale, causal, block_k, seq_k, num_q, rate, unroll=1):
    """One (batch*head, q-tile) program.
      q_ref: [1, BQ, D]; k_ref/v_ref: [1, LK, D]; bias_ref: [1, 1, BQ, LK]
      seed_ref: [1] int32 (SMEM); o_ref: [1, BQ, D]; lse_ref: [1, BQ, 1]
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q = q_ref[0]                                      # [BQ, D] native dtype
    bq = q.shape[0]
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    q_start = qi * bq

    num_k = seq_k // block_k
    if causal:
        # K-tiles strictly after this Q-tile's last row are fully masked
        num_k_live = jnp.minimum(
            num_k, (q_start + bq + block_k - 1) // block_k
        )
    else:
        num_k_live = num_k

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, q.shape[1]), jnp.float32)
    if rate > 0.0:
        thr = _drop_threshold(rate)
        inv_keep = 1.0 / (1.0 - rate)

    def body(ki, carry):
        m, l, acc = carry
        k_start = ki * block_k
        kt = k_ref[0, pl.ds(k_start, block_k), :]
        vt = v_ref[0, pl.ds(k_start, block_k), :]
        # native-dtype (bf16 under AMP) MXU matmul with f32 accumulate
        s = jnp.dot(q, kt.T, preferred_element_type=jnp.float32) * scale
        if causal:
            iq = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            ik = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(iq >= ik, s, _NEG_INF)
        if bias_ref is not None:
            s = s + bias_ref[0, 0, :, pl.ds(k_start, block_k)].astype(
                jnp.float32
            )
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        # the softmax denominator uses the UNdropped p; dropout scales the
        # normalized weights, which distributes onto the accumulator only
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        if rate > 0.0:
            _seed_tile(pltpu, seed_ref, bh, qi, ki, num_q, num_k)
            bits = pltpu.bitcast(
                pltpu.prng_random_bits(p.shape), jnp.uint32
            )
            p_acc = jnp.where(bits >= thr, p * inv_keep, 0.0)
        else:
            p_acc = p
        acc_new = acc * corr + jnp.dot(
            p_acc.astype(vt.dtype), vt, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_k_live, body, (m0, l0, acc0),
                                  unroll=unroll)
    lsafe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / lsafe).astype(o_ref.dtype)
    # per-row logsumexp for the backward recompute
    lse_ref[0] = m + jnp.log(lsafe)


def _bdot(a, b_arr, ta=False, tb=True):
    """Batched head matmul [H, M, K] x [H, N, K]^T -> [H, M, N] (f32
    accumulate). One dot_general over all heads: Mosaic pipelines the
    per-head MXU passes without fori_loop serialization."""
    ca = 1 if ta else 2
    cb = 2 if tb else 1
    return jax.lax.dot_general(
        a, b_arr, (((ca,), (cb,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )


def _fwd_small_core(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref,
                    lse_ref, *, scale, causal, num_heads, rate):
    """Short-sequence forward: the whole sequence fits one tile, so one
    program per BATCH item computes all heads at once with batched
    dot_generals — 12x fewer programs than the (b*h, q-tile) grid, big
    vectorized VPU ops, and the [L, L] bias is DMA'd once per batch.
    Kernel-launch/DMA overhead dominates this regime, not VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bi = pl.program_id(0)
    q = q_ref[0]                                      # [H, LQ, D]
    kt = k_ref[0]                                     # [H, LK, D]
    vt = v_ref[0]                                     # [H, LK, D]
    s = _bdot(q, kt) * scale                          # [H, LQ, LK] f32
    if causal:
        iq = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ik = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(iq >= ik, s, _NEG_INF)
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)       # [1|H, LQ, LK]
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    lsafe = jnp.where(l == 0.0, 1.0, l)
    if rate > 0.0:
        thr = _drop_threshold(rate)
        inv_keep = 1.0 / (1.0 - rate)
        # one draw covers all heads: tile_id folds (bi, h=0..H) into the
        # same id space as the (b*h)-grid kernels' single-tile case
        _seed_tile(pltpu, seed_ref, bi * num_heads, 0, 0, 1, 1)
        bits = pltpu.bitcast(pltpu.prng_random_bits(p.shape), jnp.uint32)
        p_acc = jnp.where(bits >= thr, p * inv_keep, 0.0)
    else:
        p_acc = p
    o = _bdot(p_acc.astype(vt.dtype), vt, tb=False)   # [H, LQ, D]
    o_ref[0] = (o / lsafe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(lsafe)


def _bwd_small_core(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    bias_ref, seed_ref, dq_ref, dk_ref, dv_ref, *, scale,
                    causal, num_heads, rate):
    """Short-sequence backward companion of _fwd_small_core: one program
    per batch item, all heads batched, dQ/dK/dV in one pass. Regenerates
    the forward's dropout mask (same seed tile id, same [H, LQ, LK]
    draw)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bi = pl.program_id(0)
    q = q_ref[0]                                      # [H, LQ, D]
    kt = k_ref[0]
    vt = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]                                  # [H, LQ, 1]
    delta = delta_ref[0]
    s = _bdot(q, kt) * scale                          # [H, LQ, LK]
    if causal:
        iq = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ik = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(iq >= ik, s, _NEG_INF)
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)
    p = jnp.exp(s - lse)
    dpd = _bdot(do, vt)                               # [H, LQ, LK]
    if rate > 0.0:
        thr = _drop_threshold(rate)
        inv_keep = 1.0 / (1.0 - rate)
        _seed_tile(pltpu, seed_ref, bi * num_heads, 0, 0, 1, 1)
        bits = pltpu.bitcast(pltpu.prng_random_bits(p.shape), jnp.uint32)
        keep = bits >= thr
        p_v = jnp.where(keep, p * inv_keep, 0.0)
        dp = jnp.where(keep, dpd * inv_keep, 0.0)
    else:
        p_v = p
        dp = dpd
    dv_ref[0] = _bdot(
        p_v.astype(do.dtype), do, ta=True, tb=False
    ).astype(dv_ref.dtype)
    ds = p * (dp - delta)
    dq_ref[0] = (_bdot(ds.astype(kt.dtype), kt, tb=False) * scale
                 ).astype(dq_ref.dtype)
    dk_ref[0] = (_bdot(ds.astype(q.dtype), q, ta=True, tb=False) * scale
                 ).astype(dk_ref.dtype)


def _small_bias_arg(bias, b, h, lq, lk, pl, pltpu):
    if bias.shape[1] == 1:
        arr = jnp.broadcast_to(bias, (b, 1, lq, lk))
        spec = pl.BlockSpec((1, 1, lq, lk), lambda bi: (bi, 0, 0, 0),
                            memory_space=pltpu.VMEM)
    else:
        arr = bias
        spec = pl.BlockSpec((1, h, lq, lk), lambda bi: (bi, 0, 0, 0),
                            memory_space=pltpu.VMEM)
    return arr, spec


def _pallas_fwd_small(q, k, v, bias, seed, causal, scale, rate):
    """Whole-sequence-per-tile forward over grid (b,)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    lk = k.shape[2]
    has_bias = bias is not None
    has_drop = rate > 0.0
    tile = lambda l: pl.BlockSpec((1, h, l, d), lambda bi: (bi, 0, 0, 0),
                                  memory_space=pltpu.VMEM)
    specs = [tile(lq), tile(lk), tile(lk)]
    args = [q, k, v]
    if has_bias:
        arr, spec = _small_bias_arg(bias, b, h, lq, lk, pl, pltpu)
        specs.append(spec)
        args.append(arr)
    if has_drop:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray(seed, jnp.int32).reshape(1))

    def kernel(*refs):
        n_in = 3 + (1 if has_bias else 0) + (1 if has_drop else 0)
        ins, outs = list(refs[:n_in]), refs[n_in:]
        i = 3
        bias_ref = ins[i] if has_bias else None
        i += 1 if has_bias else 0
        seed_ref = ins[i] if has_drop else None
        return _fwd_small_core(ins[0], ins[1], ins[2], bias_ref, seed_ref,
                               *outs, scale=scale, causal=causal,
                               num_heads=h, rate=rate)

    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd_small",
        grid=(b,),
        in_specs=specs,
        out_specs=[
            tile(lq),
            pl.BlockSpec((1, h, lq, 1), lambda bi: (bi, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, lq, 1), jnp.float32),
        ],
    )(*args)
    return out, lse


def _bwd_small_fits_vmem(h, lq, lk, d, budget=6 << 20):
    """The one-pass backward holds ALL heads of one batch item in VMEM:
    7 bf16 [h,l,d] operand/result tiles plus 3 f32 [h,lq,lk] score-sized
    intermediates. The compiler's scoped-vmem stack roughly doubles the
    estimate (in/out buffering), so gate at ~6 MB against the 16 MB core
    limit — at h=12,d=64 this admits L=128 (3.7 MB) and correctly sends
    L>=256 (12+ MB, observed 18.5 MB scoped OOM) to the tiled kernels."""
    tiles = 7 * h * max(lq, lk) * d * 2
    scores = 3 * h * lq * lk * 4
    return tiles + scores <= budget


def _pallas_bwd_small(q, k, v, bias, seed, causal, scale, rate, lse, g,
                      delta):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    lk = k.shape[2]
    has_bias = bias is not None
    has_drop = rate > 0.0
    tile = lambda l: pl.BlockSpec((1, h, l, d), lambda bi: (bi, 0, 0, 0),
                                  memory_space=pltpu.VMEM)
    col = pl.BlockSpec((1, h, lq, 1), lambda bi: (bi, 0, 0, 0),
                       memory_space=pltpu.VMEM)
    specs = [tile(lq), tile(lk), tile(lk), tile(lq), col, col]
    args = [q, k, v, g, lse, delta]
    if has_bias:
        arr, spec = _small_bias_arg(bias, b, h, lq, lk, pl, pltpu)
        specs.append(spec)
        args.append(arr)
    if has_drop:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray(seed, jnp.int32).reshape(1))

    def kernel(*refs):
        n_in = 6 + (1 if has_bias else 0) + (1 if has_drop else 0)
        ins, outs = list(refs[:n_in]), refs[n_in:]
        i = 6
        bias_ref = ins[i] if has_bias else None
        i += 1 if has_bias else 0
        seed_ref = ins[i] if has_drop else None
        return _bwd_small_core(*ins[:6], bias_ref, seed_ref, *outs,
                               scale=scale, causal=causal, num_heads=h,
                               rate=rate)

    dq, dk, dv = pl.pallas_call(
        kernel,
        name="flash_bwd_small",
        grid=(b,),
        in_specs=specs,
        out_specs=[tile(lq), tile(lk), tile(lk)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, lk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, lk, d), v.dtype),
        ],
    )(*args)
    return dq, dk, dv


def _adapt(core, has_bias, has_drop, **kw):
    """Bind a kernel core whose optional refs may be absent."""

    def kernel(*refs):
        n_in = 3 + (1 if has_bias else 0) + (1 if has_drop else 0)
        ins = list(refs[:n_in])
        outs = refs[n_in:]
        i = 3
        bias_ref = ins[i] if has_bias else None
        i += 1 if has_bias else 0
        seed_ref = ins[i] if has_drop else None
        return core(ins[0], ins[1], ins[2], bias_ref, seed_ref, *outs, **kw)

    return kernel


def _bias_spec(bias, b, h, lq, lk, block_q, pl, pltpu):
    """BlockSpec + reshaped operand for bias [B, 1|H, LQ, LK] -> per
    (bh, qi) tile [1, 1, BQ, LK]."""
    if bias.shape[1] == 1:
        arr = jnp.broadcast_to(bias, (b, 1, lq, lk))
        spec = pl.BlockSpec(
            (1, 1, block_q, lk), lambda bh, qi: (bh // h, 0, qi, 0),
            memory_space=pltpu.VMEM,
        )
    else:
        arr = bias.reshape(b * h, 1, lq, lk)
        spec = pl.BlockSpec(
            (1, 1, block_q, lk), lambda bh, qi: (bh, 0, qi, 0),
            memory_space=pltpu.VMEM,
        )
    return arr, spec


def _effective_blocks(lq, lk, block_q, block_k):
    """Tile sizes the kernels actually use. The grids FLOOR-divide seq
    by block, so a 128-multiple that is not a block multiple (L=384,
    640, ...) must shrink to the 128 base tile or its tail rows are
    silently dropped (_supported gates on L % 128 == 0)."""
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    if lq % block_q:
        block_q = 128
    if lk % block_k:
        block_k = 128
    return block_q, block_k


def _use_small_path(h, lq, lk, d, block_q, block_k):
    """One dispatch predicate for BOTH forward and backward small
    kernels. They must agree whenever dropout is on: the small kernels
    seed the PRNG per batch item while the tiled ones re-seed per head,
    so a small-forward/tiled-backward split would regenerate a DIFFERENT
    mask for every head but the first — silently wrong gradients."""
    # the backward's VMEM bound gates BOTH directions: with dropout the
    # masks must pair, and without it the small backward would still OOM
    # scoped VMEM at shapes the forward alone could handle
    return (lq <= block_q and lk <= block_k
            and _bwd_small_fits_vmem(h, lq, lk, d))


def _pallas_fwd(q, k, v, bias, seed, causal, scale, rate,
                block_q=256, block_k=256, unroll=1):
    """Returns (out, lse): lse is the per-row logsumexp [B*H, LQ], f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    lk = k.shape[2]
    block_q, block_k = _effective_blocks(lq, lk, block_q, block_k)
    if _use_small_path(h, lq, lk, d, block_q, block_k):
        out, lse = _pallas_fwd_small(q, k, v, bias, seed, causal, scale,
                                     rate)
        return out, lse.reshape(b * h, lq, 1)
    qf = q.reshape(b * h, lq, d)
    kf = k.reshape(b * h, lk, d)
    vf = v.reshape(b * h, lk, d)
    grid = (b * h, lq // block_q)
    has_bias = bias is not None
    has_drop = rate > 0.0

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, lk, d), lambda bh, qi: (bh, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, lk, d), lambda bh, qi: (bh, 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [qf, kf, vf]
    if has_bias:
        arr, spec = _bias_spec(bias, b, h, lq, lk, block_q, pl, pltpu)
        in_specs.append(spec)
        args.append(arr)
    if has_drop:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray(seed, jnp.int32).reshape(1))

    kernel = _adapt(_fwd_core, has_bias, has_drop, scale=scale,
                    causal=causal, block_k=block_k, seq_k=lk,
                    num_q=lq // block_q, rate=rate, unroll=unroll)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, lq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, lq, 1), jnp.float32),
        ],
    )(*args)
    return out.reshape(b, h, lq, d), lse


# -- backward kernels ---------------------------------------------------------


def _dq_core(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
             seed_ref, dq_ref, *, scale, causal, block_k, seq_k, num_q,
             rate, unroll=1):
    """dQ program per (bh, q-tile): walk K-tiles, recompute P from the
    saved logsumexp, regenerate the identical dropout mask per tile."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q = q_ref[0]                                      # [BQ, D]
    do = do_ref[0]                                    # [BQ, D]
    lse = lse_ref[0]                                  # [BQ, 1]
    delta = delta_ref[0]                              # [BQ, 1]
    bq = q.shape[0]
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    q_start = qi * bq

    num_k = seq_k // block_k
    if causal:
        num_k_live = jnp.minimum(
            num_k, (q_start + bq + block_k - 1) // block_k
        )
    else:
        num_k_live = num_k
    if rate > 0.0:
        thr = _drop_threshold(rate)
        inv_keep = 1.0 / (1.0 - rate)

    def body(ki, dq_acc):
        k_start = ki * block_k
        kt = k_ref[0, pl.ds(k_start, block_k), :]
        vt = v_ref[0, pl.ds(k_start, block_k), :]
        s = jnp.dot(q, kt.T, preferred_element_type=jnp.float32) * scale
        if causal:
            iq = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            ik = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(iq >= ik, s, _NEG_INF)
        if bias_ref is not None:
            s = s + bias_ref[0, 0, :, pl.ds(k_start, block_k)].astype(
                jnp.float32
            )
        p = jnp.exp(s - lse)                           # normalized probs
        dpd = jnp.dot(do, vt.T, preferred_element_type=jnp.float32)
        if rate > 0.0:
            _seed_tile(pltpu, seed_ref, bh, qi, ki, num_k=num_k,
                       num_q=num_q)
            bits = pltpu.bitcast(
                pltpu.prng_random_bits(p.shape), jnp.uint32
            )
            dp = jnp.where(bits >= thr, dpd * inv_keep, 0.0)
        else:
            dp = dpd
        ds = p * (dp - delta)                          # [BQ, BK]
        return dq_acc + jnp.dot(
            ds.astype(kt.dtype), kt, preferred_element_type=jnp.float32
        )

    dq0 = jnp.zeros((bq, q.shape[1]), jnp.float32)
    dq = jax.lax.fori_loop(0, num_k_live, body, dq0, unroll=unroll)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_core(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
              seed_ref, dk_ref, dv_ref, *, scale, causal, block_q, seq_q,
              num_k, rate, unroll=1):
    """dK/dV program per (bh, k-tile): walk Q-tiles. The dropout re-seed
    uses the same (seed, bh, qi, ki) tuple as the forward, so the mask
    for each (qi, ki) tile is bit-identical despite the transposed
    iteration order."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kt = k_ref[0]                                     # [BK, D]
    vt = v_ref[0]                                     # [BK, D]
    bk = kt.shape[0]
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    k_start = ki * bk

    num_q = seq_q // block_q
    # causal: Q-tiles entirely above this K-tile see none of it
    qi_start = (k_start // block_q) if causal else 0
    if rate > 0.0:
        thr = _drop_threshold(rate)
        inv_keep = 1.0 / (1.0 - rate)

    def body(qi, carry):
        dk_acc, dv_acc = carry
        q_start = qi * block_q
        qt = q_ref[0, pl.ds(q_start, block_q), :]
        do = do_ref[0, pl.ds(q_start, block_q), :]
        lse = lse_ref[0, pl.ds(q_start, block_q), :]
        delta = delta_ref[0, pl.ds(q_start, block_q), :]
        s = jnp.dot(qt, kt.T, preferred_element_type=jnp.float32) * scale
        if causal:
            iq = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            ik = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(iq >= ik, s, _NEG_INF)
        if bias_ref is not None:
            s = s + bias_ref[0, 0, pl.ds(q_start, block_q), :].astype(
                jnp.float32
            )
        p = jnp.exp(s - lse)                           # [BQ, BK]
        dpd = jnp.dot(do, vt.T, preferred_element_type=jnp.float32)
        if rate > 0.0:
            _seed_tile(pltpu, seed_ref, bh, qi, ki, num_q, num_k)
            bits = pltpu.bitcast(
                pltpu.prng_random_bits(p.shape), jnp.uint32
            )
            keep = bits >= thr
            p_v = jnp.where(keep, p * inv_keep, 0.0)
            dp = jnp.where(keep, dpd * inv_keep, 0.0)
        else:
            p_v = p
            dp = dpd
        dv_new = dv_acc + jnp.dot(
            p_v.T.astype(do.dtype), do, preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dk_new = dk_acc + jnp.dot(
            ds.T.astype(qt.dtype), qt, preferred_element_type=jnp.float32
        )
        return dk_new, dv_new

    dk0 = jnp.zeros((bk, kt.shape[1]), jnp.float32)
    dv0 = jnp.zeros((bk, vt.shape[1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(qi_start, num_q, body, (dk0, dv0),
                               unroll=unroll)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _pallas_bwd(q, k, v, bias, seed, causal, scale, rate, out, lse, g,
                block_q=256, block_k=256, unroll=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    lk = k.shape[2]
    block_q, block_k = _effective_blocks(lq, lk, block_q, block_k)
    qf = q.reshape(b * h, lq, d)
    kf = k.reshape(b * h, lk, d)
    vf = v.reshape(b * h, lk, d)
    gf = g.reshape(b * h, lq, d)
    # D_i = rowsum(dO * O): cheap, fuses into the surrounding XLA program
    delta = jnp.sum(
        gf.astype(jnp.float32) * out.reshape(b * h, lq, d).astype(jnp.float32),
        axis=-1, keepdims=True,
    )  # [B*H, LQ, 1]
    if _use_small_path(h, lq, lk, d, block_q, block_k):
        # short-sequence regime: one program per batch item (all heads)
        # beats two tiled passes (launch + DMA overhead dominates there);
        # the predicate is SHARED with the forward so dropout seeding
        # schemes always pair
        return _pallas_bwd_small(
            q, k, v, bias, seed, causal, scale, rate,
            lse.reshape(b, h, lq, 1), g, delta.reshape(b, h, lq, 1))
    has_bias = bias is not None
    has_drop = rate > 0.0

    whole = lambda l: pl.BlockSpec((1, l, d), lambda bh, i: (bh, 0, 0),
                                   memory_space=pltpu.VMEM)
    row = lambda blk: pl.BlockSpec((1, blk, 1), lambda bh, i: (bh, i, 0),
                                   memory_space=pltpu.VMEM)
    whole_row = lambda l: pl.BlockSpec((1, l, 1), lambda bh, i: (bh, 0, 0),
                                       memory_space=pltpu.VMEM)

    # -- dQ: grid over q-tiles
    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0),
                     memory_space=pltpu.VMEM),
        whole(lk), whole(lk),
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0),
                     memory_space=pltpu.VMEM),
        row(block_q), row(block_q),
    ]
    dq_args = [qf, kf, vf, gf, lse, delta]
    if has_bias:
        arr, spec = _bias_spec(bias, b, h, lq, lk, block_q, pl, pltpu)
        dq_specs.append(spec)
        dq_args.append(arr)
    if has_drop:
        dq_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dq_args.append(jnp.asarray(seed, jnp.int32).reshape(1))

    def dq_kernel(*refs):
        n_in = 6 + (1 if has_bias else 0) + (1 if has_drop else 0)
        ins, outs = list(refs[:n_in]), refs[n_in:]
        i = 6
        bias_ref = ins[i] if has_bias else None
        i += 1 if has_bias else 0
        seed_ref = ins[i] if has_drop else None
        return _dq_core(*ins[:6], bias_ref, seed_ref, *outs, scale=scale,
                        causal=causal, block_k=block_k, seq_k=lk,
                        num_q=lq // block_q, rate=rate, unroll=unroll)

    dq = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        grid=(b * h, lq // block_q),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b * h, lq, d), q.dtype),
    )(*dq_args)

    # -- dK/dV: grid over k-tiles
    dkv_specs = [
        whole(lq),
        pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0),
                     memory_space=pltpu.VMEM),
        whole(lq), whole_row(lq), whole_row(lq),
    ]
    dkv_args = [qf, kf, vf, gf, lse, delta]
    if has_bias:
        # column-slice of the bias per k-tile: [1, 1, LQ, BK]
        if bias.shape[1] == 1:
            arr = jnp.broadcast_to(bias, (b, 1, lq, lk))
            spec = pl.BlockSpec(
                (1, 1, lq, block_k), lambda bh, ki: (bh // h, 0, 0, ki),
                memory_space=pltpu.VMEM,
            )
        else:
            arr = bias.reshape(b * h, 1, lq, lk)
            spec = pl.BlockSpec(
                (1, 1, lq, block_k), lambda bh, ki: (bh, 0, 0, ki),
                memory_space=pltpu.VMEM,
            )
        dkv_specs.append(spec)
        dkv_args.append(arr)
    if has_drop:
        dkv_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dkv_args.append(jnp.asarray(seed, jnp.int32).reshape(1))

    def dkv_kernel(*refs):
        n_in = 6 + (1 if has_bias else 0) + (1 if has_drop else 0)
        ins, outs = list(refs[:n_in]), refs[n_in:]
        i = 6
        bias_ref = ins[i] if has_bias else None
        i += 1 if has_bias else 0
        seed_ref = ins[i] if has_drop else None
        return _dkv_core(*ins[:6], bias_ref, seed_ref, *outs, scale=scale,
                         causal=causal, block_q=block_q, seq_q=lq,
                         num_k=lk // block_k, rate=rate, unroll=unroll)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        grid=(b * h, lk // block_k),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, lk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, lk, d), v.dtype),
        ],
    )(*dkv_args)
    shape4 = lambda a, l: a.reshape(b, h, l, d)
    return shape4(dq, lq), shape4(dk, lk), shape4(dv, lk)


# -- custom-vjp wiring --------------------------------------------------------


def _supported(q, k, v, bias):
    if not can_emit_mosaic():
        return False
    b, h, lq, d = q.shape
    lk = k.shape[2]
    if d % 128 != 0 and d not in (64,):  # lane dim should tile well
        if d % 8 != 0:
            return False
    if lq % 128 != 0 or lk % 128 != 0:
        return False
    return True


def _sched_for(q, k):
    b, h, lq, d = q.shape
    return _schedule_blocks(b, h, lq, k.shape[2], d, q.dtype)


# ``sched`` (block_q, block_k, unroll) is a NONDIFF STATIC argument,
# resolved ONCE by flash_attention() before the custom_vjp: forward and
# backward must tile identically — the dropout PRNG re-seeds per
# (q-tile, k-tile), so a background tuned swap-in landing between the
# eager forward and its deferred backward would otherwise regenerate
# different masks (silently wrong gradients).
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, seed, causal, scale, rate, bias_grad=True,
           sched=(_BLOCK, _BLOCK, 1), bias=None):
    if _supported(q, k, v, bias):
        bq, bk, unroll = sched
        out, _ = _pallas_fwd(q, k, v, bias, seed, causal, scale, rate,
                             block_q=bq, block_k=bk, unroll=unroll)
        return out
    return _plain_attention(q, k, v, bias, causal, scale, rate, seed)


def _flash_fwd(q, k, v, seed, causal, scale, rate, bias_grad=True,
               sched=(_BLOCK, _BLOCK, 1), bias=None):
    if _supported(q, k, v, bias):
        bq, bk, unroll = sched
        out, lse = _pallas_fwd(q, k, v, bias, seed, causal, scale, rate,
                               block_q=bq, block_k=bk, unroll=unroll)
        return out, (q, k, v, bias, seed, out, lse)
    out = _plain_attention(q, k, v, bias, causal, scale, rate, seed)
    return out, (q, k, v, bias, seed, None, None)


def _flash_bwd(causal, scale, rate, bias_grad, sched, res, g):
    q, k, v, bias, seed, out, lse = res
    dseed = np.zeros((), dtype=jax.dtypes.float0)
    if out is not None:  # pallas path
        bq, bk, unroll = sched  # the forward's exact tiling, statically
        dq, dk, dv = _pallas_bwd(
            q, k, v, bias, seed, causal, scale, rate, out, lse, g,
            block_q=bq, block_k=bk, unroll=unroll
        )
        if bias is None:
            return dq, dk, dv, dseed, None
        if not bias_grad or rate > 0.0:
            # bias_grad=False: caller declared the bias non-trainable
            # (eager attention masks) — zeros beat the recompute below,
            # which eager mode would otherwise execute just to discard.
            # rate>0: see module docstring — bias is non-trainable under
            # in-kernel dropout (jnp cannot reproduce the TPU PRNG mask)
            return dq, dk, dv, dseed, jnp.zeros_like(bias)
        # exact dbias via recompute (DCE'd by XLA when bias carries no
        # gradient, which is the case for every reference attention mask)
        def fwd(bias):
            return _plain_attention(q, k, v, bias, causal, scale)

        _, vjp = jax.vjp(fwd, bias)
        (dbias,) = vjp(g)
        return dq, dk, dv, dseed, dbias

    # fallback path: recompute with the same seed -> identical mask
    if bias is None:
        _, vjp = jax.vjp(
            lambda q, k, v: _plain_attention(
                q, k, v, None, causal, scale, rate, seed),
            q, k, v,
        )
        dq, dk, dv = vjp(g)
        return dq, dk, dv, dseed, None
    if not bias_grad:
        _, vjp = jax.vjp(
            lambda q, k, v: _plain_attention(
                q, k, v, bias, causal, scale, rate, seed),
            q, k, v,
        )
        dq, dk, dv = vjp(g)
        return dq, dk, dv, dseed, jnp.zeros_like(bias)

    _, vjp = jax.vjp(
        lambda q, k, v, b: _plain_attention(
            q, k, v, b, causal, scale, rate, seed),
        q, k, v, bias,
    )
    dq, dk, dv, dbias = vjp(g)
    return dq, dk, dv, dseed, dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, bias=None, causal=False, scale=None,
                    dropout_rate=0.0, dropout_key=None):
    """Fused attention over [B, H, L, D] operands.

    On TPU with tile-aligned shapes, runs the pallas flash kernel
    (forward AND backward; attention-probability dropout runs inside the
    kernel via the TPU PRNG). Otherwise falls back to the fused-by-XLA
    jnp path. Accepts Tensors or arrays; additive bias broadcastable to
    [B, H, LQ, LK].

    ``dropout_rate`` drops attention probabilities (upscale-in-train).
    ``dropout_key`` supplies the jax PRNG key; when None, the global
    generator (framework/random.py) is split — inside a compiled train
    step this is the functionalized per-step key, so masks differ per
    step.
    """
    from ...framework.tensor import Tensor

    unwrap = lambda t: t._array if isinstance(t, Tensor) else t
    wrap = isinstance(q, Tensor)
    qa, ka, va = unwrap(q), unwrap(k), unwrap(v)
    ba = unwrap(bias) if bias is not None else None
    if scale is None:
        scale = float(qa.shape[-1]) ** -0.5
    rate = float(dropout_rate)
    if rate > 0.0:
        if dropout_key is None:
            from ...framework import random as _random

            dropout_key = _random.split_key()
        seed = jax.random.bits(dropout_key, (), "uint32").astype(jnp.int32)
    else:
        seed = jnp.int32(0)

    # bias_grad=False when the bias is declared non-trainable: the eager
    # backward then returns cheap zeros instead of executing the exact
    # dbias recompute (which materializes [B, H, LQ, LK] scores) just to
    # discard it. Trainable biases require dropout_rate == 0 on the
    # pallas path (module docstring).
    bias_grad = not (isinstance(bias, Tensor) and bias.stop_gradient)
    if (bias is not None and bias_grad and rate > 0.0
            and isinstance(bias, Tensor)):
        raise ValueError(
            "flash_attention: a trainable bias (stop_gradient=False) "
            "cannot be combined with dropout_rate > 0 — the in-kernel "
            "TPU dropout mask is not reproducible for the bias gradient. "
            "Set bias.stop_gradient = True or use dropout_rate=0.0."
        )

    # resolve the schedule ONCE, here, so the custom_vjp's forward and
    # deferred backward share the exact same static tiling (a background
    # tuned swap-in between the two can then never split them); off-TPU
    # the kernels never run — skip resolution, keep the path tuner-free
    sched = _sched_for(qa, ka) if on_tpu_platform() else (_BLOCK, _BLOCK, 1)
    if wrap:
        from ...framework.autograd import apply_op

        tensors = [q, k, v] + ([bias] if bias is not None else [])
        tensors = [
            t if isinstance(t, Tensor) else Tensor._from_array(jnp.asarray(t))
            for t in tensors
        ]
        if bias is not None:
            fn = lambda q, k, v, b: _flash(q, k, v, seed, causal, scale,
                                           rate, bias_grad, sched, b)
        else:
            fn = lambda q, k, v: _flash(q, k, v, seed, causal, scale,
                                        rate, True, sched)
        return apply_op("flash_attention", fn, tensors, {})
    if ba is not None:
        return _flash(qa, ka, va, seed, causal, scale, rate, True, sched,
                      ba)
    return _flash(qa, ka, va, seed, causal, scale, rate, True, sched)

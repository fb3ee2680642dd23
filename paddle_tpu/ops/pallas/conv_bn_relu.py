"""Fused conv2d + batch_norm + relu (TPU pallas kernels, fwd + bwd).

The path takes POINTWISE convolutions only: kernel 1x1, stride 1, no
padding (in ResNet-50 each bottleneck block's first conv, sixteen a
forward). Only there is the conv's matmul form its own input: a move
of the channel axis and a reshape give ``x2 [N*H*W, Cin]``, and
``x2 @ w2 [Cin, Cout]`` runs as a tiled MXU matmul whose epilogue does
the batch-norm work in the same VMEM pass. A conv with a spatial extent
(KxK, strided, padded) would first need its im2col patch matrix
written, re-tiled, padded, kept for the backward pass and scattered
back: on the chip that traffic cost more than the whole of XLA's
convolution, which reads the input in place (PERF.md, Findings PR 32).
Such a conv takes the fallback below, flag on or off, forced or not.

- **eval**: ONE kernel computes ``relu((x2 @ w2) * scale + shift)``
  per [TM, TN] tile, so the pre-activation never exists in HBM. scale /
  shift fold gamma/beta with the running statistics.
- **training**: kernel 1 computes the matmul AND per-tile partial
  channel sums in the same pass; kernel 2 reduces the CENTERED
  sum-of-squares (two-pass variance: the one-pass E[x^2]-mean^2 form
  catastrophically cancels for large-mean channels, see
  ``_centered_sumsq_kernel``); kernel 3 is one elementwise
  normalize+relu pass.
- **backward (training)**: kernel B1 recomputes the relu gate from the
  saved conv output and emits per-tile partials of ``sum(dy)`` and
  ``sum(dy * co)`` (one pass); kernel B2 applies the folded BN
  backward ``d_co = k1*dy - k3*co - b0`` elementwise. The matmul
  gradients finish through ``jnp.dot`` (MXU via XLA): the same
  "kernels do the fused pointwise work, jnp finishes the reductions"
  discipline as layernorm_residual's dw/db.

The fallback (off-TPU, unadmitted shapes, every spatial conv) calls the
IDENTICAL registered op kernels (``conv2d`` -> ``batch_norm`` -> relu)
in the same order, so ``FLAGS_use_fused_conv_bn`` never changes
numerics off the pallas path: the same flag discipline as the PR-10
kernels.

Tile geometry (TM, TN) resolves through the kernel autotuner
(``tuning.resolve("conv_bn_relu", ...)``) with the historical 256/256
as the byte-identical default point.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..._internal_tuning import register_schedule, resolve_schedule
from ._platform import can_emit_mosaic, on_tpu_platform

__all__ = ["conv_bn_relu"]

_LANES = 128
_SUBLANES = {"float32": 8, "bfloat16": 16}
_TILE = 256  # default M/N tile (the schedule space's default point)


# -- schedule space -----------------------------------------------------------


def _schedule_tiles(mp, kp, cp, dtype) -> tuple:
    params = resolve_schedule("conv_bn_relu", m=int(mp), k=int(kp),
                              c=int(cp), dtype=str(dtype))
    return (max(8, min(int(params["tile_m"]), mp)),
            max(_LANES, min(int(params["tile_n"]), cp)))


def _bucket(info):
    # raw-shape tune() keys and padded-dim resolve() keys must collapse
    # into one bucket: clamp dims to their tile floors first
    from ...tuning.schedule import aligned_bucket

    return aligned_bucket({
        "m": lambda i: _SUBLANES.get(str(i.get("dtype", "float32")), 8),
        "k": _LANES, "c": _LANES,
    })(info)


def _conv_vmem_ok(info, c) -> bool:
    # full-K stripes resident per program: [tile_m, K] + [K, tile_n]
    # operand blocks (2B at the bf16 floor) + f32 [tile_m, tile_n]
    # accumulator/output; ~12 MB admission line under the 16 MB core
    k = int(info["k"])
    bytes_ = 2 * (c["tile_m"] * k + k * c["tile_n"]) \
        + 4 * c["tile_m"] * c["tile_n"]
    return (c["tile_m"] % 8 == 0 and c["tile_n"] % _LANES == 0
            and bytes_ <= 12 * (1 << 20))


def _tuning_bench(info):
    m, k, c = int(info["m"]), int(info["k"]), int(info["c"])
    dtype = str(info.get("dtype", "float32"))
    rng = np.random.RandomState(0)
    p2 = jnp.asarray(rng.randn(m, k).astype("f4")).astype(dtype)
    w2 = jnp.asarray(rng.randn(k, c).astype("f4")).astype(dtype)
    scale = jnp.asarray(rng.rand(c).astype("f4") + 0.5)
    shift = jnp.asarray(rng.randn(c).astype("f4"))
    interpret = not on_tpu_platform()

    def builder(params):
        tiles = (max(8, min(int(params["tile_m"]), m)),
                 max(_LANES, min(int(params["tile_n"]), c)))
        fn = jax.jit(lambda p2, w2, s, b: _mm_affine_relu(
            p2, w2, s, b, interpret=interpret, tiles=tiles))

        def run():
            jax.block_until_ready(fn(p2, w2, scale, shift))

        return run

    return builder


register_schedule(
    name="conv_bn_relu",
    version=1,
    params={"tile_m": (64, 128, 256, 512),
            "tile_n": (128, 256, 512)},
    # tile floors keep the default point valid for RAW shapes too (the
    # dispatch path always passes padded dims, where the max() is a
    # no-op — byte-identity of the default holds either way)
    default=lambda info: {"tile_m": max(8, min(int(info["m"]), _TILE)),
                          "tile_n": max(_LANES, min(int(info["c"]),
                                                    _TILE))},
    supported=_conv_vmem_ok,
    bench=_tuning_bench,
    bucket=_bucket,
)


# -- reference / fallback -----------------------------------------------------


def _reference(x, w, gamma, beta, mean, var, *, stride, padding, training,
               momentum, eps, data_format):
    """EXACTLY the unfused op sequence: the registered conv2d kernel ->
    the registered batch_norm kernel -> relu, same primitives, same
    order — enabling the flag off-TPU is numerically free."""
    from ..kernels import batch_norm as _bn
    from ..kernels import conv2d as _conv

    co = _conv(x, w, stride=stride, padding=padding, dilation=1, groups=1,
               data_format=data_format)
    y, new_mean, new_var = _bn(co, gamma, beta, mean, var,
                               momentum=momentum, epsilon=eps,
                               training=training, data_format=data_format)
    return jax.nn.relu(y), new_mean, new_var


# -- matmul operands ----------------------------------------------------------


def _pair(v):
    return tuple(v) if isinstance(v, (list, tuple)) else (int(v), int(v))


def _pointwise(w, stride, padding):
    """Kernel 1x1, stride 1, no padding (an int, a pair, a 4-list or a
    pair of pairs, all zero): the one conv whose matmul form is its
    input. SAME/VALID strings fall back like every spatial conv."""
    return (tuple(w.shape[2:]) == (1, 1) and _pair(stride) == (1, 1)
            and not isinstance(padding, str) and not np.any(padding))


def _pad_mat(a, rows, cols):
    r, c = a.shape
    if (r, c) == (rows, cols):
        return a
    return jnp.pad(a, ((0, rows - r), (0, cols - c)))


def _pad_vec(v, cols):
    return v if v.shape[0] == cols else jnp.pad(v, (0, cols - v.shape[0]))


def _padded_dims(m, k, c, dtype):
    sub = _SUBLANES.get(str(dtype), 8)
    mp = ((m + sub - 1) // sub) * sub
    kp = ((k + _LANES - 1) // _LANES) * _LANES
    cp = ((c + _LANES - 1) // _LANES) * _LANES
    return mp, kp, cp


# -- forward kernels ----------------------------------------------------------


def _mm_affine_relu_kernel(x_ref, w_ref, s_ref, b_ref, y_ref, *, dt):
    # conv output cast to the carrier dtype FIRST (what the unfused conv
    # hands batch_norm), then the f32 affine + relu — one VMEM pass
    acc = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    co = acc.astype(dt).astype(jnp.float32)
    y = co * s_ref[0] + b_ref[0]
    y_ref[:] = jnp.maximum(y, 0.0).astype(dt)


def _mm_stats_kernel(x_ref, w_ref, co_ref, ps_ref, *, dt, nrows, tile_m):
    """Matmul + channel-sum partials. A ragged last row-tile reads
    out-of-bounds rows (undefined content — NaN in interpret mode);
    stores clamp them away but the REDUCTION must mask them, same as
    the layernorm bwd row-validity mask. Zero-padded patch rows below
    ``nrows`` contribute 0 on their own."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    acc = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    co = acc.astype(dt)
    co_ref[:] = co
    cf = co.astype(jnp.float32)
    row = i * tile_m + lax.broadcasted_iota(jnp.int32, cf.shape, 0)
    ps_ref[0] = jnp.sum(jnp.where(row < nrows, cf, 0.0), axis=0,
                        keepdims=True)


def _centered_sumsq_kernel(co_ref, mean_ref, pss_ref, *, nrows, tile_m):
    """Per-tile partial of sum((co - mean)^2): the CENTERED second
    statistics pass. E[x^2] - mean^2 would be one pass cheaper but
    catastrophically cancels for large-mean channels (f32 carries ~7
    digits; a channel at mean 100, std 0.1 loses the variance
    entirely) — the two-pass form matches the unfused batch_norm
    kernel's jnp.var numerics class. Padded rows are masked (zero co
    minus a nonzero mean would otherwise contribute mean^2 each)."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    cf = co_ref[:].astype(jnp.float32)
    d = cf - mean_ref[0]
    row = i * tile_m + lax.broadcasted_iota(jnp.int32, cf.shape, 0)
    d = jnp.where(row < nrows, d, 0.0)
    pss_ref[0] = jnp.sum(d * d, axis=0, keepdims=True)


def _bn_relu_kernel(co_ref, s_ref, b_ref, y_ref, *, dt):
    cf = co_ref[:].astype(jnp.float32)
    y = cf * s_ref[0] + b_ref[0]
    y_ref[:] = jnp.maximum(y, 0.0).astype(dt)


def _tile_specs(pl, pltpu, tile_m, tile_n):
    """Block specs over a (row-tile i, col-tile j) grid: an [M, C] tile,
    a [1, C] per-channel vector, and a per-row-tile partial sum. The
    partials are [gm, 1, C] with a (1, 1, tile_n) block: Mosaic wants a
    block's second-to-last dim to be a multiple of 8 or the array's own,
    which a (1, tile_n) block of a [gm, C] array is not."""
    tile = pl.BlockSpec((tile_m, tile_n), lambda i, j: (i, j),
                        memory_space=pltpu.VMEM)
    vec = pl.BlockSpec((1, tile_n), lambda i, j: (0, j),
                       memory_space=pltpu.VMEM)
    part = pl.BlockSpec((1, 1, tile_n), lambda i, j: (i, 0, j),
                        memory_space=pltpu.VMEM)
    return tile, vec, part


def _mm_specs(pl, pltpu, tile_m, tile_n, kp):
    """Full-K operand stripes of the matmul passes."""
    row = pl.BlockSpec((tile_m, kp), lambda i, j: (i, 0),
                       memory_space=pltpu.VMEM)
    col = pl.BlockSpec((kp, tile_n), lambda i, j: (0, j),
                       memory_space=pltpu.VMEM)
    return row, col


def _mm_affine_relu(p2, w2, scale, shift, interpret=False, tiles=None):
    """Eval-mode fused pass: ``relu((p2 @ w2) * scale + shift)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = p2.shape
    c = w2.shape[1]
    dt = p2.dtype
    mp, kp, cp = _padded_dims(m, k, c, dt)
    tile_m, tile_n = tiles if tiles is not None else _schedule_tiles(
        mp, kp, cp, dt)
    xp = _pad_mat(p2, mp, kp)
    wp = _pad_mat(w2, kp, cp)
    sp = _pad_vec(scale.astype(jnp.float32), cp).reshape(1, cp)
    bp = _pad_vec(shift.astype(jnp.float32), cp).reshape(1, cp)
    row, col = _mm_specs(pl, pltpu, tile_m, tile_n, kp)
    out, vec, _ = _tile_specs(pl, pltpu, tile_m, tile_n)
    y = pl.pallas_call(
        functools.partial(_mm_affine_relu_kernel, dt=dt),
        name="conv_mm_affine_relu",
        grid=(pl.cdiv(mp, tile_m), pl.cdiv(cp, tile_n)),
        in_specs=[row, col, vec, vec],
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct((mp, cp), dt),
        interpret=interpret,
    )(xp, wp, sp, bp)
    return y[:m, :c]


def _mm_stats(p2, w2, interpret=False, tiles=None):
    """Training pass 1: conv matmul + per-tile channel-sum partials in
    the same VMEM pass. Returns (co, sum) with ``co`` left PADDED
    [Mp, Cp] — the statistics and normalize passes and the backward
    kernels consume it aligned, so keeping the padding avoids a
    slice-then-repad HBM round trip of the largest intermediate (padded
    rows/cols are zero and contribute nothing to any partial)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = p2.shape
    c = w2.shape[1]
    dt = p2.dtype
    mp, kp, cp = _padded_dims(m, k, c, dt)
    tile_m, tile_n = tiles if tiles is not None else _schedule_tiles(
        mp, kp, cp, dt)
    xp = _pad_mat(p2, mp, kp)
    wp = _pad_mat(w2, kp, cp)
    row, col = _mm_specs(pl, pltpu, tile_m, tile_n, kp)
    out, _, part = _tile_specs(pl, pltpu, tile_m, tile_n)
    gm = pl.cdiv(mp, tile_m)
    co, ps = pl.pallas_call(
        functools.partial(_mm_stats_kernel, dt=dt, nrows=m,
                          tile_m=tile_m),
        name="conv_mm_stats",
        grid=(gm, pl.cdiv(cp, tile_n)),
        in_specs=[row, col],
        out_specs=[out, part],
        out_shape=[
            jax.ShapeDtypeStruct((mp, cp), dt),
            jax.ShapeDtypeStruct((gm, 1, cp), jnp.float32),
        ],
        interpret=interpret,
    )(xp, wp)
    return co, ps.sum(axis=(0, 1))[:c]


def _centered_sumsq(co_p, mean, nrows, interpret=False, tiles=None):
    """Training pass 2: per-channel sum((co - mean)^2) over the PADDED
    conv output (rows >= nrows masked in-kernel)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    mp, cp = co_p.shape
    c = mean.shape[0]
    tile_m, tile_n = tiles if tiles is not None else _schedule_tiles(
        mp, _LANES, cp, co_p.dtype)
    meanp = _pad_vec(mean.astype(jnp.float32), cp).reshape(1, cp)
    tile, vec, part = _tile_specs(pl, pltpu, tile_m, tile_n)
    gm = pl.cdiv(mp, tile_m)
    pss = pl.pallas_call(
        functools.partial(_centered_sumsq_kernel, nrows=nrows,
                          tile_m=tile_m),
        name="conv_centered_sumsq",
        grid=(gm, pl.cdiv(cp, tile_n)),
        in_specs=[tile, vec],
        out_specs=part,
        out_shape=jax.ShapeDtypeStruct((gm, 1, cp), jnp.float32),
        interpret=interpret,
    )(co_p, meanp)
    return pss.sum(axis=(0, 1))[:c]


def _bn_relu(co, scale, shift, interpret=False, tiles=None):
    """Training pass 2: one elementwise normalize+relu pass."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, c = co.shape
    dt = co.dtype
    mp, _, cp = _padded_dims(m, 1, c, dt)
    tile_m, tile_n = tiles if tiles is not None else _schedule_tiles(
        mp, _LANES, cp, dt)
    cop = _pad_mat(co, mp, cp)
    sp = _pad_vec(scale.astype(jnp.float32), cp).reshape(1, cp)
    bp = _pad_vec(shift.astype(jnp.float32), cp).reshape(1, cp)
    tile, vec, _ = _tile_specs(pl, pltpu, tile_m, tile_n)
    y = pl.pallas_call(
        functools.partial(_bn_relu_kernel, dt=dt),
        name="conv_bn_relu",
        grid=(pl.cdiv(mp, tile_m), pl.cdiv(cp, tile_n)),
        in_specs=[tile, vec, vec],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((mp, cp), dt),
        interpret=interpret,
    )(cop, sp, bp)
    return y[:m, :c]


# -- backward kernels (training) ----------------------------------------------


def _bn_bwd_partials_kernel(co_ref, g_ref, s_ref, b_ref, pdy_ref,
                            pdyc_ref, *, nrows, tile_m):
    """Per-tile partials of sum(dy_relu) and sum(dy_relu * co): the relu
    gate recomputes from the saved conv output (pre = co*scale + shift),
    the flash-attention recompute discipline. Ragged-tail rows are
    masked out of the reductions (see _mm_stats_kernel)."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    cf = co_ref[:].astype(jnp.float32)
    pre = cf * s_ref[0] + b_ref[0]
    dyr = jnp.where(pre > 0, g_ref[:].astype(jnp.float32), 0.0)
    row = i * tile_m + lax.broadcasted_iota(jnp.int32, cf.shape, 0)
    valid = row < nrows
    dyr = jnp.where(valid, dyr, 0.0)
    pdy_ref[0] = jnp.sum(dyr, axis=0, keepdims=True)
    # cf must be masked too: 0 * (out-of-bounds NaN) is still NaN
    pdyc_ref[0] = jnp.sum(dyr * jnp.where(valid, cf, 0.0), axis=0,
                          keepdims=True)


def _bn_bwd_dco_kernel(co_ref, g_ref, s_ref, b_ref, k3_ref, b0_ref,
                       dco_ref):
    """Folded BN backward, elementwise: d_co = k1*dy_relu - k3*co - b0
    (k1 = scale = gamma*rstd; k3/b0 fold the batch-statistic terms)."""
    cf = co_ref[:].astype(jnp.float32)
    pre = cf * s_ref[0] + b_ref[0]
    dyr = jnp.where(pre > 0, g_ref[:].astype(jnp.float32), 0.0)
    dco_ref[:] = s_ref[0] * dyr - k3_ref[0] * cf - b0_ref[0]


def _bn_bwd_partials(co, g2, scale, shift, interpret=False, tiles=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, c = co.shape
    dt = co.dtype
    mp, _, cp = _padded_dims(m, 1, c, dt)
    tile_m, tile_n = tiles if tiles is not None else _schedule_tiles(
        mp, _LANES, cp, dt)
    cop = _pad_mat(co, mp, cp)
    gp = _pad_mat(g2, mp, cp)  # zero-padded rows/cols -> exact partials
    sp = _pad_vec(scale, cp).reshape(1, cp)
    bp = _pad_vec(shift, cp).reshape(1, cp)
    tile, vec, part = _tile_specs(pl, pltpu, tile_m, tile_n)
    gm = pl.cdiv(mp, tile_m)
    pdy, pdyc = pl.pallas_call(
        functools.partial(_bn_bwd_partials_kernel, nrows=m,
                          tile_m=tile_m),
        name="conv_bn_bwd_partials",
        grid=(gm, pl.cdiv(cp, tile_n)),
        in_specs=[tile, tile, vec, vec],
        out_specs=[part, part],
        out_shape=[
            jax.ShapeDtypeStruct((gm, 1, cp), jnp.float32),
            jax.ShapeDtypeStruct((gm, 1, cp), jnp.float32),
        ],
        interpret=interpret,
    )(cop, gp, sp, bp)
    return pdy.sum(axis=(0, 1))[:c], pdyc.sum(axis=(0, 1))[:c]


def _bn_bwd_dco(co, g2, scale, shift, k3, b0, interpret=False, tiles=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, c = co.shape
    dt = co.dtype
    mp, _, cp = _padded_dims(m, 1, c, dt)
    tile_m, tile_n = tiles if tiles is not None else _schedule_tiles(
        mp, _LANES, cp, dt)
    cop = _pad_mat(co, mp, cp)
    gp = _pad_mat(g2, mp, cp)
    vecs = [
        _pad_vec(v, cp).reshape(1, cp) for v in (scale, shift, k3, b0)
    ]
    tile, vec, _ = _tile_specs(pl, pltpu, tile_m, tile_n)
    dco = pl.pallas_call(
        _bn_bwd_dco_kernel,
        name="conv_bn_bwd_dco",
        grid=(pl.cdiv(mp, tile_m), pl.cdiv(cp, tile_n)),
        in_specs=[tile, tile, vec, vec, vec, vec],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((mp, cp), jnp.float32),
        interpret=interpret,
    )(cop, gp, *vecs)
    return dco[:m, :c]


# -- custom-vjp cores ---------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _train_core(p2, w2, gamma, beta, eps, interpret):
    y2, _, mean, var = _train_fwd_impl(p2, w2, gamma, beta, eps, interpret)
    return y2, mean, var


def _train_fwd_impl(p2, w2, gamma, beta, eps, interpret):
    m, c = p2.shape[0], w2.shape[1]
    co_p, s = _mm_stats(p2, w2, interpret=interpret)  # co PADDED
    mean = s / m
    # centered two-pass variance (biased, like jnp.var) — see
    # _centered_sumsq_kernel for why E[x^2]-mean^2 is not an option
    var = _centered_sumsq(co_p, mean, m, interpret=interpret) / m
    rstd = lax.rsqrt(var + eps)
    scale = gamma * rstd
    shift = beta - mean * scale
    # co_p is already tile-aligned: the normalize pass pads nothing
    y2 = _bn_relu(co_p, scale, shift, interpret=interpret)[:m, :c]
    return y2, co_p, mean, var


def _train_core_fwd(p2, w2, gamma, beta, eps, interpret):
    y2, co, mean, var = _train_fwd_impl(p2, w2, gamma, beta, eps,
                                        interpret)
    return (y2, mean, var), (p2, w2, gamma, beta, co, mean, var)


def _train_core_bwd(eps, interpret, saved, cots):
    p2, w2, gamma, beta, co_p, mean, var = saved  # co_p PADDED [Mp, Cp]
    g, _, _ = cots  # the batch-stat outputs feed only the DETACHED
    #                 running-stat blend: their cotangents are zero
    m, c = p2.shape[0], w2.shape[1]
    mp, cp = co_p.shape
    gp = _pad_mat(g, mp, cp)  # zero pad rows/cols -> exact partials
    rstd = lax.rsqrt(var + eps)
    scale = gamma * rstd
    shift = beta - mean * scale
    sum_dy, sum_dyc = _bn_bwd_partials(co_p, gp, scale, shift,
                                       interpret=interpret)
    sum_dy, sum_dyc = sum_dy[:c], sum_dyc[:c]
    dbeta = sum_dy
    dgamma = (sum_dyc - mean * sum_dy) * rstd
    c1 = sum_dy / m
    c2 = dgamma / m                               # = mean(dy * xhat)
    k3 = scale * c2 * rstd
    b0 = scale * c1 - k3 * mean
    dco = _bn_bwd_dco(co_p, gp, scale, shift, k3, b0,
                      interpret=interpret)[:m, :c]
    # matmul grads: MXU dots through XLA (dco sliced back to the real
    # extent; p2's padded rows were zero, so nothing was ever lost)
    dp2 = jnp.dot(dco, w2.astype(jnp.float32).T).astype(p2.dtype)
    dw2 = jnp.dot(p2.astype(jnp.float32).T, dco).astype(w2.dtype)
    return dp2, dw2, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype)


_train_core.defvjp(_train_core_fwd, _train_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _eval_core(p2, w2, gamma, beta, mean, var, eps, interpret):
    rstd = lax.rsqrt(var + eps)
    scale = gamma * rstd
    shift = beta - mean * scale
    return _mm_affine_relu(p2, w2, scale, shift, interpret=interpret)


def _eval_expr(p2, w2, gamma, beta, mean, var, eps, dt):
    """The eval-mode math as plain jnp (the backward recompute)."""
    acc = jnp.dot(p2, w2, preferred_element_type=jnp.float32)
    co = acc.astype(dt).astype(jnp.float32)
    rstd = lax.rsqrt(var + eps)
    y = (co - mean) * rstd * gamma + beta
    return jnp.maximum(y, 0.0).astype(dt)


def _eval_core_fwd(p2, w2, gamma, beta, mean, var, eps, interpret):
    y = _eval_core(p2, w2, gamma, beta, mean, var, eps, interpret)
    return y, (p2, w2, gamma, beta, mean, var)


def _eval_core_bwd(eps, interpret, saved, g):
    # inference backward is off the training hot path: exact grads via
    # the jnp recompute (one extra matmul, the recompute discipline)
    p2, w2, gamma, beta, mean, var = saved
    _, vjp = jax.vjp(
        lambda *a: _eval_expr(*a, eps, p2.dtype),
        p2, w2, gamma, beta, mean, var)
    return vjp(g)


_eval_core.defvjp(_eval_core_fwd, _eval_core_bwd)


# -- dispatch -----------------------------------------------------------------


def _supported(x, w, stride, padding, data_format, dilation, groups):
    if not can_emit_mosaic():
        return False
    if str(x.dtype) not in _SUBLANES or x.dtype != w.dtype:
        return False
    if groups != 1 or _pair(dilation) != (1, 1):
        return False
    if x.ndim != 4 or not _pointwise(w, stride, padding):
        return False
    if data_format not in ("NCHW", "NHWC"):
        return False
    cout = w.shape[0]
    # tiny convs are not worth two pallas dispatches
    return x.shape[0] * cout >= 8 * _LANES // 2


def _fused(x, w, gamma, beta, mean, var, *, stride, padding, training,
           momentum, eps, data_format, interpret=False, force=False):
    # force skips the platform, dtype and size gates (the CPU tests' way
    # in), never the pointwise one: there is no lowering for the rest
    if not (_supported(x, w, stride, padding, data_format, 1, 1)
            or force and _pointwise(w, stride, padding)):
        return _reference(x, w, gamma, beta, mean, var, stride=stride,
                          padding=padding, training=training,
                          momentum=momentum, eps=eps,
                          data_format=data_format)
    if data_format == "NCHW":
        x = jnp.moveaxis(x, 1, -1)
    *lead, cin = x.shape
    cout = w.shape[0]
    p2 = x.reshape(-1, cin)     # the "patches" ARE the input, channels-last
    w2 = w.reshape(cout, cin).T                    # [K, Cout]
    gf = gamma.astype(jnp.float32)
    bf = beta.astype(jnp.float32)
    if training:
        y2, bmean, bvar = _train_core(p2, w2, gf, bf, float(eps),
                                      bool(interpret))
        # the same running-stat blend as the batch_norm op kernel
        new_mean = momentum * mean + (1 - momentum) * bmean.astype(
            mean.dtype)
        new_var = momentum * var + (1 - momentum) * bvar.astype(var.dtype)
    else:
        y2 = _eval_core(p2, w2, gf, bf, mean.astype(jnp.float32),
                        var.astype(jnp.float32), float(eps),
                        bool(interpret))
        new_mean, new_var = mean, var
    y = y2.reshape(*lead, cout)
    if data_format == "NCHW":
        y = jnp.moveaxis(y, -1, 1)
    return y, new_mean, new_var


def conv_bn_relu(x, weight, gamma, beta, running_mean, running_var, *,
                 stride=1, padding=0, epsilon=1e-5, momentum=0.9,
                 training=False, data_format="NCHW"):
    """Fused ``relu(batch_norm(conv2d(x, weight)))``.

    Returns ``(y, new_running_mean, new_running_var)`` with the exact
    batch_norm running-stat semantics (``running = momentum*running +
    (1-momentum)*batch``; unchanged in eval mode). Accepts Tensors
    (autograd-tracked through the op tape) or raw arrays; pallas on TPU
    for pointwise convs of admitted shapes, the identical unfused op
    sequence for every other conv and everywhere else. The conv must be
    bias-free, ungrouped, undilated.
    """
    from ...framework.tensor import Tensor

    attrs = dict(stride=stride, padding=padding, training=bool(training),
                 momentum=float(momentum), eps=float(epsilon),
                 data_format=data_format)
    args = (x, weight, gamma, beta, running_mean, running_var)
    if any(isinstance(t, Tensor) for t in args):
        from ...framework.autograd import apply_op

        tensors = [
            t if isinstance(t, Tensor) else Tensor._from_array(jnp.asarray(t))
            for t in args
        ]
        return apply_op(
            "fused_conv_bn_relu",
            lambda x, w, g, b, m, v: _fused(x, w, g, b, m, v, **attrs),
            tensors, {})
    return _fused(*(jnp.asarray(a) for a in args), **attrs)

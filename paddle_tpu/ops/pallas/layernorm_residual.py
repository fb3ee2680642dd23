"""Fused residual-add + LayerNorm (TPU pallas kernel, fwd + bwd).

The post-norm transformer's hottest pointwise chain is

    y = LayerNorm(x + residual)

— on the op-by-op path that is an HBM round trip for the add, another
for the statistics, and a third for the affine output. The pallas
kernel does it in ONE VMEM pass per row tile: compute ``a = x + res``,
the f32 mean/rstd, and ``xhat * w + b`` without ever materializing the
sum in HBM. The backward is a second kernel over the same tiles using
the saved per-row ``(mean, rstd)``: it recomputes ``a`` from the saved
inputs (cheaper than saving ``xhat`` — the flash-attention recompute
discipline), emits ``d_input`` (= dx = dresidual) plus per-tile partial
``dw``/``db`` sums that one tiny jnp reduction finishes.

Off-TPU (and for unadmitted shapes) the jnp fallback computes the
IDENTICAL primitive sequence the ``layer_norm`` op kernel uses (f32
statistics, output cast back to the input dtype), so enabling
``FLAGS_use_fused_layernorm`` never changes f32 numerics — only where
the fusion happens (Mosaic vs XLA). The kernels express the residual
add in the INPUT dtype (same expression as the unfused path) so both
compile to the same arithmetic; for bf16 inputs agreement is to 1 ulp
rather than bit-exact, because XLA itself keeps or drops the bf16
rounding of fused intermediates depending on fusion decisions — on
both paths equally (a jitted bf16+bf16 add already computes in f32
without intermediate rounding on XLA:CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..._internal_tuning import register_schedule, resolve_schedule
from ._platform import can_emit_mosaic, on_tpu_platform

__all__ = ["layernorm_residual"]

_LANES = 128
_BLOCK_R = 256  # max rows per program
_MAX_H = 16384  # _supported bound: block_r floors at 8 rows ≤ 2 MB f32


def _block_rows(rows, h):
    """Rows per program, scaled so one f32 row block stays ≤ ~2 MB —
    the bwd kernel keeps a handful of blocks live, so an unscaled
    (256, H) tile blows the ~16 MB VMEM budget once H > 2048. This is
    the schedule space's DEFAULT point: untuned resolution returns
    exactly this geometry."""
    cap = max(8, min(_BLOCK_R, (1 << 21) // (4 * h)))
    return min(cap, rows)


def _schedule_block_rows(rows, h, dtype) -> int:
    """Row-block size through the autotuner: tuned winner for this
    (device_kind, shape-bucket, dtype) when cached, else the
    byte-identical :func:`_block_rows` default."""
    params = resolve_schedule("layernorm_residual", rows=int(rows),
                              h=int(h), dtype=str(dtype))
    return max(1, min(int(params["block_r"]), rows))


def _tuning_bench(info):
    """Measurement builder for the tuner: one jitted fwd pass at the
    candidate's row block (interpret off-TPU, so the CPU smoke can
    drive the full search pipeline)."""
    import numpy as np

    rows, h = int(info["rows"]), int(info["h"])
    dtype = str(info.get("dtype", "float32"))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(rows, h).astype("f4")).astype(dtype)
    r = jnp.asarray(rng.randn(rows, h).astype("f4")).astype(dtype)
    w = jnp.asarray(rng.randn(h).astype("f4"))
    b = jnp.asarray(rng.randn(h).astype("f4"))
    interpret = not on_tpu_platform()

    def builder(params):
        block_r = max(1, min(int(params["block_r"]), rows))
        fn = jax.jit(lambda x, r, w, b: _pallas_fwd(
            x, r, w, b, 1e-5, interpret=interpret, block_r=block_r))

        def run():
            jax.block_until_ready(fn(x, r, w, b))

        return run

    return builder


register_schedule(
    name="layernorm_residual",
    version=1,
    params={"block_r": (8, 16, 32, 64, 128, 256, 512)},
    default=lambda info: {"block_r": _block_rows(info["rows"], info["h"])},
    # one row block must stay within the searchable VMEM headroom (the
    # bwd kernel keeps several live; 4 MB/block is the admission line)
    supported=lambda info, c: (8 <= c["block_r"] <= 1024
                               and c["block_r"] * info["h"] * 4 <= (1 << 22)),
    bench=_tuning_bench,
)


# -- reference / fallback -----------------------------------------------------


def _reference(x, res, w, b, eps):
    """Exactly the layer_norm op-kernel math over ``x + res`` (same
    primitives, same order — bit-identical to norm(residual + y))."""
    a = x + res
    af = a.astype(jnp.float32) if a.dtype != jnp.float32 else a
    mean = jnp.mean(af, axis=-1, keepdims=True)
    var = jnp.var(af, axis=-1, keepdims=True)
    y = (af - mean) * lax.rsqrt(var + eps)
    y = y * w + b
    return y.astype(x.dtype)


# -- pallas kernels -----------------------------------------------------------


def _fwd_kernel(x_ref, r_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *,
                eps, dt):
    # the add happens in ``dt``, the dtype ``x + res`` promotes to (bf16
    # rounds when both are bf16), exactly like the unfused norm(x + res)
    # path — only the statistics are f32. ``dt`` is passed statically
    # because interpret mode presents bf16 refs as f32 (losslessly, so
    # the cast recovers input dtype)
    a = (x_ref[:].astype(dt) + r_ref[:].astype(dt)).astype(jnp.float32)
    mean = jnp.mean(a, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(a - mean), axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    xhat = (a - mean) * rstd
    y = xhat * w_ref[0].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, r_ref, w_ref, mean_ref, rstd_ref, dy_ref, da_ref,
                dwp_ref, dbp_ref, *, nrows, block_r, dt):
    """One (row-tile) program: d_input rows + partial dw/db sums.

    Tail tiles carry padding rows whose content is undefined — the
    row-validity mask zeroes their contribution to the dw/db partials
    (da writes to padding rows are dropped by the masked block store).
    """
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    # input-dtype add, matching the fwd kernel and the unfused path
    # (static ``dt``; see the fwd kernel on interpret-mode refs)
    a = (x_ref[:].astype(dt) + r_ref[:].astype(dt)).astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    w = w_ref[0].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    xhat = (a - mean) * rstd
    wdy = dy * w
    c1 = jnp.mean(wdy, axis=-1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    da = rstd * (wdy - c1 - xhat * c2)
    da_ref[:] = da.astype(da_ref.dtype)
    # mask padding rows out of the cross-row reductions
    row = i * block_r + lax.broadcasted_iota(jnp.int32, dy.shape, 0)
    valid = row < nrows
    dy_m = jnp.where(valid, dy, 0.0)
    dwp_ref[0] = jnp.sum(dy_m * jnp.where(valid, xhat, 0.0), axis=0,
                         keepdims=True)
    dbp_ref[0] = jnp.sum(dy_m, axis=0, keepdims=True)


def _pallas_fwd(x2, r2, w, b, eps, interpret=False, block_r=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, h = x2.shape
    if block_r is None:
        block_r = _schedule_block_rows(rows, h, x2.dtype)
    grid = (pl.cdiv(rows, block_r),)
    row_spec = pl.BlockSpec((block_r, h), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, h), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    col_spec = pl.BlockSpec((block_r, 1), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps,
                          dt=jnp.promote_types(x2.dtype, r2.dtype)),
        name="layernorm_residual_fwd",
        grid=grid,
        in_specs=[row_spec, row_spec, vec_spec, vec_spec],
        out_specs=[row_spec, col_spec, col_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, h), x2.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, r2, w.reshape(1, h), b.reshape(1, h))
    return y, mean, rstd


def _pallas_bwd(x2, r2, w, mean, rstd, dy2, interpret=False, block_r=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, h = x2.shape
    if block_r is None:
        block_r = _schedule_block_rows(rows, h, x2.dtype)
    ntiles = pl.cdiv(rows, block_r)
    row_spec = pl.BlockSpec((block_r, h), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, h), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    col_spec = pl.BlockSpec((block_r, 1), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    # per-tile partials are [ntiles, 1, h] with a (1, 1, h) block: Mosaic
    # wants a block's second-to-last dim to be a multiple of 8 or the
    # array's own, which a (1, h) block of an [ntiles, h] array is not
    part_spec = pl.BlockSpec((1, 1, h), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM)
    # d_input comes out in the dtype ``x + res`` promotes to; the caller
    # casts it to each operand's own dtype
    dt = jnp.promote_types(x2.dtype, r2.dtype)
    da, dwp, dbp = pl.pallas_call(
        functools.partial(_bwd_kernel, nrows=rows, block_r=block_r, dt=dt),
        name="layernorm_residual_bwd",
        grid=(ntiles,),
        in_specs=[row_spec, row_spec, vec_spec, col_spec, col_spec,
                  row_spec],
        out_specs=[row_spec, part_spec, part_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, h), dt),
            jax.ShapeDtypeStruct((ntiles, 1, h), jnp.float32),
            jax.ShapeDtypeStruct((ntiles, 1, h), jnp.float32),
        ],
        interpret=interpret,
    )(x2, r2, w.reshape(1, h), mean, rstd, dy2)
    return da, dwp.sum(axis=(0, 1)), dbp.sum(axis=(0, 1))


# -- custom-vjp wiring --------------------------------------------------------


def _supported(x, res, w, b) -> bool:
    if not can_emit_mosaic():
        return False
    if any(str(a.dtype) not in ("float32", "bfloat16") for a in (x, res)):
        return False
    h = x.shape[-1]
    return (x.shape == res.shape and h % _LANES == 0 and h <= _MAX_H
            and w.shape == (h,) and b.shape == (h,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ln_res(x, res, w, b, eps):
    if _supported(x, res, w, b):
        x2 = x.reshape(-1, x.shape[-1])
        y, _, _ = _pallas_fwd(x2, res.reshape(x2.shape), w, b, eps)
        return y.reshape(x.shape)
    return _reference(x, res, w, b, eps)


def _ln_res_fwd(x, res, w, b, eps):
    if _supported(x, res, w, b):
        x2 = x.reshape(-1, x.shape[-1])
        r2 = res.reshape(x2.shape)
        y, mean, rstd = _pallas_fwd(x2, r2, w, b, eps)
        return y.reshape(x.shape), (x, res, w, b, mean, rstd)
    return _reference(x, res, w, b, eps), (x, res, w, b, None, None)


def _ln_res_bwd(eps, saved, g):
    x, res, w, b, mean, rstd = saved
    if mean is not None:  # pallas path
        h = x.shape[-1]
        da, dw, db = _pallas_bwd(
            x.reshape(-1, h), res.reshape(-1, h), w, mean, rstd,
            g.reshape(-1, h))
        da = da.reshape(x.shape)
        return (da.astype(x.dtype), da.astype(res.dtype),
                dw.astype(w.dtype), db.astype(b.dtype))
    _, vjp = jax.vjp(lambda x, r, w, b: _reference(x, r, w, b, eps),
                     x, res, w, b)
    return vjp(g)


_ln_res.defvjp(_ln_res_fwd, _ln_res_bwd)


def layernorm_residual(x, residual, weight, bias, epsilon=1e-5):
    """Fused ``LayerNorm(x + residual)`` over the last dimension.

    Accepts Tensors (autograd-tracked through the framework's op tape)
    or raw arrays. ``weight``/``bias`` are the LayerNorm affine params
    ``[H]``. Pallas on TPU for lane-aligned ``H``; jnp fallback with the
    identical primitive sequence elsewhere.
    """
    from ...framework.tensor import Tensor

    eps = float(epsilon)
    if isinstance(x, Tensor) or isinstance(residual, Tensor):
        from ...framework.autograd import apply_op

        tensors = [
            t if isinstance(t, Tensor) else Tensor._from_array(jnp.asarray(t))
            for t in (x, residual, weight, bias)
        ]
        return apply_op(
            "fused_layernorm_residual",
            lambda x, r, w, b: _ln_res(x, r, w, b, eps), tensors, {})
    return _ln_res(jnp.asarray(x), jnp.asarray(residual),
                   jnp.asarray(weight), jnp.asarray(bias), eps)

"""Fused momentum / weight-decay optimizer update (TPU pallas kernel).

One VMEM pass over ``[rows, 128]`` tiles computes

    g' = grad + wd * param
    v' = mu * v + g'
    p' = p - lr * (g' + mu * v')      (nesterov)
        | p - lr * v'                  (plain)

with ``input_output_aliases`` so param and velocity update in place, and
``lr`` (a traced scalar: the schedule feeds a fresh value every step
without recompiling) in SMEM as ``[1, 1]``. Padding rows compute garbage
that is never written back, which is safe for an elementwise update.

The kernel takes an operand only when its ``[rows, 128]`` view is free
(``_flat_view_is_dense``: vectors, matrices, pointwise ``[O, I, 1, 1]``
weights). The chip keeps an ``[O, I, 3, 3]`` weight with ``O`` and ``I``
minor, and flattening it row-major first pads each 3 x 3 plane to a whole
tile: 57 times the bytes, three times in and twice out (PERF.md, PR 35).
Such operands, other dtypes and everything off the TPU take
``_jnp_update``, the IDENTICAL expression in the same order, which XLA
fuses into the step in the layout the weight has; so the two paths are
bit-compatible and ``FLAGS_use_fused_optimizer`` is numerically free to
leave on. Profiler counters ``optimizer::momentum_kernel`` /
``optimizer::momentum_xla`` count the operands sent each way (once an
operand while a step is traced).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..._internal_tuning import register_schedule, resolve_schedule
from ...profiler import bump_counter
from ._platform import can_emit_mosaic, on_tpu_platform

__all__ = ["fused_momentum_update"]

_LANES = 128
# minimum sublane multiple per dtype (pallas_guide.md tiling table)
_SUBLANES = {"float32": 8, "bfloat16": 16}
_BLOCK_R = 2048  # default rows per program: ≤ 2048×128 f4 = 1 MB / operand


def _schedule_block_rows(rows, dtype) -> int:
    """Row-block size through the autotuner; the default point is the
    historical ``min(rows, 2048)`` — byte-identical when untuned."""
    params = resolve_schedule("optimizer_update", rows=int(rows),
                              dtype=str(dtype))
    return max(1, min(int(params["block_r"]), rows))


def _tuning_bench(info):
    import numpy as np

    rows = int(info["rows"])
    dtype = str(info.get("dtype", "float32"))
    n = rows * _LANES
    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.randn(n).astype("f4")).astype(dtype)
    g = jnp.asarray(rng.randn(n).astype("f4")).astype(dtype)
    v = jnp.asarray(rng.randn(n).astype("f4")).astype(dtype)
    interpret = not on_tpu_platform()

    def builder(params):
        block_r = max(1, min(int(params["block_r"]), rows))
        fn = jax.jit(lambda p, g, v, lr: _pallas_update(
            p, g, v, lr, 0.9, 1e-4, False, interpret=interpret,
            block_r=block_r))
        lr = jnp.float32(0.1)

        def run():
            jax.block_until_ready(fn(p, g, v, lr))

        return run

    return builder


def _bucket(info):
    # raw-row tune() keys and padded-[R,128] resolve() keys must
    # collapse into one bucket: clamp rows to the sublane floor first
    from ...tuning.schedule import aligned_bucket

    return aligned_bucket({
        "rows": lambda i: _SUBLANES.get(str(i.get("dtype", "float32")),
                                        8),
    })(info)


register_schedule(
    name="optimizer_update",
    version=1,
    params={"block_r": (256, 512, 1024, 2048, 4096, 8192)},
    default=lambda info: {"block_r": min(int(info["rows"]), _BLOCK_R)},
    bucket=_bucket,
    # 5 live [block_r, 128] operand blocks (3 in + 2 out) must stay far
    # under the ~16 MB VMEM budget, bf16 sublane multiple respected
    supported=lambda info, c: (
        c["block_r"] >= _SUBLANES.get(info.get("dtype", "float32"), 8)
        and 5 * c["block_r"] * _LANES * 4 <= (1 << 23)),
    bench=_tuning_bench,
)


def _jnp_update(param, grad, velocity, lr, mu, wd, nesterov):
    """Reference/fallback path: the exact expression the kernel fuses,
    in the same operation order (bit-identical off-TPU)."""
    g = grad + wd * param if wd else grad
    v = mu * velocity + g
    if nesterov:
        new_p = param - lr * (g + mu * v)
    else:
        new_p = param - lr * v
    return new_p, v


def _kernel(lr_ref, p_ref, g_ref, v_ref, p_out, v_out, *, mu, wd,
            nesterov):
    lr = lr_ref[0, 0]
    p = p_ref[:]
    g = g_ref[:]
    if wd:
        g = g + wd * p
    v = mu * v_ref[:] + g
    v_out[:] = v
    if nesterov:
        p_out[:] = p - lr * (g + mu * v)
    else:
        p_out[:] = p - lr * v


def _flat_view_is_dense(shape, dtype) -> bool:
    """Whether the row-major ``[rows, 128]`` view of ``shape`` is a
    bitcast or one dense move: the row-major tiled footprint of the
    shape without its unit dimensions is at most twice its size. False
    for ``[512, 512, 3, 3]`` (3 x 3 in a tile: 57-114 x) and the stem's
    ``[64, 3, 7, 7]`` (21 x); true for ``[2048]``, ``[1000, 2048]`` and
    any ``[O, I, 1, 1]`` from 64 x 64 up."""
    dims = [d for d in shape if d != 1]
    tile = (_SUBLANES[str(dtype)], _LANES)[-len(dims):]
    tiled = dims[:-len(tile)] + [
        -(-d // t) * t for d, t in zip(dims[-len(tile):], tile)]
    return math.prod(tiled) <= 2 * math.prod(dims)


def _supported(param, grad, velocity) -> bool:
    if str(param.dtype) not in _SUBLANES:
        return False
    return (param.shape == grad.shape == velocity.shape
            and param.size >= _LANES
            and _flat_view_is_dense(param.shape, param.dtype))


def _pallas_update(param, grad, velocity, lr, mu, wd, nesterov,
                   interpret=False, block_r=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape, dtype, n = param.shape, param.dtype, param.size
    sub = _SUBLANES[str(dtype)]
    tile = sub * _LANES
    padded = ((n + tile - 1) // tile) * tile
    rows = padded // _LANES

    def flat(a):
        a = a.reshape(-1)
        if padded != n:
            a = jnp.pad(a, (0, padded - n))
        return a.reshape(rows, _LANES)

    pf, gf, vf = flat(param), flat(grad), flat(velocity)
    if block_r is None:
        block_r = _schedule_block_rows(rows, dtype)
    grid = (pl.cdiv(rows, block_r),)
    row_spec = pl.BlockSpec((block_r, _LANES), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1, 1)

    def kernel(lr_ref, p_ref, g_ref, v_ref, p_out, v_out):
        return _kernel(lr_ref, p_ref, g_ref, v_ref, p_out, v_out,
                       mu=mu, wd=wd, nesterov=nesterov)

    new_p, new_v = pl.pallas_call(
        kernel,
        name="momentum_update",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            row_spec, row_spec, row_spec,
        ],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANES), dtype),
            jax.ShapeDtypeStruct((rows, _LANES), dtype),
        ],
        # param/velocity update IN PLACE (XLA aliases the dead inputs)
        input_output_aliases={1: 0, 3: 1},
        interpret=interpret,
    )(lr_arr, pf, gf, vf)
    unflat = lambda a: a.reshape(-1)[:n].reshape(shape)
    return unflat(new_p), unflat(new_v)


def fused_momentum_update(param, grad, velocity, lr, momentum=0.9,
                          weight_decay=0.0, use_nesterov=False):
    """One fused momentum(+L2 decay) parameter update.

    Returns ``(new_param, new_velocity)``. Dispatches to the pallas
    kernel on TPU for admitted shapes/dtypes (``_supported``); elsewhere
    the jnp fallback computes the identical expression (same order, same
    dtypes). Safe inside a jitted train step (``lr`` may be a traced
    scalar).
    """
    param = jnp.asarray(param)
    grad = jnp.asarray(grad, param.dtype)
    velocity = jnp.asarray(velocity, param.dtype)
    mu = float(momentum)
    wd = float(weight_decay)
    nesterov = bool(use_nesterov)
    if can_emit_mosaic() and _supported(param, grad, velocity):
        bump_counter("optimizer::momentum_kernel")
        return _pallas_update(param, grad, velocity, lr, mu, wd, nesterov)
    bump_counter("optimizer::momentum_xla")
    return _jnp_update(param, grad, velocity, lr, mu, wd, nesterov)

"""Fused max-pool backward pallas kernel.

Reference parity: the backward of pool2d (paddle/fluid/operators/pool_op.cc
MaxPool2dGradFunctor — CUDA walks each window and routes the gradient to
the first max position). XLA lowers the same vjp to select_and_scatter,
which on TPU costs ~2.6 ms/step at the ResNet-50 stem shape (measured by
zero-backward ablation, [128,64,112,112] batch 128): the select scan and
the scatter run as separate HBM passes.

This kernel fuses the whole backward into ONE HBM pass: read x, y, dy
once, write dx once. Mosaic constraints shape the implementation:

- strided slices/reshape-interleaves are unsupported on the LANE (W)
  axis, so all stride-s W motion runs on the MXU as matmuls against
  one-hot selection matrices built from iota (exact for bf16 operands;
  ``Precision.HIGHEST`` — bf16x3, reconstructing all 24 mantissa bits —
  for f32, keeping the x == max equality comparison faithful);
- the SUBLANE (H) axis supports split/merge reshapes, so H de-striding is
  a reshape+index and H re-striding is a zero-interleave (stack+reshape).

Tie handling is first-max-wins over row-major window taps — the identical
subgradient to select_and_scatter's ge-select and the reference CUDA
kernel. Grid: rows of the collapsed [N*C] axis; each program holds full
spatial planes in VMEM (stem shape: ~1 MB per 8-row block in f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..._internal_tuning import register_schedule, resolve_schedule
from ._platform import can_emit_mosaic, on_tpu_platform

__all__ = ["max_pool2d_backward", "max_pool_backward_supported"]


def _row_elems(h, w, oh, ow, ph, pw):
    """The kernel's rough f32 working set per [N*C] row (module
    docstring: padded planes + half-width planes + coarse planes)."""
    hp, wp = h + 2 * ph, w + 2 * pw
    return 3 * hp * wp + 6 * hp * ow + 6 * oh * ow + 2 * h * w


def _default_block_rows(r, h, w, oh, ow, ph, pw):
    """The historical policy: start at 8 rows, halve until the block
    fits ~2 MB AND divides the collapsed [N*C] axis — the schedule
    space's byte-identical default point."""
    elems = _row_elems(h, w, oh, ow, ph, pw)
    br = 8
    while br > 1 and br * elems * 4 > (2 << 20):
        br //= 2
    while r % br:
        br //= 2
    return br


def _schedule_block_rows(r, h, w, oh, ow, ph, pw, dtype) -> int:
    params = resolve_schedule("pool_backward", r=int(r), h=int(h),
                              w=int(w), oh=int(oh), ow=int(ow),
                              ph=int(ph), pw=int(pw), dtype=str(dtype))
    return int(params["block_rows"])


def _tuning_bench(info):
    import numpy as np
    from jax import lax

    r, h, w = int(info["r"]), int(info["h"]), int(info["w"])
    oh, ow = int(info["oh"]), int(info["ow"])
    # a 2x2/2 pool reproduces the (h, w) -> (oh, ow) geometry the shape
    # bucket describes when oh = h//2; bench shapes should respect that
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, r, h, w).astype("f4"))
    y = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 2, 2),
                          (1, 1, 2, 2), [(0, 0)] * 4)
    dy = jnp.asarray(rng.randn(*y.shape).astype("f4"))
    interpret = not on_tpu_platform()

    def builder(params):
        br = int(params["block_rows"])

        def run():
            jax.block_until_ready(max_pool2d_backward(
                x, y, dy, kernel=(2, 2), stride=(2, 2), padding=(0, 0),
                interpret=interpret, block_rows=br))

        return run

    return builder


register_schedule(
    name="pool_backward",
    version=1,
    params={"block_rows": (1, 2, 4, 8, 16)},
    default=lambda info: {"block_rows": _default_block_rows(
        info["r"], info["h"], info["w"], info["oh"], info["ow"],
        info["ph"], info["pw"])},
    # must divide the collapsed row axis exactly (the grid floor-divides)
    # and keep the block within 2x the historical ~2 MB VMEM line
    supported=lambda info, c: (
        info["r"] % c["block_rows"] == 0
        and c["block_rows"] * _row_elems(
            info["h"], info["w"], info["oh"], info["ow"],
            info["ph"], info["pw"]) * 4 <= (4 << 20)),
    bench=_tuning_bench,
)


def _onehot(rows, cols, row_of_col_fn, dtype):
    """M[r, c] = 1 where r == row_of_col_fn(c) — built from 2D iota."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return (r == row_of_col_fn(c)).astype(dtype)


def _matmul(a, b, precision):
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32,
    )


def _pool_bwd_kernel(x_ref, y_ref, dy_ref, dx_ref, *, kh, kw, sh, sw,
                     ph, pw, oh, ow, h, w, precision):
    # all in-kernel compute runs in f32: Mosaic rejects bf16 sublane
    # stack/reshape, and f32 is exact for bf16-origin values (the matmul
    # precision still follows the input dtype — DEFAULT rounds operands
    # to bf16, lossless for bf16 data)
    dt = jnp.float32
    x = x_ref[...].astype(dt)       # [R, H, W]
    y = y_ref[...].astype(dt)       # [R, OH, OW]
    dy = dy_ref[...].astype(dt)
    r = x.shape[0]
    hp, wp = h + 2 * ph, w + 2 * pw
    hpe = hp + (-hp) % sh           # padded-H rounded up to the stride
    # pad x with a huge finite negative so padded cells never match the
    # window max — NOT -inf (the one-hot matmuls would turn -inf * 0
    # into NaN) and bf16-representable (f32 min overflows to -inf when
    # the MXU rounds operands to bf16)
    neg = jnp.asarray(-1.0e38, dt)
    xp = jnp.pad(x, ((0, 0), (ph, hpe - h - ph), (pw, pw)),
                 constant_values=neg)

    # W de-stride on the MXU: X_dj[r, i, wj] = xp[r, i, sw*wj + dj],
    # then split H phases ONCE per dj (sublane reshape): ph_q holds rows
    # q, q+sh, ... — every (di, dj) tap is then a cheap static slice
    phases = []                     # phases[dj][q] : [R, HPE/sh, OW]
    for dj in range(kw):
        g = _onehot(wp, ow, lambda c, dj=dj: sw * c + dj, dt)
        xc = _matmul(xp, g, precision).astype(dt)        # [R, HPE, OW]
        split = xc.reshape(r, hpe // sh, sh, ow)
        phases.append([split[:, :, q, :] for q in range(sh)])

    # first-max-wins selection per tap, row-major over (di, dj); the
    # per-tap gradient stays on the COARSE [OH, OW] grid (no relayouts
    # inside the loop)
    taken = jnp.zeros((r, oh, ow), jnp.bool_)
    coarse = [[None] * kw for _ in range(kh)]
    for di in range(kh):
        q, off = di % sh, di // sh
        for dj in range(kw):
            xw = jax.lax.slice(
                phases[dj][q], (0, off, 0), (r, off + oh, ow))
            sel = jnp.logical_and(xw == y, jnp.logical_not(taken))
            taken = jnp.logical_or(taken, sel)
            coarse[di][dj] = jnp.where(sel, dy, jnp.asarray(0, dt))

    # H re-stride: merge taps sharing a phase (shifted adds on the coarse
    # grid), then ONE interleave per dj; W re-stride on the MXU
    dxw = []
    nrow = hpe // sh
    for dj in range(kw):
        combs = []
        for q in range(sh):
            acc = jnp.zeros((r, nrow, ow), dt)
            for di in range(q, kh, sh):
                off = di // sh
                acc = acc + jnp.pad(
                    coarse[di][dj],
                    ((0, 0), (off, nrow - oh - off), (0, 0)))
            combs.append(acc)
        inter = jnp.stack(combs, axis=2).reshape(r, hpe, ow)
        dxw.append(inter)
    cat = jnp.concatenate(dxw, axis=2)                  # [R, HPE, kw*OW]
    es = []
    for dj in range(kw):
        rr = jax.lax.broadcasted_iota(jnp.int32, (ow, wp), 0)
        cc = jax.lax.broadcasted_iota(jnp.int32, (ow, wp), 1)
        es.append((cc == sw * rr + dj).astype(dt))
    e = jnp.concatenate(es, axis=0)                     # [kw*OW, WP]
    dxp = _matmul(cat, e, precision)                    # [R, HPE, WP]
    dx_ref[...] = dxp[:, ph:ph + h, pw:pw + w].astype(dx_ref.dtype)


def max_pool2d_backward(x, y, dy, *, kernel, stride, padding,
                        interpret=False, block_rows=None):
    """dx for max pooling: x [N,C,H,W], y/dy [N,C,OH,OW] -> dx like x.

    First-max-wins tie semantics, matching XLA select_and_scatter (and the
    reference CUDA MaxPool2dGradFunctor). The rows-per-program schedule
    resolves through the autotuner OUTSIDE the jitted impl (it is a
    static argument, so a tuned swap retraces instead of reusing the
    old grid).
    """
    ph, pw = padding
    n, c, h, w = x.shape
    oh, ow = y.shape[2], y.shape[3]
    if block_rows is None:
        block_rows = _schedule_block_rows(n * c, h, w, oh, ow, ph, pw,
                                          x.dtype)
    return _max_pool2d_backward(x, y, dy, kernel=tuple(kernel),
                                stride=tuple(stride),
                                padding=tuple(padding),
                                interpret=interpret,
                                block_rows=int(block_rows))


@functools.partial(
    jax.jit, static_argnames=("kernel", "stride", "padding", "interpret",
                              "block_rows"))
def _max_pool2d_backward(x, y, dy, *, kernel, stride, padding,
                         interpret, block_rows):
    kh, kw = kernel
    sh, sw = stride
    ph, pw = padding
    n, c, h, w = x.shape
    oh, ow = y.shape[2], y.shape[3]
    r = n * c
    br = block_rows
    precision = (jax.lax.Precision.DEFAULT
                 if x.dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    xr = x.reshape(r, h, w)
    yr = y.reshape(r, oh, ow)
    dyr = dy.reshape(r, oh, ow)
    kern = functools.partial(
        _pool_bwd_kernel, kh=kh, kw=kw, sh=sh, sw=sw, ph=ph, pw=pw,
        oh=oh, ow=ow, h=h, w=w, precision=precision,
    )
    dx = pl.pallas_call(
        kern,
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((br, h, w), lambda i: (i, 0, 0)),
            pl.BlockSpec((br, oh, ow), lambda i: (i, 0, 0)),
            pl.BlockSpec((br, oh, ow), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((br, h, w), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, h, w), x.dtype),
        interpret=interpret,
    )(xr, yr, dyr)
    return dx.reshape(n, c, h, w)


def max_pool_backward_supported(x_shape, dtype, ks, st, p, ceil_extra,
                                data_format):
    """Gate for the pallas path: TPU backend, NCHW 4D floating input,
    symmetric padding (no ceil_mode tail), spatial dims known."""
    if not can_emit_mosaic():
        return False
    if data_format != "NCHW" or len(x_shape) != 4:
        return False
    if ceil_extra != (0, 0):
        return False
    if not jnp.issubdtype(dtype, jnp.floating):
        return False
    # window must actually cover the input (standard pooling geometry)
    return all(int(d) > 0 for d in x_shape)

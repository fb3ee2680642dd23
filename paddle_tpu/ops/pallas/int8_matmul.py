"""Int8 matmul (TPU pallas kernel): int8 × int8 → int32 on the MXU.

The deployable int8 inference programs `slim.ptq.save_int8_model` emits
carry REAL int8 weights and quantized activations; their matmul/mul ops
(`matmul_int8`/`mul_int8` in ops/quantize_kernels.py) contract the two
int8 operands into int32 accumulators and only then apply the combined
dequantization scale — the MXU reads a quarter of the HBM bytes an f32
matmul would and accumulates exactly (int8·int8 products fit int32 with
headroom: 2^7 · 2^7 · K ≤ 2^31 for any practical K), so the int8 path
has ZERO accumulation error relative to the jnp fallback.

Kernel design per /opt/skills/guides/pallas_guide.md: the grid walks
``[TILE_M, K] × [K, TILE_N]`` VMEM blocks (int8 min tile is (32, 128),
so M pads to 32 and K/N pad to 128 — zero padding is exact for an
integer matmul), and every contraction runs through
``jnp.dot(..., preferred_element_type=jnp.int32)``. Off-TPU (and for
shapes the kernel does not admit) the jnp fallback computes the
IDENTICAL ``lax.dot_general`` with int8 inputs and int32
preferred-element-type, so ``FLAGS_use_int8_matmul`` never changes
numerics — the same flag discipline as the PR-10 fused kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..._internal_tuning import register_schedule, resolve_schedule
from ._platform import can_emit_mosaic, on_tpu_platform

__all__ = ["int8_matmul"]

_LANES = 128      # last-dim tile (every dtype)
_SUBLANES = 32    # int8 second-to-last-dim minimum tile
_TILE = 256       # default M/N tile (the historical hardcoded geometry)


def _schedule_tiles(pm, pk, pn) -> tuple:
    """(tile_m, tile_n) through the autotuner; default point is the
    historical ``min(dim, 256)`` pair — byte-identical when untuned."""
    params = resolve_schedule("int8_matmul", m=int(pm), k=int(pk),
                              n=int(pn), dtype="int8")
    return (max(_SUBLANES, min(int(params["tile_m"]), pm)),
            max(_LANES, min(int(params["tile_n"]), pn)))


def _bucket(info):
    # raw-shape tune() keys and padded-dim resolve() keys must collapse
    # into one bucket: clamp dims to their tile floors first
    from ...tuning.schedule import aligned_bucket

    return aligned_bucket({"m": _SUBLANES, "k": _LANES,
                           "n": _LANES})(info)


def _int8_vmem_ok(info, c) -> bool:
    # residents per program: int8 [tile_m, K] + int8 [K, tile_n]
    # + int32 [tile_m, tile_n]; keep the sum under ~12 MB of the 16 MB
    # core budget (the compiler's in/out buffering needs headroom)
    k = int(info["k"])
    b = (c["tile_m"] * k + k * c["tile_n"]
         + 4 * c["tile_m"] * c["tile_n"])
    return (c["tile_m"] % _SUBLANES == 0 and c["tile_n"] % _LANES == 0
            and b <= 12 * (1 << 20))


def _tuning_bench(info):
    import numpy as np

    m, k, n = int(info["m"]), int(info["k"]), int(info["n"])
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(-128, 128, (m, k)), jnp.int8)
    w = jnp.asarray(rng.randint(-128, 128, (k, n)), jnp.int8)
    interpret = not on_tpu_platform()

    def builder(params):
        tiles = (max(_SUBLANES, min(int(params["tile_m"]), m)),
                 max(_LANES, min(int(params["tile_n"]), n)))
        fn = jax.jit(lambda x, w: _pallas_matmul(
            x, w, interpret=interpret, tiles=tiles))

        def run():
            jax.block_until_ready(fn(x, w))

        return run

    return builder


register_schedule(
    name="int8_matmul",
    version=1,
    params={"tile_m": (32, 64, 128, 256, 512),
            "tile_n": (128, 256, 512)},
    # tile floors keep the default point valid for RAW shapes too (the
    # dispatch path always passes padded dims, where max() is a no-op)
    default=lambda info: {"tile_m": max(_SUBLANES,
                                        min(int(info["m"]), _TILE)),
                          "tile_n": max(_LANES,
                                        min(int(info["n"]), _TILE))},
    supported=_int8_vmem_ok,
    bench=_tuning_bench,
    bucket=_bucket,
)


def _jnp_matmul(x, w):
    """Fallback path: one dot_general, int8 inputs, int32 accumulation —
    the exact contraction the kernel tiles (identical expression)."""
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def _supported(x, w) -> bool:
    # the kernel handles the 2D core; callers flatten batch dims first
    # (ops/quantize_kernels.py does). Tiny operands are not worth the
    # pallas dispatch.
    return (x.ndim == 2 and w.ndim == 2 and x.shape[1] == w.shape[0]
            and str(x.dtype) == "int8" and str(w.dtype) == "int8"
            and x.shape[0] * w.shape[1] >= _SUBLANES * _LANES)


def _pad_to(a, rows, cols):
    r, c = a.shape
    if r == rows and c == cols:
        return a
    return jnp.pad(a, ((0, rows - r), (0, cols - c)))


def _pallas_matmul(x, w, interpret=False, tiles=None):
    """Tiled int8 matmul: grid over [M/TM, N/TN], K resident per block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    _, n = w.shape
    # zero padding is exact: padded rows/cols contribute 0 to int32 sums
    pm = ((m + _SUBLANES - 1) // _SUBLANES) * _SUBLANES
    pk = ((k + _LANES - 1) // _LANES) * _LANES
    pn = ((n + _LANES - 1) // _LANES) * _LANES
    xp = _pad_to(x, pm, pk)
    wp = _pad_to(w, pk, pn)
    # block geometry: full-K stripes; M/N tiles sized so the three VMEM
    # residents (int8 x-block + int8 w-block + int32 out-block) stay far
    # under the ~16 MB budget even at large K. Tuned per device_kind
    # through the schedule cache; default = the historical 256/256.
    tile_m, tile_n = tiles if tiles is not None else _schedule_tiles(
        pm, pk, pn)

    def kernel(x_ref, w_ref, o_ref):
        o_ref[:] = jnp.dot(x_ref[:], w_ref[:],
                           preferred_element_type=jnp.int32)

    out = pl.pallas_call(
        kernel,
        name="int8_matmul",
        grid=(pl.cdiv(pm, tile_m), pl.cdiv(pn, tile_n)),
        in_specs=[
            pl.BlockSpec((tile_m, pk), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((pk, tile_n), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((pm, pn), jnp.int32),
        interpret=interpret,
    )(xp, wp)
    return out[:m, :n]


def int8_matmul(x, w):
    """``x [M, K] int8 @ w [K, N] int8 -> [M, N] int32``.

    Dispatches to the pallas kernel on TPU when
    ``FLAGS_use_int8_matmul`` admits it; elsewhere the jnp fallback runs
    the identical int32-accumulating contraction (integer math — the
    two paths are bit-equal, asserted by tests and the quant smoke).
    """
    from ...flags import flag

    x = jnp.asarray(x)
    w = jnp.asarray(w)
    if flag("use_int8_matmul") and can_emit_mosaic() and _supported(x, w):
        return _pallas_matmul(x, w)
    return _jnp_matmul(x, w)

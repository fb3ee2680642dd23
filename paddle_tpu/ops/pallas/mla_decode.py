"""The absorbed latent-attention decode step (TPU pallas kernel).

One query a slot, ``q [B, H, W]`` (``W = rank + rope``: the latent
query and the rotated part), against that slot's latent ring, of which
only the first ``lengths[b]`` rows are live (``min(pos + 1, ring)``:
what ``generation.cache.decode_mask`` keeps). XLA's path reads every
ring whole, twice (scores, then values), whatever is live; this kernel
reads each slot's live rows once:

- grid = (slot, key block); ``lengths`` is a scalar-prefetch operand;
- a block at or past the slot's last live one maps to that last live
  block (:func:`live_blocks`), so no DMA is issued for it, and
  ``pl.when`` skips its body; inside the last live block the positions
  at or above the length are masked by an iota compare (scores to -inf,
  the piece's columns to 0, so a dead row may hold anything);
- ONE ``[W, block]`` piece of the ring in VMEM serves both products:
  scores ``q @ piece`` over all ``W`` channels, values ``p @ piece^T``
  over the same piece (the whole row summed; the caller drops the
  rotated channels after, so that no operand is a slice of the ring);
- float32 scores, running maximum, sum and accumulator across blocks
  (the flash-attention construction), probabilities rounded to the
  ring's dtype for the value product as ``nn.gqa.attend`` does, ONE
  rounding of the output at the end.

**The ring is taken transposed**, ``[B, W, ring]``: XLA:TPU keeps a
``[B, ring, W]`` array whose row width is no multiple of 128 lanes with
the positions minor (``{1,2,0:T(8,128)(2,1)}``: by its shape, not by
its readers; my AOT compile, PR 39), so ``jnp.swapaxes(ring, 1, 2)`` is
a bitcast there and the ring as it stands would be copied whole before
the call and back after it. That is the compiler's choice, not a
contract: :func:`mla_decode_supported` asks for such a width, and a
second latent configuration is compiled (``memory_analysis()``, no
``copy`` of a ring in the optimised HLO) before it is trusted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._platform import on_tpu_platform

__all__ = ["mla_decode", "mla_decode_supported", "key_block",
           "live_blocks", "rows_fetched"]

_LANES = 128
# keys a block: tried 256 / 512 / 1,024 on the chip (PERF.md, PR 39)
_BLOCK = 512
_NEG_INF = -1e30


def key_block(ring):
    """Keys a grid step takes of a ring of ``ring`` rows."""
    return min(_BLOCK, int(ring))


def live_blocks(lengths, block):
    """Blocks of ``block`` keys that hold a live row, for live lengths
    ``lengths`` (an int or an array, numpy or jax): the grid's clamp and
    the engine's ``generation::kv_rows_fetched`` both round here."""
    return -(-lengths // block)


def rows_fetched(lengths, block):
    """Ring rows the kernel brings from HBM for live lengths
    ``lengths``: whole blocks."""
    return live_blocks(lengths, block) * block


def mla_decode_supported(ring_shape, dtype) -> bool:
    """Whether the kernel takes a ``[B, ring, W]`` ring of ``dtype``:
    whole blocks of a lane multiple of keys, and a row width that is no
    multiple of 128, which is what makes XLA:TPU keep the positions
    minor and the transposed view free (the module's docstring)."""
    if len(ring_shape) != 3 or str(dtype) not in ("bfloat16", "float32"):
        return False
    ring, width = int(ring_shape[1]), int(ring_shape[2])
    block = key_block(ring)
    return (ring % block == 0 and (block % _LANES == 0 or block == ring)
            and width % _LANES != 0)


def _kernel(len_ref, q_ref, k_ref, o_ref, m_ref, l_ref, acc_ref, *, scale,
            block):
    from jax.experimental import pallas as pl

    b, j = pl.program_id(0), pl.program_id(1)
    n = len_ref[b]
    lo = j * block

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def accumulate(k, live):
        s = jnp.dot(q_ref[...], k, preferred_element_type=jnp.float32) * scale
        if live is not None:
            s = jnp.where(live, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(lo + block <= n)
    def _():
        accumulate(k_ref[...], None)

    @pl.when((lo < n) & (lo + block > n))
    def _():
        live = lo + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1) < n
        k = k_ref[...]
        accumulate(jnp.where(live, k, jnp.zeros_like(k)), live)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def mla_decode(q, ring_t, lengths, scale, block=None, interpret=None):
    """``q [B, H, W]`` against the transposed rings ``ring_t [B, W,
    ring]`` of which slot ``b`` has ``lengths[b]`` live rows (held to 1
    .. ring here, so a caller hands in ``pos + 1``): the
    softmax-weighted sum of the live rows a head, ``[B, H, W]`` in
    ``q``'s dtype. ``interpret`` defaults to "not on a TPU"."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, w = q.shape
    ring = ring_t.shape[2]
    block = key_block(ring) if block is None else int(block)
    if interpret is None:
        interpret = not on_tpu_platform()
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, ring)

    def piece(i, j, n):
        return i, 0, jnp.minimum(j, live_blocks(n[i], block) - 1)

    return pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), block=block),
        name="mla_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, ring // block),
            in_specs=[
                pl.BlockSpec((None, h, w), lambda i, j, n: (i, 0, 0)),
                pl.BlockSpec((None, w, block), piece),
            ],
            out_specs=pl.BlockSpec((None, h, w), lambda i, j, n: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, w), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, w), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, ring_t)

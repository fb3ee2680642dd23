"""Quantized (int8 + per-block scales) DP gradient all-reduce.

EQuARX-style (PAPERS.md): DP gradient sync pays full fp32 wire bytes for
values whose useful precision is far lower. This module moves gradients
across the ICI as int8 with one f32 abs-max scale per ``BLOCK``
elements (2048: larger blocks amortize scale wire bytes, smaller ones
track outliers tighter), in the classic two-phase shape:

1. **reduce-scatter phase** — each rank's quantized payload is
   ``alltoall``'d so every rank holds all n ranks' int8 contribution for
   ITS shard; it dequantizes and accumulates in f32 (no int8 overflow,
   no precision loss in the reduction itself);
2. **all-gather phase** — the f32 shard sum re-quantizes to int8 + fresh
   scales and is ``all_gather``'d, so every rank ends with the identical
   dequantized global sum.

Wire bytes per link: ``2·(n-1)/n · (B/4)·(1 + 4/block)`` — ~3.99× less
than the fp32 all-reduce's ``2·(n-1)/n · B`` at the default block of
2048 (scale overhead 0.2%). Both phases route through
:mod:`paddle_tpu.distributed.collective`, so the reduction lands in the
SAME algorithmic-bytes ledger (``collective/<prim>/traced_algo_bytes``)
and ``ici_bus_util`` gauges that certify every other collective —
``tests/test_quant_e2e.py`` asserts the ≥3.5× cut from ledger deltas,
not from a model.

Two execution paths, one accounting contract:

- **bound-axis SPMD** (inside ``shard_map``/``pmap``, the multi-
  controller deployment): the real ``lax`` collectives run.
- **single-controller / GSPMD** (eager, or a jit trace where mesh axes
  are not bound — this runtime's ShardedTrainStep, whose fp32 gradient
  sync is GSPMD-implicit): the collectives are identity transforms, so
  the path simulates exactly the numerics the SPMD program computes —
  the two quantization hops — and accounts exactly the wire bytes it
  would move (trace-time only, the ledger's standing rule; eager calls
  account nothing, as always).

The hook into training is ``sync_grads``: ``TrainStepFn``/
``ShardedTrainStep`` route gradients through it when
``FLAGS_quantized_allreduce`` is set at step CONSTRUCTION;
``tests/test_quant_e2e.py`` asserts loss-curve convergence vs fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..flags import flag
from ..framework.tensor import Tensor
from . import collective as _coll
from .collective import ReduceOp, _account, _axes, _group_size, _valid_axes

__all__ = [
    "quantize_blockwise", "dequantize_blockwise", "quantized_all_reduce",
    "sync_grads", "wire_bytes_per_step",
]

_BNT = 127.0
_EPS = 1e-8
BLOCK = 2048  # elements per quantization block (one f32 scale each)


def _block_size(override=None) -> int:
    b = int(override if override is not None else BLOCK)
    if b < 1:
        from ..errors import InvalidArgumentError

        raise InvalidArgumentError(
            f"the quantization block must be >= 1, got {b}")
    return b


def _absmax_quantize(blocks):
    """``[nblk, block]`` f32 → (int8 values, f32 per-block abs-max
    scales) — THE quantize step of both wire hops (one definition so
    the contribution and shard-sum hops can never drift numerically).
    An all-zero block quantizes against the ``1e-8`` floor instead of a
    0 scale (dequantizing by 0 is NaN/inf — same hazard the PTQ
    calibration clamps)."""
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1), _EPS)
    q = jnp.round(jnp.clip(blocks / scale[:, None] * _BNT, -_BNT, _BNT))
    return q.astype(jnp.int8), scale


def quantize_blockwise(x, block_size=None, pad_multiple=1):
    """Flatten ``x`` and quantize per block: ``(q int8 [nblk, block],
    scales f32 [nblk], meta)``.

    Blocks pad with zeros up to ``block · lcm`` so that ``nblk`` is a
    multiple of ``pad_multiple`` (the group size — both collective
    phases shard on the block axis).
    """
    x = x._array if isinstance(x, Tensor) else jnp.asarray(x)
    block = _block_size(block_size)
    n = int(x.size)
    flat = x.astype(jnp.float32).reshape(-1)
    nblk = max(1, -(-n // block))
    nblk = -(-nblk // pad_multiple) * pad_multiple
    padded = nblk * block
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    q, scale = _absmax_quantize(flat.reshape(nblk, block))
    return q, scale, (tuple(x.shape), str(x.dtype), n)


def dequantize_blockwise(q, scale, meta):
    """Inverse of :func:`quantize_blockwise` (original shape + dtype)."""
    shape, dtype, n = meta
    out = (q.astype(jnp.float32) * (scale / _BNT)[:, None]).reshape(-1)
    return out[:n].reshape(shape).astype(dtype)


def _axes_bound(axes) -> bool:
    """True when the mesh axes are BOUND in the current context
    (shard_map/pmap body) — the only place real lax collectives can
    run. Plain jit (GSPMD) and eager both raise on axis_index."""
    try:
        for ax in axes:
            jax.lax.axis_index(ax)
        return True
    except Exception:
        return False


def quantized_all_reduce(tensor, group=None, block_size=None,
                         average=False):
    """All-reduce ``tensor`` over the group's mesh axes with int8 wire
    precision (per-block f32 scales). See the module docstring for the
    two-phase shape and the accounting contract. ``average=True``
    divides the reduced SUM by the group size — only where a real sum
    happened (the bound-axis SPMD branch); on the single-controller
    identity path the global view already IS the mean, matching
    ``collective.all_reduce(op=AVG)``'s identity convention.

    Numerics: the result carries exactly two quantization roundings
    (contribution + shard-sum), each bounded by half a block step —
    convergence-neutral for DP gradient sync at int8 (asserted vs fp32
    on the BERT smoke).
    """
    arr = tensor._array if isinstance(tensor, Tensor) else jnp.asarray(tensor)
    axes = _valid_axes(_axes(group))
    n = _group_size(group)
    q, scale, meta = quantize_blockwise(arr, block_size, pad_multiple=n)
    nblk = q.shape[0]

    if n > 1 and _axes_bound(axes):
        # real SPMD wire path: alltoall the contributions, reduce the
        # local shard in f32, requantize, all-gather the shard results
        q_all = _coll.alltoall(q, group=group)
        s_all = _coll.alltoall(scale, group=group)
        parts = q_all.reshape(n, nblk // n, q.shape[1])
        scales = s_all.reshape(n, nblk // n)
        shard = jnp.sum(
            parts.astype(jnp.float32) * (scales / _BNT)[..., None], axis=0)
        sq, sscale = _absmax_quantize(shard)
        q2 = _coll.all_gather(None, sq, group=group).reshape(
            nblk, q.shape[1])
        s2 = _coll.all_gather(None, sscale, group=group).reshape(nblk)
        out = dequantize_blockwise(q2, s2, meta)
        if average:
            out = out / n
    else:
        # single-controller / GSPMD: the collectives are identity
        # transforms; compute the SAME two quantization hops the SPMD
        # program applies and account the SAME wire bytes it would move
        # (no-op _account contexts on identically-shaped payloads; the
        # ledger only records under tracing, exactly as for every other
        # collective)
        with _account("alltoall", q, group):
            pass
        with _account("alltoall", scale, group):
            pass
        shard = q.astype(jnp.float32) * (scale / _BNT)[:, None]
        sq, sscale = _absmax_quantize(shard)
        with _account("all_gather", sq[: nblk // n], group):
            pass
        with _account("all_gather", sscale[: nblk // n], group):
            pass
        out = dequantize_blockwise(sq, sscale, meta)
    if isinstance(tensor, Tensor):
        tensor._array = out
        return tensor
    return out


def sync_grads(grads, group=None, average=False, block_size=None,
               quantized=None):
    """Gradient-sync entry the train steps route through.

    ``quantized=None`` reads ``FLAGS_quantized_allreduce``; fp32 mode is
    one :func:`collective.all_reduce` per leaf (the ledger baseline the
    smoke compares against), int8 mode is :func:`quantized_all_reduce`.
    Works on any pytree of gradient arrays.
    """
    use_q = (bool(flag("quantized_allreduce")) if quantized is None
             else bool(quantized))
    if use_q:
        return jax.tree_util.tree_map(
            lambda g: quantized_all_reduce(
                g, group=group, block_size=block_size, average=average),
            grads)
    op = ReduceOp.AVG if average else ReduceOp.SUM
    return jax.tree_util.tree_map(
        lambda g: _coll.all_reduce(g, op=op, group=group), grads)


def wire_bytes_per_step(snapshot_before, snapshot_after) -> int:
    """Sum the per-execution gradient-sync wire bytes between two
    ``monitor.registry_snapshot()``s (all ``collective/*/
    traced_algo_bytes`` deltas) — the ledger arithmetic the quant smoke
    uses to certify the fp32→int8 byte cut."""
    total = 0
    for name, m in snapshot_after.items():
        if not name.endswith("/traced_algo_bytes"):
            continue
        before = snapshot_before.get(name, {}).get("value", 0)
        total += int(m["value"] - before)
    return total

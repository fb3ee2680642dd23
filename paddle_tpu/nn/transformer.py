"""Transformer stack.

Reference parity: python/paddle/nn/layer/transformer.py:67 (MultiHeadAttention),
:385/:525 (encoder), :595 (decoder). TPU-native: attention math is pure jnp —
XLA fuses the softmax chain; a pallas flash-attention kernel can be swapped
in via paddle_tpu.ops.pallas_kernels for long sequences.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import ops
from ..framework.tensor import Tensor
from . import functional as F
from .layer_base import Layer
from .layers import Dropout, LayerList, LayerNorm, Linear

# Sequence length from which use_flash_attention dispatches to the pallas
# kernel; below it XLA's fused attention is faster on TPU (measured, see
# COVERAGE.md "Flash attention"). Tests may lower it to force the kernel.
FLASH_ATTENTION_MIN_SEQ = 512


def _residual_norm(norm, residual, y):
    """Post-norm ``LayerNorm(residual + y)`` through the fused pallas
    residual-add+layernorm kernel (``FLAGS_use_fused_layernorm``) when
    the norm is a plain last-dim LayerNorm with affine params — the jnp
    fallback and the unfused path execute the identical primitive
    sequence, so this is a scheduling choice, never a numeric one."""
    from ..flags import flag

    if (flag("use_fused_layernorm") and isinstance(norm, LayerNorm)
            and norm.weight is not None and norm.bias is not None
            and len(norm.normalized_shape) == 1):
        from ..ops.pallas import layernorm_residual

        return layernorm_residual(y, residual, norm.weight, norm.bias,
                                  norm.epsilon)
    return norm(residual + y)


def _convert_attention_mask(attn_mask, dtype):
    """Normalize a mask to an ADDITIVE mask broadcastable against the
    [B, H, Lq, Lk] score tensor.

    Accepts bool masks (True = keep, paddle semantics) and additive float
    masks, at rank 2 ``[Lq, Lk]``, rank 3 ``[B, Lq, Lk]``, or rank 4
    ``[B, 1|H, Lq, Lk]`` — all composed the same way on the encoder,
    decoder, and incremental-cache paths. Rank 3 in particular would
    silently broadcast against the wrong axes if added raw to the scores
    (``[B, Lq, Lk]`` lines up as ``[1, B, Lq, Lk]``), so ranks are
    normalized here, once, instead of per call site.
    """
    if attn_mask is None:
        return None
    if attn_mask.dtype == np.bool_ or str(attn_mask.dtype) == "bool":
        # True = keep, False = mask out (paddle semantics)
        zero = ops.zeros_like(ops.cast(attn_mask, dtype))
        neg = ops.full_like(zero, -1e9)
        attn_mask = ops.where(attn_mask, zero, neg)
    else:
        attn_mask = ops.cast(attn_mask, dtype)
    if attn_mask.ndim == 2:        # [Lq, Lk] -> [1, 1, Lq, Lk]
        attn_mask = ops.unsqueeze(attn_mask, [0, 1])
    elif attn_mask.ndim == 3:      # [B, Lq, Lk] -> [B, 1, Lq, Lk]
        attn_mask = ops.unsqueeze(attn_mask, [1])
    return attn_mask


def causal_mask(length, window=None, dtype="float32"):
    """Additive ``[L, L]`` causal mask; ``window=W`` additionally masks
    keys more than ``W-1`` positions behind the query (sliding-window
    attention) — the full-sequence equivalent of decoding with a ring KV
    cache of capacity ``W``, which keeps exactly the last ``W`` tokens.
    ``window=None`` is the standard full causal mask."""
    i = np.arange(length)[:, None]
    j = np.arange(length)[None, :]
    keep = j <= i
    if window is not None:
        keep = keep & (j > i - int(window))
    from ..framework.tensor import to_tensor

    return to_tensor(np.where(keep, 0.0, -1e9).astype(dtype))


class StaticCache(NamedTuple):
    """Fixed-shape ring KV cache for ONE attention layer.

    ``k``/``v`` are ``[B, H, C, D]`` arrays (C = cache capacity) and
    ``pos`` is ``[B]`` int32 — how many tokens each row has written so
    far. Writes are FUNCTIONAL index updates (``.at[].set`` /
    ``dynamic_update_slice``), so the pytree's shapes never change
    across decode steps: one XLA program decodes forever, and once
    ``pos`` passes ``C`` the write index wraps (``pos % C``) and the
    oldest entry is overwritten — O(1) memory, compile-once decoding
    (PAPERS.md: portable O(1) autoregressive caching). Validity/window
    masking is the CALLER's job (the mask composes causal + cache-fill,
    see generation/cache.py); the layer only writes and attends.
    """

    k: Any
    v: Any
    pos: Any


class ContinuedCache(StaticCache):
    """A :class:`StaticCache` as a prompt's CHUNK is handed it: the ring
    holds the prompt's rows below ``pos``, each where its position says,
    and the chunk's tokens stand at ``pos`` onwards. A layer that is
    given more than one token with it attends the ring's rows and the
    chunk's own and writes the chunk's in from there, where with a plain
    :class:`StaticCache` it fills fresh rings from position 0."""

    __slots__ = ()


class RecurrentCache(NamedTuple):
    """Per-slot state of ONE linear-attention layer: what a recurrence
    keeps in place of a growing K/V window.

    ``state`` is ``[B, H, Dk, Dv]`` float32 (the running outer-product
    memory of :class:`nn.GatedDeltaAttention`), ``conv_tail`` is ``[B,
    K-1, channels]``: the last ``K-1`` inputs of the layer's short
    causal convolution, and ``pos`` is the same shared ``[B]`` vector
    every layer's cache carries. No leaf has a cache-length axis: a slot
    costs the same bytes at any context length. A decode step reads and
    rewrites ``state`` whole; a prefill yields the state after the
    prompt's last REAL token (right-padding does not advance it).
    """

    state: Any
    conv_tail: Any
    pos: Any


class StateCache(NamedTuple):
    """Per-slot state of ONE recurrent layer that keeps nothing else
    (:class:`nn.LightningAttention`: no convolution, so no tail):
    ``state [B, H, Dk, Dv]`` float32 and the shared ``pos [B]``."""

    state: Any
    pos: Any


class LatentCache(NamedTuple):
    """Ring cache of ONE latent-attention layer: ``c`` is ``[B, C, rank
    + rope]``, one row a token and no head axis: the normalised latent
    every head's key and value are made from, then the rotated key
    channels all heads share. Ring semantics, functional writes and the
    caller-owned mask are :class:`StaticCache`'s; ``pos`` is the same
    shared ``[B]`` vector."""

    c: Any
    pos: Any


class QuantizedStaticCache(NamedTuple):
    """:class:`StaticCache` at int8 storage with per-head dynamic scales.

    ``k``/``v`` are int8 ``[B, H, C, D]``; ``k_scale``/``v_scale`` are
    f32 ``[B, H, C]`` — one abs-max scale per written head-vector,
    computed DYNAMICALLY at ring-write time (no calibration pass: each
    K/V row quantizes against its own magnitude, so attention sinks and
    outlier heads never clip the rest of the cache). The attention read
    dequantizes the full static window (``q · scale/127``) before the
    score matmul — decode HBM traffic drops to ~(D+4)/(4·D) of the f32
    cache (3.8× at head_dim 64), which is what lets the same HBM hold
    ~2× the decode slots (``FLAGS_generation_kv_cache_dtype=int8``).

    Ring semantics, functional updates, and the caller-owned mask
    contract are exactly :class:`StaticCache`'s; parity vs the full
    f32 forward holds at the int8 envelope documented in README
    "Quantization" (goldens in tests/test_quantization.py).
    """

    k: Any
    v: Any
    k_scale: Any
    v_scale: Any
    pos: Any


class PagedStaticCache(NamedTuple):
    """:class:`StaticCache` semantics over a PAGE-POOL layout.

    ``k``/``v`` are ``[P, H, ps, D]`` — the whole shared pool of ``P``
    physical pages (``ps`` tokens each) for ONE layer, not one slot's
    ring. ``table`` is ``[B, NP]`` int32: row ``b`` maps that slot's
    ``NP`` logical ring pages to physical pool pages, and ``pos`` is the
    shared ``[B]`` position vector. The LOGICAL cache is the exact same
    ring the contiguous cache implements — entry index ``pos % (NP*ps)``
    splits into logical page ``idx // ps`` and offset ``idx % ps``, so
    every mask (decode/prefill/verify) and the wraparound contract carry
    over unchanged, and greedy output is token-identical to the ring
    layout by construction.

    Writes scatter through the table (a functional ``.at[phys, :, off,
    :].set``); reads gather ``k[table]`` back into the contiguous
    ``[B, H, NP*ps, D]`` window the score matmul expects. Page
    ALLOCATION is host-side bookkeeping between steps
    (:mod:`paddle_tpu.generation.paging`): physical page 0 is reserved
    as the trash page — vacant slots and unallocated logical pages point
    at it, absorbing writes that the ring layout would make into a
    vacant slot's own storage. The pool owner guarantees every page a
    busy slot is about to write is PRIVATE (refcount 1); shared prefix
    pages are remapped copy-on-write before the step.
    """

    k: Any
    v: Any
    table: Any
    pos: Any


class QuantizedPagedCache(NamedTuple):
    """:class:`PagedStaticCache` at int8 storage: int8 ``k``/``v``
    ``[P, H, ps, D]`` plus f32 per-head dynamic scale pools
    ``k_scale``/``v_scale`` ``[P, H, ps]`` — :class:`QuantizedStaticCache`'s
    quantize-on-write / dequantize-on-read contract through the same
    page-table indirection."""

    k: Any
    v: Any
    k_scale: Any
    v_scale: Any
    table: Any
    pos: Any


#: int8 grid half-width for KV-cache quantization
KV_QUANT_BNT = 127.0
#: scale floor: an all-zero head-vector must not dequantize as NaN
KV_QUANT_EPS = 1e-8


def update_slice_in_range(operand, update, *start):
    """``lax.dynamic_update_slice`` for start indices known to lie in
    range, given one per axis as scalars of one integer type: the primitive bound
    directly. The wrapper spends about 3.5 ms of trace time a call on
    normalising indices that might be negative, and the cache programs
    trace one of these per layer and plane (admission) or per row
    (decode)."""
    return jax.lax.dynamic_update_slice_p.bind(operand, update, *start)


def _write_rows(cache, new, idx):
    """The decode step's ring write: row ``b`` of ``new`` (``[B, H, 1,
    D]`` values or ``[B, H, 1]`` scales) goes to ring index ``idx[b]``
    of ``cache`` (``[B, H, C, D]`` / ``[B, H, C]``). One
    ``dynamic_update_slice`` per row, unrolled: it works in whatever
    layout the compiler gave the array, so a donated cache is updated
    in place. One scatter over all rows is the same mathematics, but
    XLA:TPU keeps the cache with ``C`` minor and wants ``D`` minor for
    the scatter: it copies the whole array there and back, every layer
    and step; a ``fori_loop`` over the rows moves the array into and
    out of fast memory around the loop (PERF.md, PR 26)."""
    zero = np.zeros((), idx.dtype)
    tail = (zero,) * (cache.ndim - 3)
    for b in range(cache.shape[0]):
        cache = update_slice_in_range(
            cache, jax.lax.slice_in_dim(new, b, b + 1),
            np.asarray(b, idx.dtype), zero,
            jax.lax.index_in_dim(idx, b, keepdims=False), *tail)
    return cache


def quantize_kv(x):
    """``[..., D]`` float → (int8 values, f32 abs-max scales ``[...]``).

    One dynamic scale per trailing vector (per head per cache entry) —
    the quantize-on-ring-write half of the int8 KV cache.
    """
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), KV_QUANT_EPS)
    q = jnp.round(jnp.clip(x / scale[..., None] * KV_QUANT_BNT,
                           -KV_QUANT_BNT, KV_QUANT_BNT))
    return q.astype(jnp.int8), scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv` — the attention-read half."""
    return q.astype(dtype) * (scale[..., None] / KV_QUANT_BNT).astype(dtype)


class MultiHeadAttention(Layer):
    """Scaled dot-product multi-head attention (transformer.py:67)."""

    Cache = tuple  # (k, v)

    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None, vdim=None,
                 need_weights=False, weight_attr=None, bias_attr=None,
                 use_ring_attention=False, use_flash_attention=False,
                 use_ulysses_attention=False):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.dropout = dropout
        self.need_weights = need_weights
        # TPU extensions: sequence-parallel attention over the sp mesh axis
        # — ring (parallel/ring_attention.py) or Ulysses all-to-all
        # (parallel/ulysses.py) — and the fused pallas flash kernel
        # (ops/pallas/flash_attention.py). Flash supports attention dropout
        # (in-kernel TPU PRNG); ring/Ulysses require dropout == 0.
        self.use_ring_attention = use_ring_attention
        self.use_ulysses_attention = use_ulysses_attention
        if use_ring_attention and use_ulysses_attention:
            raise ValueError("pick ONE sp attention mode: ring or ulysses")
        self.use_flash_attention = use_flash_attention
        if (use_ring_attention or use_ulysses_attention) and dropout:
            raise ValueError(
                "sequence-parallel attention (ring/ulysses) does not "
                "support attn dropout"
            )
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim
        kdim = kdim or embed_dim
        vdim = vdim or embed_dim
        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _shape(self, x):
        # [B, L, E] -> [B, H, L, D]
        b, l = x.shape[0], x.shape[1]
        x = ops.reshape(x, [b, l, self.num_heads, self.head_dim])
        return ops.transpose(x, [0, 2, 1, 3])

    def forward(self, query, key=None, value=None, attn_mask=None, cache=None):
        key = query if key is None else key
        value = key if value is None else value
        q = self._shape(self.q_proj(query))
        k = self._shape(self.k_proj(key))
        v = self._shape(self.v_proj(value))
        if isinstance(cache, (StaticCache, QuantizedStaticCache,
                              PagedStaticCache, QuantizedPagedCache)):
            # incremental path: write the new K/V into the ring cache by
            # functional index update, then attend over the FULL static
            # window — shapes never change across steps, so a jitted
            # decode step compiles exactly once (the caller's mask hides
            # not-yet-written entries). The quantized cache writes int8
            # + per-head scales and hands back the dequantized window;
            # the paged caches route the same logical ring indices
            # through a per-slot page table into a shared pool.
            k, v, new_cache = self._update_static_cache(cache, k, v)
        elif cache is not None:
            pk, pv = cache
            k = ops.concat([pk, k], axis=2)
            v = ops.concat([pv, v], axis=2)
            new_cache = (k, v)

        scale = float(self.head_dim) ** -0.5
        mask_ring_ok = attn_mask is None or (
            attn_mask.ndim == 4 and attn_mask.shape[-2] == 1
        )  # ring rotation supports only K-dim [B,1,1,L] masks
        if (self.use_ring_attention and not self.need_weights
                and cache is None and mask_ring_ok):
            from ..parallel.ring_attention import ring_attention

            mask = _convert_attention_mask(attn_mask, q.dtype)
            out = ring_attention(q, k, v, mask=mask, scale=scale)
        elif (self.use_ulysses_attention and not self.need_weights
                and cache is None and mask_ring_ok):
            from ..parallel.ulysses import ulysses_attention

            mask = _convert_attention_mask(attn_mask, q.dtype)
            out = ulysses_attention(q, k, v, mask=mask, scale=scale)
        elif (self.use_flash_attention and not self.need_weights
                and cache is None
                and k.shape[2] >= FLASH_ATTENTION_MIN_SEQ):
            # Pallas flash kernel: wins once the [L, L] score tiles stop
            # fitting XLA's fused-attention working set (measured on v5e:
            # >=1.5x at L=512+, but 0.8x at L=128 where XLA's batched
            # fusion is already optimal — see COVERAGE.md "Flash
            # attention"). Below the threshold the XLA path runs, so the
            # flag is always safe to enable.
            from ..ops.pallas import flash_attention

            mask = _convert_attention_mask(attn_mask, q.dtype)
            out = flash_attention(
                q, k, v, bias=mask, scale=scale,
                dropout_rate=self.dropout if self.training else 0.0,
            )
        else:
            if self.use_ring_attention or self.use_ulysses_attention:
                # an sp mode was requested but the call shape ruled it out
                # (need_weights / incremental cache / Lq>1 mask): record
                # the fallback so harness asserts can't false-pass on a
                # stale "sharded" entry
                from ..parallel.ring_attention import LAST_DISPATCH

                LAST_DISPATCH.clear()
                LAST_DISPATCH.update(
                    op=("ring_attention" if self.use_ring_attention
                        else "ulysses_attention"),
                    mode="fallback", axis_size=0,
                )
            scores = ops.matmul(q, k, transpose_y=True) * scale
            mask = _convert_attention_mask(attn_mask, q.dtype)
            if mask is not None:
                scores = scores + mask
            weights = F.softmax(scores, axis=-1)
            if self.dropout:
                weights = F.dropout(weights, p=self.dropout, training=self.training)
            out = ops.matmul(weights, v)  # [B, H, L, D]
        out = ops.transpose(out, [0, 2, 1, 3])
        b, l = out.shape[0], out.shape[1]
        out = ops.reshape(out, [b, l, self.embed_dim])
        out = self.out_proj(out)

        results = [out]
        if self.need_weights:
            results.append(weights)
        if cache is not None:
            results.append(new_cache)
        return out if len(results) == 1 else tuple(results)

    def gen_cache(self, key, value=None, type=None):
        b = key.shape[0]
        k = ops.zeros([b, self.num_heads, 0, self.head_dim], key.dtype)
        v = ops.zeros([b, self.num_heads, 0, self.head_dim], key.dtype)
        return (k, v)

    def gen_static_cache(self, batch, cache_len, dtype="float32"):
        """A zeroed :class:`StaticCache` of capacity ``cache_len``."""
        shape = (int(batch), self.num_heads, int(cache_len), self.head_dim)
        return StaticCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                           jnp.zeros((int(batch),), jnp.int32))

    def _update_static_cache(self, cache, k, v):
        """Write the freshly projected K/V into the ring cache.

        Decode (Lq == 1): every row writes its own ring index
        ``pos % C``, one ``dynamic_update_slice`` per row
        (:func:`_write_rows`), so co-batched sequences at different
        positions share one program and a donated cache is written
        where it lies. Multi-token (Lq > 1,
        prefill and speculative verify): each row writes its span at
        its OWN offset ``(pos + t) % C`` — the same batched scatter
        over a ``[B, T]`` index plane, so per-slot positions may differ
        and the span may wrap the ring (the verify step's window-exact
        in-place write; see generation/cache.py "store vs window").
        """
        if isinstance(cache, QuantizedStaticCache):
            return self._update_quantized_cache(cache, k, v)
        if isinstance(cache, (PagedStaticCache, QuantizedPagedCache)):
            return self._update_paged_cache(cache, k, v)
        kc, vc, pos = cache
        kn = k._array if isinstance(k, Tensor) else jnp.asarray(k)
        vn = v._array if isinstance(v, Tensor) else jnp.asarray(v)
        kn = kn.astype(kc.dtype)
        vn = vn.astype(vc.dtype)
        c = kc.shape[2]
        if kn.shape[2] == 1:
            idx = jnp.mod(pos, c)
            kc = _write_rows(kc, kn, idx)
            vc = _write_rows(vc, vn, idx)
        else:
            t = kn.shape[2]
            rows = jnp.arange(kc.shape[0])[:, None]
            idx = jnp.mod(pos[:, None] + jnp.arange(t)[None, :], c)
            # advanced indices split by the H slice put the [B, T] index
            # dims first, so the payload transposes to [B, T, H, D]
            kc = kc.at[rows, :, idx, :].set(jnp.moveaxis(kn, 2, 1))
            vc = vc.at[rows, :, idx, :].set(jnp.moveaxis(vn, 2, 1))
        return (Tensor._from_array(kc), Tensor._from_array(vc),
                StaticCache(kc, vc, pos))

    def _update_quantized_cache(self, cache, k, v):
        """Int8 twin of :meth:`_update_static_cache`.

        The fresh K/V projections quantize per head-vector (one dynamic
        abs-max scale each, :func:`quantize_kv`) before the ring write —
        int8 values and f32 scales land at the same ring index the f32
        cache would write. The attention read then dequantizes the FULL
        window: masked (never-written / stale) entries dequantize to
        whatever garbage they hold, exactly as in the f32 cache, and the
        caller's mask hides them.
        """
        kc, vc, ks, vs, pos = cache
        kn = k._array if isinstance(k, Tensor) else jnp.asarray(k)
        vn = v._array if isinstance(v, Tensor) else jnp.asarray(v)
        out_dtype = kn.dtype
        kq, ksc = quantize_kv(kn)
        vq, vsc = quantize_kv(vn)
        c = kc.shape[2]
        if kn.shape[2] == 1:
            idx = jnp.mod(pos, c)
            kc = _write_rows(kc, kq, idx)
            vc = _write_rows(vc, vq, idx)
            ks = _write_rows(ks, ksc, idx)
            vs = _write_rows(vs, vsc, idx)
        else:
            t = kn.shape[2]
            rows = jnp.arange(kc.shape[0])[:, None]
            idx = jnp.mod(pos[:, None] + jnp.arange(t)[None, :], c)
            kc = kc.at[rows, :, idx, :].set(jnp.moveaxis(kq, 2, 1))
            vc = vc.at[rows, :, idx, :].set(jnp.moveaxis(vq, 2, 1))
            ks = ks.at[rows, :, idx].set(jnp.moveaxis(ksc, 2, 1))
            vs = vs.at[rows, :, idx].set(jnp.moveaxis(vsc, 2, 1))
        kf = dequantize_kv(kc, ks, out_dtype)
        vf = dequantize_kv(vc, vs, out_dtype)
        return (Tensor._from_array(kf), Tensor._from_array(vf),
                QuantizedStaticCache(kc, vc, ks, vs, pos))

    @staticmethod
    def _paged_indices(table, pos, t, store, ps):
        """Physical (page, offset) coordinates for a ``t``-token write
        starting at each row's ``pos`` — the logical ring index
        ``(pos + j) % store`` split into the table lookup."""
        if t == 1:
            idx = jnp.mod(pos, store)
            rows = jnp.arange(table.shape[0])
            return table[rows, idx // ps], jnp.mod(idx, ps)
        idx = jnp.mod(pos[:, None] + jnp.arange(t)[None, :], store)
        rows = jnp.arange(table.shape[0])[:, None]
        return table[rows, idx // ps], jnp.mod(idx, ps)

    def _update_paged_cache(self, cache, k, v):
        """Paged twin of :meth:`_update_static_cache`: the identical
        logical ring write/read, with the page table translating logical
        pages to shared-pool pages. The write scatters into the pool
        (the pool owner pre-guarantees written pages are private — CoW
        happened host-side before this step); the read gathers each
        row's ``NP`` pages back into the contiguous ``[B, H, NP*ps, D]``
        window so the attention math — and hence the numerics — is
        byte-identical to the ring layout's."""
        quant = isinstance(cache, QuantizedPagedCache)
        if quant:
            kc, vc, ks, vs, table, pos = cache
        else:
            kc, vc, table, pos = cache
        kn = k._array if isinstance(k, Tensor) else jnp.asarray(k)
        vn = v._array if isinstance(v, Tensor) else jnp.asarray(v)
        out_dtype = kn.dtype
        ps = kc.shape[2]
        b, np_ = table.shape
        store = np_ * ps
        t = kn.shape[2]
        phys, off = self._paged_indices(table, pos, t, store, ps)
        if quant:
            kq, ksc = quantize_kv(kn)
            vq, vsc = quantize_kv(vn)
            if t == 1:
                kc = kc.at[phys, :, off, :].set(kq[:, :, 0, :])
                vc = vc.at[phys, :, off, :].set(vq[:, :, 0, :])
                ks = ks.at[phys, :, off].set(ksc[:, :, 0])
                vs = vs.at[phys, :, off].set(vsc[:, :, 0])
            else:
                kc = kc.at[phys, :, off, :].set(jnp.moveaxis(kq, 2, 1))
                vc = vc.at[phys, :, off, :].set(jnp.moveaxis(vq, 2, 1))
                ks = ks.at[phys, :, off].set(jnp.moveaxis(ksc, 2, 1))
                vs = vs.at[phys, :, off].set(jnp.moveaxis(vsc, 2, 1))
        else:
            kn = kn.astype(kc.dtype)
            vn = vn.astype(vc.dtype)
            if t == 1:
                kc = kc.at[phys, :, off, :].set(kn[:, :, 0, :])
                vc = vc.at[phys, :, off, :].set(vn[:, :, 0, :])
            else:
                kc = kc.at[phys, :, off, :].set(jnp.moveaxis(kn, 2, 1))
                vc = vc.at[phys, :, off, :].set(jnp.moveaxis(vn, 2, 1))
        # gather the per-row window: [B, NP, H, ps, D] -> [B, H, NP*ps, D]
        h, d = kc.shape[1], kc.shape[3]

        def window(pool):
            g = pool[table]
            return jnp.transpose(g, (0, 2, 1, 3, 4)).reshape(
                b, h, store, d)

        if quant:
            def swindow(spool):
                g = spool[table]  # [B, NP, H, ps]
                return jnp.transpose(g, (0, 2, 1, 3)).reshape(b, h, store)

            kw = dequantize_kv(window(kc), swindow(ks), out_dtype)
            vw = dequantize_kv(window(vc), swindow(vs), out_dtype)
            new = QuantizedPagedCache(kc, vc, ks, vs, table, pos)
        else:
            kw = window(kc).astype(out_dtype)
            vw = window(vc).astype(out_dtype)
            new = PagedStaticCache(kc, vc, table, pos)
        return Tensor._from_array(kw), Tensor._from_array(vw), new


class TransformerEncoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1, activation="relu",
                 attn_dropout=None, act_dropout=None, normalize_before=False,
                 weight_attr=None, bias_attr=None, use_flash_attention=False,
                 sp_attention="none"):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        if sp_attention not in ("none", "ring", "ulysses"):
            raise ValueError(f"sp_attention must be none|ring|ulysses, "
                             f"got {sp_attention!r}")
        self.self_attn = MultiHeadAttention(d_model, nhead, dropout=attn_dropout,
                                            weight_attr=weight_attr, bias_attr=bias_attr,
                                            use_flash_attention=use_flash_attention,
                                            use_ring_attention=sp_attention == "ring",
                                            use_ulysses_attention=sp_attention == "ulysses")
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.activation = getattr(F, activation)

    def forward(self, src, src_mask=None, cache=None):
        residual = src
        if self.normalize_before:
            src = self.norm1(src)
        if cache is None:
            src = self.self_attn(src, src, src, src_mask)
        else:
            src, new_cache = self.self_attn(src, src, src, src_mask, cache)
        if self.normalize_before:
            src = residual + self.dropout1(src)
        else:
            src = _residual_norm(self.norm1, residual, self.dropout1(src))

        residual = src
        if self.normalize_before:
            src = self.norm2(src)
        src = self.linear2(self.dropout(self.activation(self.linear1(src))))
        if self.normalize_before:
            src = residual + self.dropout2(src)
        else:
            src = _residual_norm(self.norm2, residual, self.dropout2(src))
        return src if cache is None else (src, new_cache)


class TransformerEncoder(Layer):
    def __init__(self, encoder_layer, num_layers, norm=None):
        super().__init__()
        import copy

        self.layers = LayerList([encoder_layer] + [copy.deepcopy(encoder_layer) for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, src, src_mask=None):
        out = src
        for layer in self.layers:
            out = layer(out, src_mask)
        if self.norm is not None:
            out = self.norm(out)
        return out


class TransformerDecoderLayer(Layer):
    """Decoder block: self-attention (+ optional cross-attention) + FFN.

    ``with_cross_attention=False`` builds a decoder-ONLY block (GPT
    style): no cross-attention parameters exist at all — not merely
    skipped, so the functional state stays free of zombie weights — and
    ``memory`` may be omitted at call time.
    """

    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1, activation="relu",
                 attn_dropout=None, act_dropout=None, normalize_before=False,
                 weight_attr=None, bias_attr=None, with_cross_attention=True):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, dropout=attn_dropout,
                                            weight_attr=weight_attr, bias_attr=bias_attr)
        if with_cross_attention:
            self.cross_attn = MultiHeadAttention(d_model, nhead, dropout=attn_dropout,
                                                 weight_attr=weight_attr, bias_attr=bias_attr)
            self.norm2 = LayerNorm(d_model)
            self.dropout2 = Dropout(dropout)
        else:
            self.cross_attn = None
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout3 = Dropout(dropout)
        self.activation = getattr(F, activation)

    def forward(self, tgt, memory=None, tgt_mask=None, memory_mask=None, cache=None):
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if cache is None:
            tgt = self.self_attn(tgt, tgt, tgt, tgt_mask)
        else:
            tgt, new_cache = self.self_attn(tgt, tgt, tgt, tgt_mask, cache)
        if self.normalize_before:
            tgt = residual + self.dropout1(tgt)
        else:
            tgt = _residual_norm(self.norm1, residual, self.dropout1(tgt))

        if self.cross_attn is not None:
            if memory is None:
                raise ValueError(
                    "this TransformerDecoderLayer was built with cross-"
                    "attention; pass memory (or build it with "
                    "with_cross_attention=False for decoder-only use)")
            residual = tgt
            if self.normalize_before:
                tgt = self.norm2(tgt)
            tgt = self.cross_attn(tgt, memory, memory, memory_mask)
            if self.normalize_before:
                tgt = residual + self.dropout2(tgt)
            else:
                tgt = _residual_norm(self.norm2, residual,
                                     self.dropout2(tgt))

        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = self.linear2(self.dropout(self.activation(self.linear1(tgt))))
        if self.normalize_before:
            tgt = residual + self.dropout3(tgt)
        else:
            tgt = _residual_norm(self.norm3, residual, self.dropout3(tgt))
        return tgt if cache is None else (tgt, new_cache)


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        import copy

        self.layers = LayerList([decoder_layer] + [copy.deepcopy(decoder_layer) for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None):
        out = tgt
        for layer in self.layers:
            out = layer(out, memory, tgt_mask, memory_mask)
        if self.norm is not None:
            out = self.norm(out)
        return out


class Transformer(Layer):
    """Full encoder-decoder transformer (transformer.py Transformer class)."""

    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6, num_decoder_layers=6,
                 dim_feedforward=2048, dropout=0.1, activation="relu",
                 attn_dropout=None, act_dropout=None, normalize_before=False,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            enc_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before)
            enc_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(enc_layer, num_encoder_layers, enc_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            dec_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before)
            dec_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(dec_layer, num_decoder_layers, dec_norm)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None, memory_mask=None):
        memory = self.encoder(src, src_mask)
        return self.decoder(tgt, memory, tgt_mask, memory_mask)

    @staticmethod
    def generate_square_subsequent_mask(length):
        mask = np.triu(np.full((length, length), -1e9, np.float32), k=1)
        from ..framework.tensor import to_tensor

        return to_tensor(mask)

"""Cached grouped-query softmax attention for decoder layers whose
models hand the generation engine a per-layer list of cache kinds
(``generation/cache.py``): one layer with the options the served
families differ in.

- ``gated``: the output is multiplied elementwise by ``sigmoid(x Wg)``;
- ``qk_norm``: q and k are RMS-normalised over the head dimension, each
  with a learned gain, before anything else is done to them;
- ``rope_theta``: q and k are rotated by their absolute position (full
  rotary over the head dimension, half-split pairing, ``theta_i =
  rope_theta^(-2i/head_dim)``); ``None`` gives no position signal;
- ``window``: query ``t`` sees keys ``t-window+1 .. t`` and the layer's
  ring is ``window`` rows long (``cache.kv(heads, head_dim, window)``);
  ``None`` is causal over whatever the ring holds;
- ``key_chunk``: a prefill block's keys are taken this many at a time
  and the parts joined by their running maxima and sums (the softmax of
  the whole row, computed in pieces): XLA:TPU's row reductions over
  4,300-8,192 float32 scores run 40 x slower than over 4,096 (my chip
  run, PR 33: 23.5 ms against 0.6 for a block of 128 queries), and a
  block's score tensor stays ``[heads, block, key_chunk]``.

A key is rotated before it is written, so a ring row keeps its absolute
position through any number of wraps, and the row of position ``p`` lies
at ``p mod ring``. With a cache, one token a row is a decode step
(``mask`` the additive ``[B, 1, 1, ring]`` decode mask, or ``{ring
length: mask}`` where a model's rings differ); more than one is a
prefill from position 0 into fresh caches by query blocks, ``mask`` then
the additive key-padding mask ``[B, 1, 1, T]``. A window layer's block
attends its own keys and the ``window - 1`` before them, so its cost is
linear in the prompt, and its ring ends up holding the last ``min(length,
window)`` real rows. Softmax and norm statistics are float32.

More than one token with a :class:`ContinuedCache` is a prompt's CHUNK:
the ring holds the prompt's rows below ``cache.pos`` (``lo``), the
queries stand at ``lo .. lo + T - 1`` (``positions`` says so to a rotary
layer) and ``mask`` is the chunk's own key-padding mask. A full layer
attends ring rows ``0 .. lo`` by key chunks, none that lies wholly
beyond ``lo`` read, then its own rows causally; a window layer takes the
ring's rows out in position order, puts the chunk's behind them and
bands the lot as a prefill is banded. The chunk's rows then go in at
``position mod ring``. Whatever a ring row at or beyond ``lo`` held
before (a decode step that ran over the half-filled slot wrote one) is
never read: a full ring's is overwritten first, and a window ring's row
of ``lo`` held position ``lo - ring``, which the band leaves out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.tensor import Parameter
from .layer_base import Layer
from .linear_attention import normal_or_zeros
from .transformer import (ContinuedCache, StaticCache, _write_rows,
                          update_slice_in_range)

__all__ = ["CachedGQAttention", "rms_norm", "apply_rotary", "attend",
           "attend_by_chunks", "attend_keys", "attend_causal_blocks",
           "attend_continued"]

_NEG_INF = -1e9


def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def apply_rotary(x, positions, theta, interleaved=False):
    """``x [B, T, ..., D]`` rotated by ``positions [B, T]``: channel
    ``i < D/2`` pairs with ``i + D/2``, or, ``interleaved``, channel
    ``2i`` with ``2i + 1``; the angle of pair ``i`` is ``position x
    theta^(-2i/D)``; float32 angles and products. A layer that rotates a
    part of its head hands that part in."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * freq   # [B, T, D/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleaved:
        x32 = x.astype(jnp.float32)
        x1, x2 = x32[..., 0::2], x32[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def attend(q, k, v, bias, scale):
    """``q [B, Hkv, G, Tq, D]`` against ``k [B, Hkv, Tk, D]`` and ``v
    [B, Hkv, Tk, Dv]`` under the additive ``bias`` (broadcast to ``[B,
    Hkv, G, Tq, Tk]``): softmax in float32."""
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q, k,
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(s * scale + bias, axis=-1)
    return jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v)


def attend_by_chunks(q, k, v, bias, scale, key_chunk):
    """:func:`attend` with the keys ``key_chunk`` at a time: per chunk
    the scores' row maxima ``m``, ``exp(s - m)`` summed, and its product
    with ``v`` in float32; the chunks are then weighted by ``exp(m - max
    m)``. A chunk a row sees nothing of weighs nothing (its maximum is
    the mask's -1e9)."""
    parts = []
    for k0 in range(0, k.shape[2], key_chunk):
        k1 = min(k0 + key_chunk, k.shape[2])
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q, k[:, :, k0:k1],
                       preferred_element_type=jnp.float32)
        s = s * scale + bias[..., k0:k1]
        m = s.max(-1, keepdims=True)
        p = jnp.exp(s - m)
        parts.append((m, p.sum(-1, keepdims=True), jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(v.dtype), v[:, :, k0:k1],
            preferred_element_type=jnp.float32)))
    top = parts[0][0]
    for m, _, _ in parts[1:]:
        top = jnp.maximum(top, m)
    total = sum(jnp.exp(m - top) * l for m, l, _ in parts)
    out = sum(jnp.exp(m - top) * o for m, _, o in parts)
    return (out / total).astype(v.dtype)


def attend_keys(q, k, v, bias, scale, key_chunk=None):
    """:func:`attend`, by chunks where there are more than ``key_chunk``
    keys."""
    if key_chunk is None or k.shape[2] <= key_chunk:
        return attend(q, k, v, bias, scale)
    return attend_by_chunks(q, k, v, bias, scale, key_chunk)


def attend_causal_blocks(q, k, v, mask, scale, block, key_chunk=None,
                         window=None, offset=0):
    """A whole sequence from position 0, by blocks of ``block`` queries:
    block i sees keys 0 .. its own end (with ``window``: from ``window -
    1`` before its start), so no score tensor is larger than ``[heads,
    block, T]`` and half of them are never formed. ``q [B, Hkv, G, T,
    D]``, ``k [B, Hkv, T, D]``, ``v [B, Hkv, T, Dv]``; ``mask`` is the
    additive key-padding mask ``[B, 1, 1, T]`` or None. Returns ``[B,
    Hkv, G, T, Dv]``. With ``offset`` the keys begin that many rows
    before the queries (``k``, ``v`` and ``mask`` are ``offset + T``
    long): query i is the sequence's row ``offset + i``."""
    t, w = q.shape[3], window
    pad = 0.0 if mask is None else mask[:, :, None]      # [B,1,1,1,T]
    blocks = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        k0 = 0 if w is None else max(lo + offset - w + 1, 0)
        rows = jnp.arange(lo + offset, hi + offset)[:, None]
        hi += offset
        cols = jnp.arange(k0, hi)[None, :]
        keep = rows >= cols
        if w is not None:
            keep = keep & (rows - cols < w)
        bias = jnp.where(keep, 0.0, _NEG_INF) \
            + (pad[..., k0:hi] if mask is not None else 0.0)
        blocks.append(attend_keys(
            q[..., lo:hi - offset, :], k[:, :, k0:hi], v[:, :, k0:hi], bias,
            scale, key_chunk))
    return jnp.concatenate(blocks, axis=3)


def _join(part, q, k, v, bias, scale):
    """One more piece of keys into a running softmax: ``part`` is the
    rows' maxima, sums and weighted values so far (float32)."""
    m, total, out = part
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q, k,
                   preferred_element_type=jnp.float32) * scale + bias
    top = jnp.maximum(m, s.max(-1, keepdims=True))
    p, old = jnp.exp(s - top), jnp.exp(m - top)
    return (top, old * total + p.sum(-1, keepdims=True),
            old * out + jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32))


def attend_continued(q, k, v, kc, vc, lo, mask, scale, block,
                     key_chunk=None):
    """A prompt's chunk against a ring that holds the prompt's rows
    below ``lo [B]`` where their positions say (no wrap: a prompt fits
    its full ring): ``q [B, Hkv, G, T, D]`` stands at ``lo .. lo + T -
    1``, ``k`` / ``v [B, Hkv, T, D]`` are the chunk's own rows, ``kc`` /
    ``vc [B, Hkv, ring, D]`` the ring, ``mask`` the chunk's additive
    key-padding mask ``[B, 1, 1, T]`` or None. The ring is read
    ``key_chunk`` rows at a time as far as ``lo`` reaches and no
    further, the chunk's own rows causally, by blocks of ``block``
    queries, all joined as one softmax: no score tensor is larger than
    ``[heads, block, key_chunk]``."""
    b, hkv, g, t, d = q.shape
    ring = kc.shape[2]
    step = min(ring, key_chunk or ring)
    lo = lo.astype(jnp.int32)
    spans = [(s, min(s + block, t)) for s in range(0, t, block)]
    parts = tuple(
        (jnp.full((b, hkv, g, e - s, 1), -1e30, jnp.float32),
         jnp.zeros((b, hkv, g, e - s, 1), jnp.float32),
         jnp.zeros((b, hkv, g, e - s, v.shape[-1]), jnp.float32))
        for s, e in spans)

    def past(i, parts):
        # the last piece of a ring that is no whole number of them
        # begins early: rows a piece before it had are masked
        k0 = jnp.minimum(i * step, ring - step)
        ks = jax.lax.dynamic_slice_in_dim(kc, k0, step, axis=2)
        vs = jax.lax.dynamic_slice_in_dim(vc, k0, step, axis=2)
        p = k0 + jnp.arange(step, dtype=jnp.int32)[None]
        bias = jnp.where((p >= i * step) & (p < lo[:, None]), 0.0,
                         _NEG_INF).astype(jnp.float32)[:, None, None, None]
        return tuple(_join(part, q[..., s:e, :], ks, vs, bias, scale)
                     for (s, e), part in zip(spans, parts))

    parts = jax.lax.fori_loop(0, (lo.max() + step - 1) // step, past, parts)
    pad = 0.0 if mask is None else mask[:, :, None]
    blocks = []
    for (s, e), part in zip(spans, parts):
        rows = jnp.arange(s, e)[:, None]
        for c0 in range(0, e, step):
            c1 = min(c0 + step, e)
            bias = jnp.where(rows >= jnp.arange(c0, c1)[None], 0.0,
                             _NEG_INF).astype(jnp.float32) \
                + (pad[..., c0:c1] if mask is not None else 0.0)
            part = _join(part, q[..., s:e, :], k[:, :, c0:c1], v[:, :, c0:c1],
                         bias, scale)
        blocks.append((part[2] / part[1]).astype(v.dtype))
    return jnp.concatenate(blocks, axis=3)


class CachedGQAttention(Layer):
    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 gated=False, qk_norm=False, rope_theta=None, window=None,
                 prefill_block=512, key_chunk=None, norm_eps=1e-5,
                 initializer_range=0.02, dtype="float32"):
        super().__init__()
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim, self.gated = int(head_dim), bool(gated)
        self.qk_norm, self.rope_theta = bool(qk_norm), rope_theta
        self.window = None if window is None else int(window)
        self.prefill_block, self.norm_eps = int(prefill_block), norm_eps
        self.key_chunk = None if key_chunk is None else int(key_chunk)
        h, d = int(hidden_size), self.num_heads * self.head_dim
        kvd = self.num_kv_heads * self.head_dim
        shapes = {"wq": (h, d), "wk": (h, kvd), "wv": (h, kvd), "wo": (d, h)}
        if self.gated:
            shapes["wg"] = (h, d)
        for name, shape in shapes.items():
            setattr(self, name, Parameter.from_array(
                normal_or_zeros(shape, initializer_range, dtype), name=name))
        if self.qk_norm:
            for name in ("q_norm", "k_norm"):
                setattr(self, name, Parameter.from_array(
                    jnp.ones((self.head_dim,), dtype), name=name))

    def _last_rows(self, x, length, ring):
        """Of ``x [B, H, T, D]`` the rows a ring of ``ring`` rows holds
        after ``length [B]`` positions: ring row ``j`` is the last
        position below ``length`` that is ``j`` modulo ``ring``, zeros
        where there is none yet."""
        j = jnp.arange(ring, dtype=jnp.int32)[None]
        last = length.astype(jnp.int32)[:, None] - 1
        p = last - jnp.mod(last - j, ring)                   # [B, ring]
        rows = jnp.take_along_axis(
            x, jnp.clip(p, 0, x.shape[2] - 1)[:, None, :, None], axis=2)
        return jnp.where((p >= 0)[:, None, :, None], rows, 0)

    def _rows_after(self, old, x, lo, length):
        """The ring ``old [B, H, ring, D]`` after a chunk ``x [B, H, T,
        D]`` whose first ``length [B]`` rows are real and stand at ``lo
        [B]`` onwards: ring row ``j`` takes the last position below ``lo
        + length`` that is ``j`` modulo the ring if the chunk has it,
        and keeps what it held if not: a window ring, which wraps."""
        ring = old.shape[2]
        j = jnp.arange(ring, dtype=jnp.int32)[None]
        last = (lo + length).astype(jnp.int32)[:, None] - 1
        rel = last - jnp.mod(last - j, ring) - lo[:, None]   # [B, ring]
        rows = jnp.take_along_axis(
            x, jnp.clip(rel, 0, x.shape[2] - 1)[:, None, :, None], axis=2)
        return jnp.where((rel >= 0)[:, None, :, None], rows.astype(old.dtype),
                         old)

    def _continue(self, q, k, v, cache, mask):
        """The chunk case (module docstring): ``(o, new cache)``."""
        kc, vc, lo = cache
        b, _, t, d = k.shape
        ring, w = kc.shape[2], self.window
        if w is None:
            o = attend_continued(q, k, v, kc, vc, lo, mask, d ** -0.5,
                                 self.prefill_block, self.key_chunk)
        else:
            # the ring's rows in position order, lo - ring .. lo - 1 (the
            # first of them is beyond every query's band), then the chunk
            p = lo[:, None] - ring + jnp.arange(ring, dtype=jnp.int32)[None]
            at = jnp.mod(p, ring)[:, None, :, None]
            pad = jnp.zeros((b, 1, 1, t), jnp.float32) if mask is None \
                else mask
            held = jnp.where(p >= 0, 0.0, _NEG_INF).astype(
                pad.dtype)[:, None, None, :]
            o = attend_causal_blocks(
                q, *(jnp.concatenate(
                    [jnp.take_along_axis(c, at, axis=2).astype(n.dtype), n],
                    axis=2) for c, n in ((kc, k), (vc, v))),
                jnp.concatenate([held, pad], axis=-1), d ** -0.5,
                self.prefill_block, self.key_chunk, w, offset=ring)
        if w is None:
            # chunks begin at multiples of their length, which divides
            # the ring (the engine sees to it): lo + t <= ring
            assert ring % t == 0, (ring, t)
            kc, vc = (_write_rows(c, n.astype(c.dtype), lo)
                      for c, n in ((kc, k), (vc, v)))
        else:
            length = jnp.full((b,), t) if mask is None \
                else (mask[:, 0, 0, :] == 0).sum(-1)
            kc, vc = (self._rows_after(c, n, lo, length)
                      for c, n in ((kc, k), (vc, v)))
        return o, StaticCache(kc, vc, lo)

    def forward(self, x, cache=None, mask=None, positions=None):
        """``x [B, T, hidden]`` (an array); ``mask``: see the module's
        docstring; ``positions [B, T]`` for a rotary layer. Returns
        ``y`` or ``(y, new_cache)``."""
        b, t, _ = x.shape
        hq, hkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        g, w = hq // hkv, self.window
        q = jnp.matmul(x, self.wq._array).reshape(b, t, hkv, g, d)
        k, v = (jnp.matmul(x, m._array).reshape(b, t, hkv, d)
                for m in (self.wk, self.wv))
        if self.qk_norm:
            q = rms_norm(q, self.q_norm._array, self.norm_eps)
            k = rms_norm(k, self.k_norm._array, self.norm_eps)
        if self.rope_theta is not None:
            q = apply_rotary(q, positions, self.rope_theta)
            k = apply_rotary(k, positions, self.rope_theta)
        q = q.transpose(0, 2, 3, 1, 4)                   # [B, Hkv, G, T, D]
        k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        if cache is not None and t == 1:
            # decode: write this token's row into the ring, attend it
            kc, vc, pos = cache
            idx = jnp.mod(pos, kc.shape[2])
            kc = _write_rows(kc, k.astype(kc.dtype), idx)
            vc = _write_rows(vc, v.astype(vc.dtype), idx)
            cache = StaticCache(kc, vc, pos)
            if isinstance(mask, dict):
                mask = mask[kc.shape[2]]
            o = attend(q, kc, vc, mask[:, :, None], d ** -0.5)
        elif isinstance(cache, ContinuedCache):
            o, cache = self._continue(q, k, v, cache, mask)
        else:
            o = attend_causal_blocks(q, k, v, mask, d ** -0.5,
                                     self.prefill_block, self.key_chunk, w)
            if cache is not None:
                kc, vc, pos = cache
                ring = kc.shape[2]
                if w is not None and ring < t:
                    length = jnp.full((b,), t) if mask is None \
                        else (mask[:, 0, 0, :] == 0).sum(-1)
                    kc, vc = (self._last_rows(n, length, ring).astype(c.dtype)
                              for c, n in ((kc, k), (vc, v)))
                else:
                    zero = jnp.zeros((), jnp.int32)
                    kc, vc = (update_slice_in_range(
                        c, n.astype(c.dtype), zero, zero, zero, zero)
                        for c, n in ((kc, k), (vc, v)))
                cache = StaticCache(kc, vc, pos)
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, t, hq * d)
        if self.gated:
            o = (o.astype(jnp.float32) * jax.nn.sigmoid(jnp.matmul(
                x, self.wg._array, preferred_element_type=jnp.float32))
                 ).astype(x.dtype)
        y = jnp.matmul(o, self.wo._array)
        return y if cache is None else (y, cache)

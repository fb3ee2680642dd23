"""Cached multi-head latent attention (MLA): one layer, two paths over
the same weights, chosen by what the layer is handed.

A token's key and value for every head are made from one low-rank
latent: ``[c_raw ; k_rot] = x W_kva``, ``c = RMSNorm(c_raw) * kv_scale``,
``[k_nope_h ; v_h] = c W_kvb[h]``; ``k_rot`` (rotated by the position,
interleaved pairs) is one vector shared by all heads. The query is
low-rank too: ``q = RMSNorm(x W_qa) W_qb * q_scale``, a head ``[q_nope ;
q_rot]``. Scores are ``(q_nope_h . k_nope_h + q_rot_h . k_rot) * (nope +
rope)^-0.5``. The cache keeps, a token, the row ``[c ; rotated k_rot]``
(``generation.cache.latent(rank, rope)``: ``rank + rope`` wide, no head
axis), which is what makes a long ring cheap.

- **Expanded** (a prompt, or no cache): K and V are expanded a head from
  the latent and the layer is ordinary causal attention at head widths
  ``nope + rope`` / ``v``, by query blocks and key chunks
  (:func:`nn.gqa.attend_causal_blocks`); the prompt's latent rows are
  written into the ring from row 0.
- **Absorbed** (one token a slot, with a cache): ``W_kvb`` is folded
  into the query and the output instead of the ring being expanded:
  ``q_lat_h = q_nope_h W_kvb[h, :, :nope]^T``, scores ``(q_lat_h . c +
  q_rot_h . k_rot)`` over the ring rows as they lie, ``o_lat_h = sum p
  c`` (summed over the whole row and the rotated channels dropped, so
  that no operand is a slice of the ring), ``o_h = o_lat_h W_kvb[h, :,
  nope:]``: all heads attend ONE shared
  row (grouped-query attention with one K/V head of width ``rank +
  rope`` and a group of all the heads, by :func:`nn.gqa.attend_keys`),
  so a step reads a ring row once for all heads. The same numbers as
  the expanded path (tests/test_longcat_flash.py); ``W_kvb`` is one
  leaf and the absorbed path takes views of it. On a TPU the scores,
  the softmax and the sum are ONE Mosaic kernel over each slot's live
  rows (:mod:`ops.pallas.mla_decode`, where :func:`decode_key_block`
  says so: XLA's path reads every ring whole, twice, whatever is
  live); profiler counters ``mla::absorbed_kernel`` /
  ``mla::absorbed_xla`` count the attentions traced each way.

Softmax, norm statistics and rotary angles are float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.tensor import Parameter
from ..ops.pallas._platform import can_emit_mosaic
from ..ops.pallas.mla_decode import (key_block, mla_decode,
                                     mla_decode_supported)
from ..profiler import bump_counter
from .gqa import apply_rotary, attend_causal_blocks, attend_keys, rms_norm
from .layer_base import Layer
from .linear_attention import normal_or_zeros
from .transformer import LatentCache, _write_rows, update_slice_in_range

__all__ = ["CachedLatentAttention", "decode_key_block"]


def decode_key_block(ring_shape, dtype):
    """Keys a block of the decode kernel where an absorbed step over a
    ``[B, ring, rank + rope]`` ring of ``dtype`` takes it here and now
    (a TPU, no multi-device mesh in scope, a ring the kernel supports);
    ``None`` where XLA's path runs. The layer and the engine's
    ``generation::kv_rows_fetched`` both ask here."""
    if can_emit_mosaic() and mla_decode_supported(ring_shape, dtype):
        return key_block(ring_shape[1])
    return None


class CachedLatentAttention(Layer):
    def __init__(self, hidden_size, num_heads, q_rank, kv_rank, nope_dim,
                 rope_dim, v_dim, rope_theta=10000.0, scale_q=False,
                 scale_kv=False, prefill_block=256, key_chunk=None,
                 norm_eps=1e-5, initializer_range=0.02, dtype="float32"):
        super().__init__()
        h = int(hidden_size)
        self.num_heads, self.rank = int(num_heads), int(kv_rank)
        self.nope, self.rope, self.v_dim = (int(nope_dim), int(rope_dim),
                                            int(v_dim))
        self.rope_theta, self.norm_eps = float(rope_theta), norm_eps
        self.q_scale = (h / int(q_rank)) ** 0.5 if scale_q else 1.0
        self.kv_scale = (h / self.rank) ** 0.5 if scale_kv else 1.0
        self.softmax_scale = (self.nope + self.rope) ** -0.5
        self.prefill_block = int(prefill_block)
        self.key_chunk = None if key_chunk is None else int(key_chunk)
        n = self.num_heads
        for name, shape in (
                ("wq_a", (h, int(q_rank))),
                ("wq_b", (int(q_rank), n * (self.nope + self.rope))),
                ("wkv_a", (h, self.rank + self.rope)),
                ("wkv_b", (self.rank, n * (self.nope + self.v_dim))),
                ("wo", (n * self.v_dim, h))):
            setattr(self, name, Parameter.from_array(
                normal_or_zeros(shape, initializer_range, dtype), name=name))
        self.q_norm = Parameter.from_array(
            jnp.ones((int(q_rank),), dtype), name="q_norm")
        self.kv_norm = Parameter.from_array(
            jnp.ones((self.rank,), dtype), name="kv_norm")

    def _query_and_row(self, x, positions):
        """``(q_nope [B, T, H, nope], q_rot [B, T, H, rope], row [B, T,
        rank + rope])``: the query's two parts, rotated, and the cache
        row ``[c ; rotated k_rot]``."""
        b, t, _ = x.shape
        q = jnp.matmul(rms_norm(jnp.matmul(x, self.wq_a._array),
                                self.q_norm._array, self.norm_eps),
                       self.wq_b._array)
        if self.q_scale != 1.0:
            q = (q.astype(jnp.float32) * self.q_scale).astype(x.dtype)
        q = q.reshape(b, t, self.num_heads, self.nope + self.rope)
        kv = jnp.matmul(x, self.wkv_a._array)
        c = rms_norm(kv[..., :self.rank], self.kv_norm._array, self.norm_eps)
        if self.kv_scale != 1.0:
            c = (c.astype(jnp.float32) * self.kv_scale).astype(x.dtype)
        rot = {"theta": self.rope_theta, "interleaved": True}
        q_rot = apply_rotary(q[..., self.nope:], positions, **rot)
        k_rot = apply_rotary(kv[..., self.rank:], positions, **rot)
        return q[..., :self.nope], q_rot, jnp.concatenate([c, k_rot], -1)

    def _kvb(self):
        """``W_kvb`` as ``[rank, H, nope + v]``: a view of the leaf."""
        return self.wkv_b._array.reshape(
            self.rank, self.num_heads, self.nope + self.v_dim)

    def expanded(self, q_nope, q_rot, row, mask):
        """Ordinary causal attention over K and V expanded from ``row
        [B, T, rank + rope]``: ``[B, T, H * v]``."""
        b, t, n = row.shape[0], row.shape[1], self.num_heads
        kv = jnp.einsum("btr,rhd->bhtd", row[..., :self.rank], self._kvb())
        k = jnp.concatenate([
            kv[..., :self.nope], jnp.broadcast_to(
                row[:, None, :, self.rank:], (b, n, t, self.rope))], -1)
        q = jnp.concatenate([q_nope, q_rot], -1).transpose(0, 2, 1, 3)
        o = attend_causal_blocks(
            q[:, :, None], k, kv[..., self.nope:], mask, self.softmax_scale,
            self.prefill_block, self.key_chunk)       # [B, H, 1, T, v]
        return o[:, :, 0].transpose(0, 2, 1, 3).reshape(b, t, n * self.v_dim)

    def absorbed(self, q_nope, q_rot, ring, mask, pos=None):
        """One query a slot against the ring rows as they lie, ``ring
        [B, C, rank + rope]`` under the additive decode mask ``[B, 1, 1,
        C]``: ``[B, 1, H * v]``. With the step's ``pos [B]`` (the mask
        is then ``decode_mask(pos, C)``: rows below ``min(pos + 1, C)``)
        and where :func:`decode_key_block` allows, the attention is the
        Mosaic kernel over the live rows and the mask is not read."""
        b, n = ring.shape[0], self.num_heads
        w = self._kvb()
        q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, w[..., :self.nope])
        q = jnp.concatenate([q_lat, q_rot], -1).transpose(0, 2, 1, 3)
        # the values are the rows' latent part; the whole row is summed
        # and the rotated channels dropped after, because a slice of the
        # ring as an operand is a copy of the ring a step (0.27 GB an
        # attention at 32 x 8,192 rows: my AOT compile, PR 36)
        block = None if pos is None \
            else decode_key_block(ring.shape, ring.dtype)
        if block is not None:
            bump_counter("mla::absorbed_kernel")
            o_lat = mla_decode(
                q[:, :, 0], jnp.swapaxes(ring, 1, 2), pos + 1,
                self.softmax_scale, block)[..., :self.rank]
        else:
            bump_counter("mla::absorbed_xla")
            o_lat = attend_keys(
                q[:, None], ring[:, None], ring[:, None], mask[:, :, None],
                self.softmax_scale, self.key_chunk)[:, 0, :, 0, :self.rank]
        o = jnp.einsum("bhr,rhd->bhd", o_lat, w[..., self.nope:])
        return o.reshape(b, 1, n * self.v_dim)

    def forward(self, x, cache=None, mask=None, positions=None):
        """``x [B, T, hidden]`` (an array). With a cache, one token a
        row is a decode step (``mask`` the additive ``[B, 1, 1, ring]``
        decode mask, or ``{ring length: mask}``) and takes the absorbed
        path; more than one is a prefill from position 0 into a fresh
        cache (``mask`` the additive key-padding mask ``[B, 1, 1, T]``)
        and takes the expanded one. Returns ``y`` or ``(y, new_cache)``."""
        b, t, _ = x.shape
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.int32)[None], (b, t))
        q_nope, q_rot, row = self._query_and_row(x, positions)
        if cache is not None and t == 1:
            with jax.named_scope("mla_absorb"):
                ring, pos = cache
                idx = jnp.mod(pos, ring.shape[1])
                ring = _write_rows(ring[:, None], row.astype(
                    ring.dtype)[:, None], idx)[:, 0]
                cache = LatentCache(ring, pos)
                if isinstance(mask, dict):
                    mask = mask[ring.shape[1]]
                o = self.absorbed(q_nope, q_rot, ring, mask, pos)
        else:
            with jax.named_scope("mla_expand"):
                o = self.expanded(q_nope, q_rot, row, mask)
                if cache is not None:
                    ring, pos = cache
                    zero = jnp.zeros((), jnp.int32)
                    cache = LatentCache(update_slice_in_range(
                        ring, row.astype(ring.dtype), zero, zero, zero), pos)
        y = jnp.matmul(o, self.wo._array)
        return y if cache is None else (y, cache)

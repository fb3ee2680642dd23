"""Block-sparse grouped-query attention over a K/V ring with a ring of
pooled keys beside it (InfLLM-V2): every query chooses which blocks of
the context it attends, and a decode step reads those blocks' rows only.

The context is cut into blocks of ``block`` positions. Beside the K and V
rings the layer keeps one *pooled* key for every ``stride`` positions,
the mean of ``kernel`` consecutive (normalised) keys: row ``j`` covers
positions ``stride j .. stride j + kernel - 1`` and exists once the last
of them does. A query at position ``t`` of a sequence of ``n = t + 1``
positions then

1. attends positions ``0 .. t`` plainly where ``n < dense_len``; else
2. scores the pooled rows that exist, ``softmax_j(q_h . c_j scale)`` for
   each head of its K/V group (float32), and sums the heads;
3. gives block ``b`` the largest of those sums among the pooled rows that
   overlap it;
4. forces the first ``init_blocks`` blocks and the ``window // block``
   most recent ones, and keeps the ``topk`` highest blocks among ``0 ..
   t // block``, forced ones included, ties to the lower index;
5. attends the kept blocks' positions ``<= t``. All heads of a K/V group
   attend the same blocks; the groups choose apart.

:func:`select_blocks` is steps 1-4 for a block of queries, and both the
prompt and the decode step go through it. A prompt
(:func:`sparse_prefill`) takes its queries ``q_block`` at a time: each
block makes every position's own choice, then goes over the keys
``key_chunk`` at a time as far as causality reaches, every causal key
block computed and the unchosen ones masked (the scores of one step are
``[heads, q_block, key_chunk]``, never ``[heads, T, T]``). A decode step
(:func:`sparse_decode`) gathers the chosen blocks' rows out of the ring
and attends those: ``gather_blocks`` of them a slot, which is ``topk``
or, so that a slot under ``dense_len`` finds all its live blocks in the
same batched program, ``dense_len // block`` if that is more; the places
a slot does not use are masked. Pooled row ``j`` is written by the step
that writes the last position it covers (and, harmlessly, again by the
``stride - 1`` steps after it).

A position is a block address here, so the ring does not wrap: past its
end every new token takes the LAST row's place (``t`` stays at ``store -
1``), which keeps "the pooled ring is the pooling of the K ring" true and
is what the tests pin; a server keeps requests inside the ring
(``context_limit``). Softmaxes, norm statistics and the pooling's mean
are float32; rings are the cache's dtype.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..framework.tensor import Parameter
from .gqa import _join, rms_norm
from .layer_base import Layer
from .linear_attention import normal_or_zeros
from .transformer import _write_rows, update_slice_in_range

__all__ = ["SparseConfig", "SparseCache", "SparseGQAttention", "pool_keys",
           "select_blocks", "sparse_prefill", "sparse_decode"]

_NEG_INF = -1e9


class SparseConfig(NamedTuple):
    """The selection's sizes (the family's ``sparse_config``)."""

    kernel: int = 32
    stride: int = 16
    block: int = 64
    init_blocks: int = 1
    window: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def check(self):
        if self.kernel != 2 * self.stride or self.block % self.stride \
                or self.window % self.block:
            raise ValueError(
                f"{self}: the pooling is written for kernel = 2 x stride, "
                "blocks of whole strides and a window of whole blocks")
        return self

    @property
    def gather_blocks(self):
        """Blocks a decode step gathers a slot: see the module's text."""
        return max(self.topk, -(-self.dense_len // self.block))


class SparseCache(NamedTuple):
    """The per-layer cache of :class:`SparseGQAttention`: ``k`` / ``v``
    ``[B, heads, store, D]`` rings, ``ck [B, heads, store / stride, D]``
    the pooled keys, ``pos [B]`` shared with every other layer."""

    k: Any
    v: Any
    ck: Any
    pos: Any


def _read_rows(cache, start, count):
    """Rows ``start[b] .. start[b] + count - 1`` of every slot ``b`` of
    ``cache [B, H, C, D]``: ``[B, H, count, D]``. One ``dynamic_slice``
    a slot, unrolled, as :func:`nn.transformer._write_rows` writes: a
    gather over the row axis makes XLA:TPU keep the whole ring in
    another layout and copy it there and back every step (two 537 MB
    copies a layer in the compile for a described v5e, PR 49)."""
    zero = jnp.zeros((), start.dtype)
    return jnp.concatenate([jax.lax.dynamic_slice(
        cache, (jnp.asarray(b, start.dtype), zero, start[b], zero),
        (1, cache.shape[1], count, cache.shape[3]))
        for b in range(cache.shape[0])], axis=0)


def pool_keys(k, cfg):
    """``k [..., T, D]`` -> ``[..., T // stride, D]``: row ``j`` the
    float32 mean of rows ``stride j .. stride j + kernel - 1``, in
    ``k``'s dtype. ``T`` is a whole number of strides; the last row has
    no second half and is not a pooled key (no position validates it)."""
    t, d = k.shape[-2:]
    half = k.astype(jnp.float32).reshape(
        k.shape[:-2] + (t // cfg.stride, cfg.stride, d)).sum(-2)
    nxt = jnp.concatenate([half[..., 1:, :], jnp.zeros_like(half[..., :1, :])],
                          axis=-2)
    return ((half + nxt) / cfg.kernel).astype(k.dtype)


def select_blocks(q, pooled, t, cfg, scale):
    """Steps 1-4 for ``q [..., G, Q, D]`` (the ``G`` heads of one K/V
    group, ``Q`` queries at positions ``t [..., Q]``) against ``pooled
    [..., J, D]``: the blocks each query attends, ``[..., Q, J stride /
    block]`` bool."""
    with jax.named_scope("sparse_select"):
        j = pooled.shape[-2]
        per = cfg.block // cfg.stride
        nb = j // per
        t = t.astype(jnp.int32)[..., None]                    # [..., Q, 1]
        s = jnp.einsum("...gqd,...jd->...gqj", q, pooled,
                       preferred_element_type=jnp.float32) * scale
        rows = jnp.arange(j, dtype=jnp.int32)
        valid = rows * cfg.stride + (cfg.kernel - 1) <= t     # [..., Q, J]
        s = jnp.where(valid[..., None, :, :], s, -1e30)
        p = jnp.exp(s - s.max(-1, keepdims=True))
        p = jnp.where(valid[..., None, :, :], p, 0.0)
        p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        p = jnp.where(valid, p.sum(-3), -1.0)                 # [..., Q, J]
        # block b overlaps pooled rows per b - 1 .. per b + per - 1
        own = p.reshape(p.shape[:-1] + (nb, per))
        before = jnp.concatenate(
            [jnp.full_like(own[..., :1, -1], -1.0), own[..., :-1, -1]], -1)
        score = jnp.maximum(own.max(-1), before)              # [..., Q, NB]
        b = jnp.arange(nb, dtype=jnp.int32)
        newest = t // cfg.block
        live = b <= newest
        forced = (b < cfg.init_blocks) | (b > newest - cfg.window // cfg.block)
        score = jnp.where(live, jnp.where(forced, jnp.inf, score), -jnp.inf)
        k = min(cfg.topk, nb)
        kth = jax.lax.top_k(score, k)[0][..., -1:]
        above, level = score > kth, score == kth
        room = k - above.sum(-1, keepdims=True)
        chosen = above | (level & (jnp.cumsum(level, -1) <= room))
        return jnp.where(t + 1 < cfg.dense_len, live, chosen & live)


def sparse_prefill(q, k, v, cfg, scale, q_block=512, key_chunk=2048):
    """A whole sequence from position 0: ``q [B, H, G, T, D]``, ``k`` /
    ``v [B, H, T, D]`` -> ``([B, H, G, T, D], the pooled keys [B, H, T /
    stride, D])``. ``T`` is a whole number of ``q_block``s and of
    ``key_chunk``s (the layer pads)."""
    b, h, g, t, d = q.shape
    nq, kc_blocks = t // q_block, key_chunk // cfg.block
    with jax.named_scope("sparse_pool"):
        pooled = pool_keys(k, cfg)

    def block(lo, qb):
        pos = lo + jnp.arange(q_block, dtype=jnp.int32)
        chosen = select_blocks(qb, pooled, jnp.broadcast_to(
            pos, (b, h, q_block)), cfg, scale)            # [B, H, Q, NB]

        def piece(c, part):
            k0 = c * key_chunk
            ks = jax.lax.dynamic_slice_in_dim(k, k0, key_chunk, axis=2)
            vs = jax.lax.dynamic_slice_in_dim(v, k0, key_chunk, axis=2)
            kept = jax.lax.dynamic_slice_in_dim(
                chosen, c * kc_blocks, kc_blocks, axis=3)
            col = k0 + jnp.arange(key_chunk, dtype=jnp.int32)
            keep = jnp.repeat(kept, cfg.block, axis=3) \
                & (col[None, :] <= pos[:, None])
            bias = jnp.where(keep, 0.0, _NEG_INF).astype(
                jnp.float32)[:, :, None]                  # [B, H, 1, Q, KC]
            return _join(part, qb, ks, vs, bias, scale)

        part = (jnp.full((b, h, g, q_block, 1), -1e30, jnp.float32),
                jnp.zeros((b, h, g, q_block, 1), jnp.float32),
                jnp.zeros((b, h, g, q_block, v.shape[-1]), jnp.float32))
        with jax.named_scope("sparse_attend"):
            part = jax.lax.fori_loop(
                0, (lo + q_block + key_chunk - 1) // key_chunk, piece, part)
            return lo + q_block, (part[2] / part[1]).astype(v.dtype)

    qs = jnp.moveaxis(q.reshape(b, h, g, nq, q_block, d), 3, 0)
    _, out = jax.lax.scan(block, jnp.zeros((), jnp.int32), qs)
    return jnp.moveaxis(out, 0, 3).reshape(b, h, g, t, v.shape[-1]), pooled


def sparse_decode(q, kc, vc, ck, t, cfg, scale):
    """One query a slot at position ``t [B]`` against the rings ``kc`` /
    ``vc [B, H, S, D]`` (row ``t`` written) and the pooled ring ``ck [B,
    H, S / stride, D]``: ``q [B, H, G, 1, D]`` -> ``[B, H, G, 1, D]``."""
    b, h, s, d = kc.shape
    nb, m = s // cfg.block, min(cfg.gather_blocks, s // cfg.block)
    chosen = select_blocks(q, ck, jnp.broadcast_to(t[:, None, None],
                                                   (b, h, 1)), cfg, scale)
    with jax.named_scope("sparse_attend"):
        # the chosen blocks first, in index order; a slot uses `count`
        chosen = chosen[:, :, 0]                              # [B, H, NB]
        idx = jnp.arange(nb, dtype=jnp.int32)
        _, at = jax.lax.top_k(jnp.where(chosen, 2 * nb - idx, -idx), m)
        at = at.astype(jnp.int32)                             # [B, H, M]
        used = jnp.arange(m) < chosen.sum(-1, keepdims=True)
        # (indices come out of a top-k over the blocks: in bounds, and
        # saying so spares a select over both gathered tensors, 1.6 ms
        # of a 14.2 ms step: my chip run, PR 49)
        kb, vb = (jnp.take_along_axis(
            c.reshape(b, h, nb, cfg.block, c.shape[-1]),
            at[..., None, None], axis=2, mode="promise_in_bounds")
            for c in (kc, vc))                                # [B,H,M,blk,D]
        col = at[..., None] * cfg.block + jnp.arange(cfg.block,
                                                     dtype=jnp.int32)
        keep = used[..., None] & (col <= t[:, None, None, None])
        sc = jnp.einsum("bhgd,bhmrd->bhgmr", q[:, :, :, 0], kb,
                        preferred_element_type=jnp.float32) * scale
        sc = jnp.where(keep[:, :, None], sc, _NEG_INF)
        p = jax.nn.softmax(sc.reshape(b, h, -1, m * cfg.block), axis=-1)
        o = jnp.einsum("bhgk,bhkd->bhgd", p.astype(vb.dtype),
                       vb.reshape(b, h, m * cfg.block, vb.shape[-1]))
        return o[:, :, :, None]


class SparseGQAttention(Layer):
    """The mixer: grouped-query attention without a position signal
    whose queries choose their blocks. q and k are RMS-normalised a
    head, each with a learned gain, and the output is multiplied
    elementwise by ``sigmoid(x Wg)``: the one form the family has (a
    model whose configuration says otherwise refuses it). Weights are
    ``[in, out]``, no biases."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 sparse=SparseConfig(), q_block=512, key_chunk=2048,
                 norm_eps=1e-6, initializer_range=0.02, dtype="float32"):
        super().__init__()
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim, self.norm_eps = int(head_dim), norm_eps
        self.sparse = SparseConfig(*(int(n) for n in sparse)).check()
        self.q_block, self.key_chunk = int(q_block), int(key_chunk)
        if self.q_block % self.sparse.block or self.key_chunk % self.q_block:
            raise ValueError("q_block is whole blocks, key_chunk whole "
                             "q_blocks")
        h, d = int(hidden_size), self.num_heads * self.head_dim
        kvd = self.num_kv_heads * self.head_dim
        for name, shape in (("wq", (h, d)), ("wk", (h, kvd)),
                            ("wv", (h, kvd)), ("wo", (d, h)),
                            ("wg", (h, d))):
            setattr(self, name, Parameter.from_array(
                normal_or_zeros(shape, initializer_range, dtype), name=name))
        for name in ("q_norm", "k_norm"):
            setattr(self, name, Parameter.from_array(
                jnp.ones((self.head_dim,), dtype), name=name))

    def _prefill(self, q, k, v):
        """:func:`sparse_prefill` on a sequence of any length: padded on
        the right (causal: a pad changes no real position) to whole
        query blocks and key chunks. The pooled rows are those of the
        sequence's whole strides."""
        t, cfg = q.shape[3], self.sparse
        qb = min(self.q_block, -(-t // cfg.block) * cfg.block)
        full = -(-t // qb) * qb
        kc = self.key_chunk if full % self.key_chunk == 0 else qb
        if full != t:
            q = jnp.pad(q, ((0, 0),) * 3 + ((0, full - t), (0, 0)))
            k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, full - t), (0, 0)))
                    for a in (k, v))
        o, pooled = sparse_prefill(q, k, v, cfg, self.head_dim ** -0.5, qb,
                                   kc)
        return o[:, :, :, :t], pooled[:, :, :t // cfg.stride]

    def forward(self, x, cache=None):
        """``x [B, T, hidden]`` (an array). With a :class:`SparseCache`,
        one token a row is a decode step, more a prefill from position 0
        into fresh rings (``T`` a whole number of strides). Returns ``y``
        or ``(y, new_cache)``."""
        b, t, _ = x.shape
        hq, hkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        cfg, scale = self.sparse, self.head_dim ** -0.5
        with jax.named_scope("sparse_attn"):
            q = jnp.matmul(x, self.wq._array).reshape(b, t, hkv, hq // hkv, d)
            k, v = (jnp.matmul(x, m._array).reshape(b, t, hkv, d)
                    for m in (self.wk, self.wv))
            q = rms_norm(q, self.q_norm._array, self.norm_eps)
            k = rms_norm(k, self.k_norm._array, self.norm_eps)
            q = q.transpose(0, 2, 3, 1, 4)               # [B, Hkv, G, T, D]
            k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            if cache is not None and t == 1:
                kc, vc, ck, pos = cache
                at = jnp.minimum(pos, kc.shape[2] - 1)
                kc = _write_rows(kc, k.astype(kc.dtype), at)
                vc = _write_rows(vc, v.astype(vc.dtype), at)
                with jax.named_scope("sparse_pool"):
                    j = jnp.maximum(at - (cfg.kernel - 1), 0) // cfg.stride
                    mean = _read_rows(kc, j * cfg.stride, cfg.kernel).astype(
                        jnp.float32).sum(-2, keepdims=True) / cfg.kernel
                    ck = _write_rows(ck, mean.astype(ck.dtype), j)
                o = sparse_decode(q, kc, vc, ck, at, cfg, scale)
                cache = SparseCache(kc, vc, ck, pos)
            else:
                o, pooled = self._prefill(q, k, v)
                if cache is not None:
                    kc, vc, ck, pos = cache
                    zero = jnp.zeros((), jnp.int32)
                    kc, vc, ck = (update_slice_in_range(
                        c, n.astype(c.dtype), zero, zero, zero, zero)
                        for c, n in ((kc, k), (vc, v), (ck, pooled)))
                    cache = SparseCache(kc, vc, ck, pos)
            o = o.transpose(0, 3, 1, 2, 4).reshape(b, t, hq * d)
            # as the Lightning mixer's: a prompt's gate product is left
            # in the activations' dtype
            f32 = jnp.float32
            o = (o.astype(f32) * jax.nn.sigmoid(jnp.matmul(
                x, self.wg._array,
                preferred_element_type=f32 if t == 1 else None
            ).astype(f32))).astype(x.dtype)
            y = jnp.matmul(o, self.wo._array)
        return y if cache is None else (y, cache)

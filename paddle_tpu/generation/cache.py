"""Static-shape ring KV cache plumbing for compile-once decoding.

The per-layer cache itself is :class:`paddle_tpu.nn.StaticCache`
(``nn/transformer.py``): fixed ``[B, H, C, D]`` K/V arrays written by
functional index updates, ring-wrapping at capacity ``C``. This module
holds the ENGINE-side pieces — the stacked whole-model cache pytree and
the mask composition that makes the static window numerically exact:

- an all-layers cache is one ``[B, H, C, D]`` array per layer and plane
  (K, V, and at int8 their scale planes) plus one shared ``pos [B]``
  vector. Nothing is stacked: the compiled programs take the cache
  donated and return it, so every layer's array is written where it
  lies (a decode step writes ``B`` rows into it, an admission one
  slot) and no program copies a layer, let alone the cache;
- ``decode_mask``/``prefill_mask`` compose the causal constraint with
  cache validity (entries beyond ``pos`` are zeros, never attended) into
  one additive mask per step. Because the ring keeps exactly the last
  ``C`` tokens, decoding with the cache equals a FULL forward under a
  sliding window of width ``C`` (``nn.causal_mask(T, window=C)``) —
  the parity contract the goldens in tests/test_generation.py pin,
  including wraparound past the window.

Everything here is shape-static: the same jitted program serves every
sequence length, so steady-state generation is compile-bound at
1 decode compile + one prefill compile per ladder bucket.

**Store vs window** (speculative decoding): the physical ring STORE may
be wider than the attention WINDOW. A speculative verify step writes
``k+1`` new entries before attending; with ``store == window`` those
writes would clobber ring entries still inside an early query's
sliding window once the ring has wrapped. With ``store >= window + k``
a write at position ``p`` clobbers position ``p - store <= p - window -
k``, which no query of the round can still attend — so in-place ring
writes stay exact. The masks therefore take the physical ``store``
width and an optional logical ``window`` (default: the store itself,
the historical behavior), and :func:`verify_mask` composes causality
across the ``k+1`` in-flight positions with the window constraint.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ..errors import UnavailableError
from ..nn.sparse_attention import SparseCache, SparseConfig
from ..nn.transformer import (ContinuedCache, LatentCache,
                              QuantizedStaticCache, RecurrentCache,
                              StaticCache, update_slice_in_range)

__all__ = [
    "CacheLostError",
    "init_cache", "layer_caches", "unzip_layer_caches", "insert_slot_planes",
    "insert_slot_kv",
    "fresh_layer_caches", "cache_nbytes",
    "kv_bytes_per_token", "decode_mask", "prefill_mask", "verify_mask",
    "pad_slot_arrays",
    "kv", "state", "latent", "sparse_kv", "KVKind", "StateKind",
    "LatentKind", "SparseKVKind",
    "is_layer_kinds",
    "init_kinds_cache", "kinds_layer_caches", "unzip_kinds_caches",
    "kinds_slot_nbytes", "kinds_bytes_per_token", "kinds_ring_lengths",
    "kinds_decode_mask", "kinds_continue", "kinds_slot_caches",
]

NEG_INF = -1e9

#: storage dtypes the KV cache supports (FLAGS_generation_kv_cache_dtype)
KV_CACHE_DTYPES = ("float32", "bfloat16", "int8")


class CacheLostError(UnavailableError):
    """A compiled call failed after it had consumed the donated cache:
    every slot's context went with it. The engine has already put a
    zeroed ring in its place; whoever drives the engine fails every
    live sequence, not only the one whose call raised."""


def init_cache(num_layers, batch, num_heads, cache_len, head_dim,
               dtype="float32"):
    """Zeroed whole-model cache.

    ``dtype="float32"``: ``(k, v, pos [B])``, where ``k`` and ``v`` are
    tuples of one ``[B, H, C, D]`` array per layer. ``dtype="int8"``: a
    5-tuple that additionally carries the per-head dynamic scale planes
    ``(k, v, k_scale, v_scale, pos)`` (``[B, H, C]`` per layer) with
    int8 K/V storage (:class:`nn.QuantizedStaticCache` per layer). Every
    helper below dispatches on the tuple arity, so engine code is
    dtype-agnostic.
    """
    shape = (int(batch), int(num_heads), int(cache_len), int(head_dim))
    pos = jnp.zeros((int(batch),), jnp.int32)

    def plane(shape, dtype):
        return tuple(jnp.zeros(shape, dtype) for _ in range(int(num_layers)))

    if str(dtype) == "int8":
        return (plane(shape, jnp.int8), plane(shape, jnp.int8),
                plane(shape[:-1], jnp.float32),
                plane(shape[:-1], jnp.float32), pos)
    return plane(shape, dtype), plane(shape, dtype), pos


def layer_caches(*kv):
    """The whole-model cache as per-layer caches (``pos`` is shared —
    every layer writes the same step): :class:`StaticCache` for the
    3-tuple form, :class:`nn.QuantizedStaticCache` for the 5-tuple."""
    if len(kv) == 1:  # whole-cache tuple passed as one argument
        kv = tuple(kv[0])
    pos, planes = kv[-1], kv[:-1]
    cls = StaticCache if len(planes) == 2 else QuantizedStaticCache
    return [cls(*arrays, pos) for arrays in zip(*planes)]


def unzip_layer_caches(caches):
    """The planes of the per-layer caches a forward returned, each a
    tuple over layers: ``(k, v)`` for :class:`StaticCache` layers,
    ``(k, v, k_scale, v_scale)`` for quantized ones. The inverse of
    :func:`layer_caches`, less ``pos``."""
    return tuple(zip(*(tuple(c)[:-1] for c in caches)))


def fresh_layer_caches(num_layers, batch, num_heads, cache_len, head_dim,
                       dtype="float32"):
    """Zeroed per-layer cache list for a prefill forward (the engine
    prefills ONE sequence into fresh caches, then installs the result
    into the admitted slot)."""
    return layer_caches(*init_cache(num_layers, batch, num_heads,
                                    cache_len, head_dim, dtype))


def insert_slot_planes(planes, slot, new_planes):
    """Write one sequence's entries into row ``slot`` of every layer's
    array, plane by plane: one ``dynamic_update_slice`` per layer and
    plane, which a program that was given the cache donated does in
    place. ``new_planes`` holds per plane the slot's entries layer by
    layer: a sequence of ``[H, C, D]`` (scales ``[H, C]``) arrays, or
    one stacked ``[L, H, C, D]`` array (the handoff slab's form)."""
    slot = jnp.asarray(slot, jnp.int32)
    zero = np.zeros((), np.int32)

    def put(a, n):
        return update_slice_in_range(
            a, jax.lax.expand_dims(n, (0,)), slot, *(zero,) * (a.ndim - 1))

    return tuple(tuple(put(a, new[i]) for i, a in enumerate(plane))
                 for plane, new in zip(planes, new_planes))


def insert_slot_kv(kv, slot, new_planes, length):
    """Install one prefilled sequence into decode slot ``slot`` and set
    its position to ``length`` — the admission write of continuous
    batching, so the batch program never recompiles when a slot turns
    over. ``kv`` is the whole-model cache tuple (``pos`` last);
    ``new_planes`` as :func:`insert_slot_planes` takes them."""
    return insert_slot_planes(kv[:-1], slot, new_planes) + (
        kv[-1].at[slot].set(length),)


def cache_nbytes(kv) -> int:
    """Device bytes the whole-model cache occupies (values + scales +
    positions) — the numerator of the int8-vs-f32 HBM claim, measured
    on the REAL arrays rather than derived."""
    return int(sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                   for a in jax.tree_util.tree_leaves(kv)))


def kv_bytes_per_token(num_layers, num_heads, head_dim,
                       dtype="float32") -> int:
    """Cache bytes one decoded token occupies across all layers: K + V
    values (+ their scale entries at int8); slots-at-equal-HBM between
    two modes is the ratio of theirs."""
    per_vec = (int(head_dim) + 4 if str(dtype) == "int8"
               else int(head_dim) * jnp.dtype(dtype).itemsize)
    return 2 * int(num_layers) * int(num_heads) * per_vec


# -- storage kinds chosen per layer -----------------------------------------
#
# A model whose layers do not all keep the same thing per slot (softmax
# attention beside a recurrence) answers ``cache_spec()`` with a list,
# one kind a layer, of the four below: a K/V ring, a constant state, a
# latent ring, a K/V ring with a ring of pooled keys beside it. The
# whole-model cache is then
# ``(layer_0_arrays, ..., layer_{L-1}_arrays, pos)``: per layer the tuple
# of that kind's arrays, every one with the slot axis first, and the one
# shared ``pos [B]`` last, as in the all-alike tuples above. It is still
# one pytree with one owner, donated whole and written in place, and
# :func:`insert_slot_kv` / :func:`insert_slot_planes` write a slot into
# it as they stand (their "planes" are this form's layers). A kind says
# what its layer keeps (``arrays``), which per-layer cache the model's
# forward is handed (``wrap``), how many rows its ring has (``ring``;
# ``None``: it keeps no rows) and what a slot costs (``slot_nbytes``).
# The rings of one cache may differ in length (a layer that attends a
# window keeps the window's rows): every ring is written at ``pos mod
# its own length`` and read under the decode mask of that length
# (:func:`kinds_decode_mask`), all from the one ``pos``. What a decode
# step reads of a ring is what the layer's step names
# (``rows_read``: every live row, or the rows of the blocks a sparse
# layer chose) and what it brings from HBM for that what its
# implementation fetches (``rows_fetched``). A model may
# list more kinds than it has layers (two attentions a layer: two
# rings), in the order its forward consumes them. A kind also says
# whether its layer can take a prompt up again from what the slot holds
# (``continues``): where every kind of a cache can, the engine admits a
# long prompt a chunk at a time (:func:`kinds_continue`).


class KVKind(NamedTuple):
    """A softmax-attention layer: a ``[B, heads, ring, head_dim]`` K and
    V ring (:class:`nn.StaticCache`), ``heads`` the K/V heads. The ring
    is the engine's ``store`` rows long, or, for a layer that attends no
    further back than ``window`` positions, ``window`` rows whatever the
    store is: such a layer costs a slot a constant and a token nothing
    more once it is full."""

    heads: int
    head_dim: int
    window: int | None = None

    #: a chunk boundary is nothing but rows already written: the layer
    #: attends them where they lie (:class:`nn.ContinuedCache`)
    continues = True

    def wrap_continued(self, arrays, pos):
        """The cache of a prompt's chunk that begins at ``pos``."""
        return ContinuedCache(*arrays, pos)

    def ring(self, store):
        """Rows of this layer's ring in a cache of ``store`` rows."""
        return int(store) if self.window is None \
            else min(self.window, int(store))

    def rows_read(self, live):
        """Ring rows a decode step's attention has to read a slot, for
        ``live [S]`` live rows a slot: what the layer's step names,
        which for plain attention is every live row."""
        return live

    def rows_fetched(self, live, store, dtype):
        """Ring rows that step brings from HBM a slot: XLA's attention
        over a ring reads it whole and masks."""
        return np.full_like(live, self.ring(store))

    def arrays(self, batch, store, dtype):
        shape = (int(batch), self.heads, self.ring(store), self.head_dim)
        return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)

    def wrap(self, arrays, pos):
        return StaticCache(*arrays, pos)

    def row_nbytes(self, dtype):
        return 2 * self.heads * self.head_dim * jnp.dtype(dtype).itemsize

    def bytes_per_token(self, dtype):
        return 0 if self.window is not None else self.row_nbytes(dtype)

    def slot_nbytes(self, store, dtype):
        return self.ring(store) * self.row_nbytes(dtype)


class StateKind(NamedTuple):
    """A recurrent layer: per slot the arrays of ``shapes`` / ``dtypes``,
    none with a cache-length axis, handed to the layer as ``cache``
    (state, then convolution tail: :class:`nn.RecurrentCache`; a state
    alone: :class:`nn.StateCache`)."""

    shapes: tuple
    dtypes: tuple
    cache: type = RecurrentCache

    #: a chunk boundary would be a state hand-over and a convolution
    #: tail, and a decode step between two chunks would advance a
    #: half-filled slot's state: no continuation yet
    continues = False

    def arrays(self, batch, store, dtype):
        return tuple(jnp.zeros((int(batch),) + tuple(s), d)
                     for s, d in zip(self.shapes, self.dtypes))

    def ring(self, store):
        return None

    def wrap(self, arrays, pos):
        return self.cache(*arrays, pos)

    def bytes_per_token(self, dtype):
        return 0

    def slot_nbytes(self, store, dtype):
        return sum(int(np.prod(s)) * jnp.dtype(d).itemsize
                   for s, d in zip(self.shapes, self.dtypes))


class LatentKind(NamedTuple):
    """A latent-attention layer: ONE ``[B, ring, rank + rope]`` plane
    (:class:`nn.LatentCache`) and no head axis: a row is the token's
    normalised latent, which every head's key and value are expanded
    from (or which the absorbed query attends as it lies), then the
    rotated key channels all heads share. The ring is the engine's
    ``store`` rows long."""

    rank: int
    rope: int

    #: the earlier rows would have to be expanded again for every
    #: chunk: no continuation yet
    continues = False

    def ring(self, store):
        return int(store)

    def rows_read(self, live):
        """Ring rows a decode step's attention has to read a slot: every
        live row."""
        return live

    def rows_fetched(self, live, store, dtype):
        """Ring rows a decode step's attention brings from HBM a slot,
        for ``live [S]`` live rows a slot: whole key blocks of the live
        rows where the absorbed step is the decode kernel
        (``nn.mla.decode_key_block``), the ring whole where XLA reads
        it."""
        from ..nn.mla import decode_key_block
        from ..ops.pallas.mla_decode import rows_fetched

        block = decode_key_block(
            (len(live), int(store), self.rank + self.rope), dtype)
        return np.full_like(live, int(store)) if block is None \
            else rows_fetched(live, block)

    def arrays(self, batch, store, dtype):
        return (jnp.zeros((int(batch), int(store), self.rank + self.rope),
                          dtype),)

    def wrap(self, arrays, pos):
        return LatentCache(*arrays, pos)

    def row_nbytes(self, dtype):
        return (self.rank + self.rope) * jnp.dtype(dtype).itemsize

    def bytes_per_token(self, dtype):
        return self.row_nbytes(dtype)

    def slot_nbytes(self, store, dtype):
        return int(store) * self.row_nbytes(dtype)


class SparseKVKind(NamedTuple):
    """A block-sparse attention layer (:class:`nn.SparseGQAttention`): a
    ``[B, heads, store, head_dim]`` K and V ring and beside them a ring
    of pooled keys, one row for every ``sparse.stride`` positions, which
    the layer's queries choose their blocks from
    (:class:`nn.SparseCache`). A position is a block address: the ring
    is ``store`` rows long, a whole number of blocks, and does not wrap;
    past its end every new token takes the last row's place
    (``nn/sparse_attention.py``), and a server keeps a request's
    positions inside the ring."""

    heads: int
    head_dim: int
    sparse: SparseConfig

    #: a chunk would have to make its queries' choices against pooled
    #: rows of the chunks before it and attend blocks of the ring:
    #: neither is written, and the state layers beside it refuse anyway
    continues = False
    #: a full-length ring: the engine counts it with the K/V rings that
    #: have no window (``GenerationEngine._kind_place``)
    window = None

    def ring(self, store):
        return int(store)

    def _newest(self, live):
        return np.maximum(np.asarray(live, np.int64) - 1, 0)

    def blocks_live(self, live):
        """Blocks that hold a live row, for ``live [S]`` rows a slot."""
        return self._newest(live) // self.sparse.block + 1

    def blocks_read(self, live):
        """Blocks a decode step attends a slot: every live one under
        ``dense_len``, else ``topk`` of them (fewer if fewer live)."""
        have = self.blocks_live(live)
        return np.where(np.asarray(live) < self.sparse.dense_len, have,
                        np.minimum(have, self.sparse.topk))

    def pooled_read(self, live):
        """Pooled rows a decode step scores a slot: none under
        ``dense_len``, else every one that exists."""
        c = self.sparse
        rows = np.maximum(self._newest(live) - (c.kernel - 1), -1) \
            // c.stride + 1
        return np.where(np.asarray(live) < c.dense_len, 0, rows)

    def rows_read(self, live):
        """Ring rows a decode step has to read a slot, in K/V rows (a
        pooled row is a key alone: half a row): the attended blocks'
        live rows (the newest block is full as far as the step's own
        position) and the pooled rows scored."""
        t = self._newest(live)
        c = self.sparse
        rows = (self.blocks_read(live) - 1) * c.block + t % c.block + 1
        return rows + (self.pooled_read(live) + 1) // 2

    def rows_fetched(self, live, store, dtype):
        """Ring rows the step brings from HBM a slot, in K/V rows: the
        gather takes ``sparse.gather_blocks`` whole blocks whatever the
        slot uses of them, and the selection scores the pooled ring
        whole and masks."""
        c = self.sparse
        blocks = min(c.gather_blocks, int(store) // c.block)
        return np.full_like(live, blocks * c.block
                            + int(store) // c.stride // 2)

    def arrays(self, batch, store, dtype):
        if int(store) % self.sparse.block:
            raise ValueError(f"a ring of {store} rows is no whole number "
                             f"of blocks of {self.sparse.block}")
        shape = (int(batch), self.heads, int(store), self.head_dim)
        pooled = shape[:2] + (int(store) // self.sparse.stride, shape[3])
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                jnp.zeros(pooled, dtype))

    def wrap(self, arrays, pos):
        return SparseCache(*arrays, pos)

    def row_nbytes(self, dtype):
        return 2 * self.heads * self.head_dim * jnp.dtype(dtype).itemsize

    def bytes_per_token(self, dtype):
        """A K and a V row, and a sixteenth (1 / stride) of a pooled
        row."""
        return self.row_nbytes(dtype) + -(-self.row_nbytes(dtype) // (
            2 * self.sparse.stride))

    def slot_nbytes(self, store, dtype):
        return int(store) * self.row_nbytes(dtype) + (
            int(store) // self.sparse.stride) * self.row_nbytes(dtype) // 2


def kv(heads, head_dim, window=None):
    """The kind of a layer that keeps K/V rows for ``heads`` K/V heads:
    as many as the cache is long, or the last ``window`` of them."""
    return KVKind(int(heads), int(head_dim),
                  None if window is None else int(window))


def state(shapes, dtypes, cache=RecurrentCache):
    """The kind of a layer that keeps a constant per-slot state, in the
    named tuple ``cache`` (its arrays, then ``pos``)."""
    return StateKind(tuple(tuple(int(n) for n in s) for s in shapes),
                     tuple(str(d) for d in dtypes), cache)


def latent(rank, rope):
    """The kind of a layer that keeps one latent row a token: ``rank``
    latent channels and ``rope`` rotated key channels, no heads."""
    return LatentKind(int(rank), int(rope))


def sparse_kv(heads, head_dim, sparse):
    """The kind of a block-sparse attention layer: K/V rows for
    ``heads`` K/V heads and the pooled keys its ``sparse`` sizes
    (:class:`nn.SparseConfig`) ask."""
    return SparseKVKind(int(heads), int(head_dim),
                        SparseConfig(*(int(n) for n in sparse)).check())


def is_layer_kinds(spec):
    """Is this ``cache_spec()`` a per-layer list of kinds (and not the
    ``(layers, heads, head_dim)`` of a model whose layers are alike)?"""
    return all(isinstance(k, (KVKind, StateKind, LatentKind, SparseKVKind))
               for k in spec) and len(spec) > 0


def init_kinds_cache(kinds, batch, store, dtype="float32"):
    """Zeroed whole-model cache of a per-layer list of kinds; ``dtype``
    is the ring's (a state kind names its own)."""
    return tuple(k.arrays(batch, store, dtype) for k in kinds) + (
        jnp.zeros((int(batch),), jnp.int32),)


def kinds_layer_caches(kinds, kv):
    """The per-layer caches a forward takes, from the whole-model cache
    of :func:`init_kinds_cache`'s form."""
    return [k.wrap(arrays, kv[-1]) for k, arrays in zip(kinds, kv[:-1])]


def kinds_continue(kinds):
    """Can every layer of this cache take a prompt up again from the
    rows its slot holds, so that a prompt may go in a chunk at a time?"""
    return bool(kinds) and all(k.continues for k in kinds)


def kinds_slot_caches(kinds, kv, slot, start):
    """The per-layer caches a CHUNK's forward takes: row ``slot`` of
    every array of the whole-model cache (a copy: batch 1), each as the
    kind's continued cache from position ``start [1]``."""
    def row(a):
        return jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0)

    return [k.wrap_continued(tuple(row(a) for a in arrays), start)
            for k, arrays in zip(kinds, kv[:-1])]


def unzip_kinds_caches(caches):
    """The inverse of :func:`kinds_layer_caches`, less ``pos``."""
    return tuple(tuple(c)[:-1] for c in caches)


def kinds_bytes_per_token(kinds, dtype="float32") -> int:
    """Cache bytes one more token costs a slot: the full-length K/V
    layers' rows and the latent layers'; a window or state layer adds
    nothing."""
    return sum(k.bytes_per_token(dtype) for k in kinds)


def kinds_slot_nbytes(kinds, store, dtype="float32") -> int:
    """Cache bytes one slot costs: each K/V or latent layer's ring at
    its own length (``store`` rows, or its window) and a constant in
    every state layer (``pos`` aside)."""
    return sum(k.slot_nbytes(store, dtype) for k in kinds)


def kinds_ring_lengths(kinds, store):
    """The distinct ring lengths of the layers that keep rows (K/V or
    latent), longest first."""
    return sorted({k.ring(store) for k in kinds} - {None}, reverse=True)


def kinds_decode_mask(kinds, pos, store, window=None):
    """The decode step's mask for a per-layer list of kinds. Where every
    K/V or latent ring is ``store`` rows long, :func:`decode_mask` of it, as a
    model whose rings are alike takes it. Where the rings are of several
    lengths, ``{ring length: mask}``, each asked for once: a layer takes
    the mask of the ring it was handed (``cache.k.shape[2]``; a latent
    ring's ``cache.c.shape[1]``)."""
    lengths = kinds_ring_lengths(kinds, store)
    if lengths in ([], [int(store)]):
        return decode_mask(pos, store, window=window)
    return {n: decode_mask(pos, n, window=min(
        n, int(store) if window is None else int(window)))
        for n in lengths}


def decode_mask(pos, cache_len, window=None, dtype="float32"):
    """Additive ``[B, 1, 1, store]`` mask for one decode step.

    The step's query (absolute position ``pos``) may attend every cache
    entry already written INCLUDING itself and no further back than
    ``window`` positions. ``cache_len`` is the physical STORE width;
    ``window`` defaults to it (the historical store-equals-window
    behavior: entry count after the write is ``min(pos + 1, C)`` and a
    wrapped ring holds exactly the last ``C`` tokens). With a wider
    store (speculative decoding) entry ``j`` holds absolute position
    ``pos - ((pos - j) mod store)`` — kept iff that distance is inside
    the window and the entry was ever written.
    """
    store = int(cache_len)
    w = store if window is None else int(window)
    dd = jnp.mod(pos[:, None] - jnp.arange(store)[None, :], store)
    keep = (dd < w) & (dd <= pos[:, None])
    return jnp.where(keep, 0.0, NEG_INF).astype(dtype)[:, None, None, :]


def verify_mask(pos, cache_len, span, window=None, dtype="float32"):
    """Additive ``[B, 1, span, store]`` mask for a speculative verify
    step: ``span = k + 1`` queries at absolute positions ``pos .. pos +
    k``, attending a ring the forward has ALREADY written all ``span``
    new entries into.

    Query ``i`` keeps entry ``j`` iff the token it holds is causally
    visible (``dd <= pos + i``, which also hides the q > i in-flight
    writes: their ring distance is ``store - (q - i) >= window`` by the
    ``store >= window + k`` allocation) and inside the sliding window
    (``dd < window``). Row 0 of the span reduces exactly to
    :func:`decode_mask`.
    """
    store = int(cache_len)
    w = store if window is None else int(window)
    q = pos[:, None, None] + jnp.arange(int(span))[None, :, None]
    dd = jnp.mod(q - jnp.arange(store)[None, None, :], store)
    keep = (dd < w) & (dd <= q)
    return jnp.where(keep, 0.0, NEG_INF).astype(dtype)[:, None]


def pad_slot_arrays(arrays, store, axis=2):
    """Zero-pad per-slot cache planes (``[L, H, C, D]`` values /
    ``[L, H, C]`` scales; ``axis=1`` for one layer's ``[H, C, D]`` /
    ``[H, C]``) from window width ``C`` up to a wider ring ``store``
    along the cache axis — a prefill tier's KV slab (always
    window-wide) landing in a decode tier whose ring carries the
    speculative scratch margin. Entries past the prompt are never-
    written zeros on both sides, so padding is exact."""
    out = []
    for a in arrays:
        c = a.shape[axis]
        if c > int(store):
            raise ValueError(
                f"slot plane cache axis {c} exceeds the target store "
                f"{store}")
        if c < int(store):
            pad = [(0, 0)] * a.ndim
            pad[axis] = (0, int(store) - c)
            a = jnp.pad(a, pad)
        out.append(a)
    return tuple(out)


def prefill_mask(bucket, cache_len, length, dtype="float32"):
    """Additive ``[1, 1, P, C]`` mask for a bucketed prefill.

    Query ``t`` keeps cache entry ``j`` iff causal (``j <= t``) and the
    entry holds a REAL prompt token (``j < length`` — bucket padding
    beyond the true prompt writes garbage K/V that must never be
    attended; decode later overwrites those entries in ring order before
    each becomes valid). Padding QUERIES (``t >= length``) produce
    garbage logits the engine never reads.
    """
    t = jnp.arange(int(bucket))[:, None]
    j = jnp.arange(int(cache_len))[None, :]
    keep = (j <= t) & (j < length)
    return jnp.where(keep, 0.0, NEG_INF).astype(dtype)[None, None]

"""Linear attention: a recurrence in place of a cache. Two of them: the
gated delta rule (below), and Lightning attention with a decay fixed a
head and rotary positions (the end of this file).

Gated-delta-rule linear attention.

Per head the layer keeps a ``[Dk, Dv]`` float32 memory ``S`` and updates
it once a token::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

with a per-channel decay ``a_t`` in (0, 1)^Dk and a write strength
``b_t`` in (0, 2) (above 1 the transition has a negative eigenvalue).
Decoding is :func:`gated_delta_step`: one token a slot, the state read
and rewritten, nothing that grows with the context. A prompt goes
through :func:`gated_delta_chunked`: chunks of 64 tokens, inside a chunk
everything is matrix products (the chunk's deltas come out of one
unit-triangular solve, the WY form of the product of the 64
transitions), and only the state is carried from chunk to chunk; it ends
in the state the recurrence would reach. :func:`gated_delta_recurrent`
is the recurrence itself over a sequence, for tests.

:class:`GatedDeltaAttention` is the mixer built on them: projections, a
short causal depthwise convolution and SiLU on q, k and v, L2-normalised
q and k, low-rank decay and output gates, a per-head RMSNorm of the
output. With a :class:`nn.RecurrentCache` it runs incrementally: the
cache's state and convolution tail go in, the updated ones come out.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..framework.tensor import Parameter
from .layer_base import Layer
from .transformer import RecurrentCache

__all__ = ["GatedDeltaAttention", "normal_or_zeros", "gated_delta_step",
           "gated_delta_recurrent", "gated_delta_chunked", "CHUNK",
           "LightningAttention", "lightning_slopes", "lightning_step",
           "lightning_recurrent", "lightning_chunked", "LIGHTNING_CHUNK"]

CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_step(s, q, k, v, g, beta):
    """One token of the recurrence for every row: ``s [B, H, Dk, Dv]``
    float32, ``q``/``k``/``g [B, H, Dk]`` (``g`` the decay's logarithm,
    <= 0), ``v [B, H, Dv]``, ``beta [B, H]``. Returns ``(s_new, o [B, H,
    Dv])``. Products and sums over the state stay elementwise float32:
    the state is never rounded on its way through a matrix unit."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    s = jnp.exp(g)[..., None] * s
    r = v - (k[..., None] * s).sum(-2)
    s = s + (beta[..., None] * k)[..., None] * r[..., None, :]
    return s, (q[..., None] * s).sum(-2)


def gated_delta_recurrent(s, q, k, v, g, beta):
    """:func:`gated_delta_step` over ``[B, T, H, ...]`` sequences, token
    by token. Returns ``(s_final, o [B, T, H, Dv])``."""
    def body(s, xs):
        s, o = gated_delta_step(s, *xs)
        return s, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    s, o = jax.lax.scan(body, s.astype(jnp.float32), xs)
    return s, jnp.moveaxis(o, 0, 1)


def _chunk(s, q, k, v, g, beta):
    """One chunk for every (row, head): ``s [..., Dk, Dv]``, ``q``/``k``/
    ``g [..., C, Dk]``, ``v [..., C, Dv]``, ``beta [..., C]``."""
    c = q.shape[-2]
    cum = jnp.cumsum(g, axis=-2)                       # log of the decay
    t = np.arange(c)                                   # from the chunk's
    incl = t[:, None] >= t[None, :]                    # start to t
    # e[t, i, d] = decay of channel d from token i to token t, i <= t.
    # Every exponent is <= 0; the two matrices of pairwise products are
    # reduced over d elementwise, never through exp(-cum), which can
    # leave float32 over 64 tokens of a fast channel
    e = jnp.exp(jnp.where(incl[..., None],
                          cum[..., :, None, :] - cum[..., None, :, :],
                          -jnp.inf))
    kk = (k[..., :, None, :] * k[..., None, :, :] * e).sum(-1)
    qk = (q[..., :, None, :] * k[..., None, :, :] * e).sum(-1)
    strict = t[:, None] > t[None, :]
    m = jnp.where(strict, beta[..., :, None] * kk, 0.0) \
        + jnp.eye(c, dtype=kk.dtype)
    decay = jnp.exp(cum)
    rhs = beta[..., None] * (v - jnp.matmul(k * decay, s, precision=_HIGHEST))
    u = jax.scipy.linalg.solve_triangular(m, rhs, lower=True,
                                          unit_diagonal=True)
    o = jnp.matmul(q * decay, s, precision=_HIGHEST) \
        + jnp.matmul(qk, u, precision=_HIGHEST)
    last = cum[..., -1:, :]
    s = jnp.swapaxes(jnp.exp(last), -1, -2) * s + jnp.matmul(
        jnp.swapaxes(k * jnp.exp(last - cum), -1, -2), u, precision=_HIGHEST)
    return s, o


def gated_delta_chunked(s, q, k, v, g, beta, chunk=CHUNK):
    """The recurrence over ``[B, T, H, ...]`` sequences in chunks of
    ``chunk`` tokens. A position with ``g == 0`` and ``beta == 0`` leaves
    the state as it was (that is how right-padding is masked, and how a
    ragged last chunk is filled). Returns ``(s_final, o [B, T, H, Dv])``
    as :func:`gated_delta_recurrent` does."""
    f32 = jnp.float32
    t = q.shape[1]
    n = -(-t // chunk)
    pad = n * chunk - t

    def split(a):  # [B, T, H, ...] -> [N, B, H, C, ...]
        a = a.astype(f32)
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = a.reshape((a.shape[0], n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    xs = (split(q), split(k), split(v), split(g), split(beta[..., None]))

    def body(s, xs):
        q, k, v, g, beta = xs
        return _chunk(s, q, k, v, g, beta[..., 0])

    s, o = jax.lax.scan(body, s.astype(f32), xs)      # o [N, B, H, C, Dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)     # [B, N, C, H, Dv]
    return s, o.reshape((o.shape[0], n * chunk) + o.shape[3:])[:, :t]


def normal_or_zeros(shape, std, dtype):
    """A fresh parameter value: normal(0, ``std``) from the framework's
    key stream, or zeros where ``std`` is None (a caller that assigns
    every parameter, as a server loading weights does, need not draw
    billions of values first)."""
    if std is None:
        return jnp.zeros(shape, dtype)
    from ..framework.random import split_key

    return (jax.random.normal(split_key(), shape, jnp.float32)
            * std).astype(dtype)


class GatedDeltaAttention(Layer):
    """The linear-attention mixer. ``hidden -> hidden``; ``num_heads``
    heads of ``head_dim`` (keys and values alike), a causal depthwise
    convolution of ``conv_size`` steps, decay and output gates through
    ``gate_rank``. Weights are ``[in, out]``, no biases; ``dtype`` is
    the parameters' and the activations'; ``initializer_range`` None
    leaves the matrices zero (:func:`normal_or_zeros`)."""

    def __init__(self, hidden_size, num_heads, head_dim, conv_size=4,
                 gate_rank=None, allow_neg_eigval=True, norm_eps=1e-5,
                 initializer_range=0.02, dtype="float32"):
        super().__init__()
        self.hidden_size, self.num_heads = int(hidden_size), int(num_heads)
        self.head_dim, self.conv_size = int(head_dim), int(conv_size)
        self.gate_rank = int(gate_rank or head_dim)
        self.beta_scale = 2.0 if allow_neg_eigval else 1.0
        self.norm_eps = float(norm_eps)
        h, d, r = self.hidden_size, self.num_heads * self.head_dim, \
            self.gate_rank
        std = initializer_range

        def param(name, shape, value=None):
            arr = (normal_or_zeros(shape, std, dtype) if value is None
                   else jnp.asarray(value, dtype))
            setattr(self, name, Parameter.from_array(arr, name=name))

        for name in ("wq", "wk", "wv"):
            param(name, (h, d))
        param("conv_w", (self.conv_size, 3 * d))
        param("a_log", (self.num_heads,),
              np.log(np.linspace(1.0, 16.0, self.num_heads)))
        param("dt_bias", (d,), np.zeros(d))
        param("wa_down", (h, r))
        param("wa_up", (r, d))
        param("wb", (h, self.num_heads))
        param("wg_down", (h, r))
        param("wg_up", (r, d))
        param("o_norm", (self.head_dim,), np.ones(self.head_dim))
        param("wo", (d, h))

    def cache_shapes(self):
        """``(shapes, dtypes)`` of what one slot keeps: the state and the
        convolution's tail."""
        d = self.num_heads * self.head_dim
        return (((self.num_heads, self.head_dim, self.head_dim),
                 (self.conv_size - 1, 3 * d)),
                ("float32", str(self.wq._array.dtype)))

    def forward(self, x, cache=None, valid=None):
        """``x [B, T, hidden]`` (an array). ``cache``: the
        :class:`nn.RecurrentCache` to continue from (else a zero state);
        ``valid [B, T]`` bool: positions that are real tokens (right
        padding is False and does not advance state or tail). Returns
        ``y`` or, with a cache, ``(y, new_cache)``."""
        f32 = jnp.float32
        w = {n: p._array for n, p in self.named_parameters()}
        b, t, _ = x.shape
        nh, hd, kc = self.num_heads, self.head_dim, self.conv_size
        d = nh * hd
        with jax.named_scope("kda"):
            tail = (cache.conv_tail if cache is not None
                    else jnp.zeros((b, kc - 1, 3 * d), x.dtype))
            streams, new_tail = [], []
            for i, name in enumerate(("wq", "wk", "wv")):
                u = jnp.concatenate(
                    [tail[..., i * d:(i + 1) * d].astype(x.dtype),
                     jnp.matmul(x, w[name])], axis=1)      # [B, K-1+T, d]
                cw = w["conv_w"][:, i * d:(i + 1) * d].astype(f32)
                y = sum(u[:, j:j + t].astype(f32) * cw[j] for j in range(kc))
                streams.append(jax.nn.silu(y).reshape(b, t, nh, hd))
                new_tail.append(u)
            q, k, v = streams
            q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + 1e-6) \
                * hd ** -0.5
            k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)
            low = jnp.matmul(x, w["wa_down"])
            g = -jnp.exp(w["a_log"].astype(f32))[:, None] * jax.nn.softplus(
                (jnp.matmul(low, w["wa_up"], preferred_element_type=f32)
                 + w["dt_bias"].astype(f32)).reshape(b, t, nh, hd))
            beta = self.beta_scale * jax.nn.sigmoid(
                jnp.matmul(x, w["wb"], preferred_element_type=f32))
            if valid is not None:
                g = jnp.where(valid[..., None, None], g, 0.0)
                beta = jnp.where(valid[..., None], beta, 0.0)
            s = (cache.state if cache is not None
                 else jnp.zeros((b, nh, hd, hd), f32))
            if t == 1:
                s, o = gated_delta_step(s, q[:, 0], k[:, 0], v[:, 0],
                                        g[:, 0], beta[:, 0])
                o = o[:, None]
            else:
                s, o = gated_delta_chunked(s, q, k, v, g, beta)
            o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                                  + self.norm_eps) * w["o_norm"].astype(f32)
            gate = jax.nn.sigmoid(jnp.matmul(
                jnp.matmul(x, w["wg_down"]), w["wg_up"],
                preferred_element_type=f32)).reshape(b, t, nh, hd)
            y = jnp.matmul((o * gate).reshape(b, t, d).astype(x.dtype),
                           w["wo"])
            if cache is None:
                return y
            u = jnp.concatenate(new_tail, axis=-1)          # [B, K-1+T, 3d]
            if valid is None:
                tail = u[:, t:]
            else:  # the K-1 inputs before the first padded position
                n = valid.sum(-1).astype(jnp.int32)
                tail = jax.vmap(lambda a, i: jax.lax.dynamic_slice_in_dim(
                    a, i, kc - 1, axis=0))(u, n)
            return y, RecurrentCache(s, tail.astype(cache.conv_tail.dtype),
                                     cache.pos)


# -- Lightning attention ------------------------------------------------------
#
# Per head a [Dk, Dv] float32 memory with a decay that is a constant of
# the head and the layer, not of the data:
#
#     S_t = exp(-s_h) S_{t-1} + k_t v_t^T        o_t = scale S_t^T q_t
#
# The functions take the decay's logarithm a position, ``g [B, T, H]``
# (``-s_h`` at a real token, 0 at a padded one, which then leaves the
# state as it was if its key is zero too). Decoding is
# :func:`lightning_step`; a prompt goes through
# :func:`lightning_chunked`: inside a chunk ``(Q K^T o D) V`` with ``D_ts
# = exp(cum_t - cum_s)`` for ``s <= t`` (every exponent <= 0), on the
# matrix unit at full float32 precision, and the state carried from
# chunk to chunk; :func:`lightning_recurrent` is the recurrence itself,
# for tests.

LIGHTNING_CHUNK = 256


def lightning_slopes(num_heads, layer, num_layers):
    """Lightning Attention's decay rates ``s_h`` for one layer: ``2^(-8
    (h + 1) / heads) x (1 - layer / (layers - 1 + 1e-5) + 1e-5)``,
    ``layer`` the layer's index among ``num_layers`` (a cut keeps the
    published index and count). float32 ``[heads]``."""
    h = np.arange(1, int(num_heads) + 1, dtype=np.float64)
    return (2.0 ** (-8.0 * h / int(num_heads)) * (
        1.0 - int(layer) / (int(num_layers) - 1 + 1e-5) + 1e-5)
    ).astype(np.float32)


def lightning_step(s, q, k, v, g):
    """One token for every row: ``s [B, H, Dk, Dv]`` float32, ``q`` /
    ``k [B, H, Dk]``, ``v [B, H, Dv]``, ``g [B, H]`` or ``[H]`` the
    decay's logarithm. Returns ``(s_new, S_new^T q [B, H, Dv])``, all
    elementwise float32: the state never passes a matrix unit."""
    f32 = jnp.float32
    q, k, v, g = (a.astype(f32) for a in (q, k, v, g))
    s = jnp.exp(g)[..., None, None] * s + k[..., :, None] * v[..., None, :]
    return s, (q[..., None] * s).sum(-2)


def lightning_recurrent(s, q, k, v, g):
    """:func:`lightning_step` over ``[B, T, H, ...]`` sequences, token
    by token (``g [B, T, H]``). Returns ``(s_final, o [B, T, H, Dv])``."""
    def body(s, xs):
        return lightning_step(s, *xs)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g))
    s, o = jax.lax.scan(body, s.astype(jnp.float32), xs)
    return s, jnp.moveaxis(o, 0, 1)


def lightning_chunked(s, q, k, v, g, chunk=LIGHTNING_CHUNK):
    """The recurrence over ``[B, T, H, ...]`` sequences in chunks of
    ``chunk`` tokens; ``q``, ``k`` and ``v`` keep their dtype into the
    products (bfloat16 operands are exact there) and everything else is
    float32. A position with ``g == 0`` and a zero key leaves the state
    as it was: right padding, and a ragged last chunk's fill. Returns
    ``(s_final float32, o [B, T, H, Dv] in v's dtype)``, as
    :func:`lightning_recurrent` does in float32."""
    f32 = jnp.float32
    t = q.shape[1]
    chunk = min(chunk, t)
    n = -(-t // chunk)
    pad = n * chunk - t

    def split(a):  # [B, T, H, ...] -> [N, B, H, C, ...]
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = a.reshape((a.shape[0], n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    incl = np.arange(chunk)[:, None] >= np.arange(chunk)[None, :]

    def body(s, xs):
        q, k, v, g = xs                       # [B, H, C, D], g [B, H, C, 1]
        cum = jnp.cumsum(g[..., 0].astype(f32), axis=-1)      # [B, H, C]
        d = jnp.exp(jnp.where(incl, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
        a = jnp.einsum("bhtd,bhsd->bhts", q, k, precision=_HIGHEST,
                       preferred_element_type=f32) * d
        v32 = v.astype(f32)
        o = jnp.matmul(a, v32, precision=_HIGHEST) + jnp.matmul(
            q.astype(f32) * jnp.exp(cum)[..., None], s, precision=_HIGHEST)
        last = cum[..., -1:]
        s = jnp.exp(last)[..., None] * s + jnp.matmul(
            jnp.swapaxes(k.astype(f32) * jnp.exp(last - cum)[..., None],
                         -1, -2), v32, precision=_HIGHEST)
        return s, o.astype(v.dtype)

    s, o = jax.lax.scan(body, s.astype(f32), (
        split(q), split(k), split(v), split(g[..., None])))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)         # [B, N, C, H, Dv]
    return s, o.reshape((o.shape[0], n * chunk) + o.shape[3:])[:, :t]


class LightningAttention(Layer):
    """The Lightning mixer. ``hidden -> hidden``; ``num_heads`` heads of
    ``head_dim`` for q, k and v alike: ``q`` and ``k`` RMS-normalised a
    head (learned gains) and rotated by their position, the recurrence
    above with ``slopes [heads]`` (data: a constant of the program),
    the output scaled by ``head_dim^-0.5``, RMS-normalised over all
    ``heads x head_dim`` channels, gated by ``sigmoid(x Wz)`` and
    projected. No convolution, no activation on q, k or v. Weights are
    ``[in, out]``, no biases; ``initializer_range`` None leaves the
    matrices zero (:func:`normal_or_zeros`)."""

    def __init__(self, hidden_size, num_heads, head_dim, slopes,
                 rope_theta=10000.0, norm_eps=1e-6, chunk=LIGHTNING_CHUNK,
                 initializer_range=0.02, dtype="float32"):
        super().__init__()
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.rope_theta, self.norm_eps = rope_theta, float(norm_eps)
        self.chunk = int(chunk)
        self.slopes = np.asarray(slopes, np.float32).reshape(self.num_heads)
        h, d = int(hidden_size), self.num_heads * self.head_dim
        for name, shape in (("wq", (h, d)), ("wk", (h, d)), ("wv", (h, d)),
                            ("wz", (h, d)), ("wo", (d, h))):
            setattr(self, name, Parameter.from_array(
                normal_or_zeros(shape, initializer_range, dtype), name=name))
        for name, n in (("q_norm", self.head_dim), ("k_norm", self.head_dim),
                        ("o_norm", d)):
            setattr(self, name, Parameter.from_array(
                jnp.ones((n,), dtype), name=name))

    def cache_shapes(self):
        """``(shapes, dtypes)`` of what one slot keeps: the state alone."""
        return (((self.num_heads, self.head_dim, self.head_dim),),
                ("float32",))

    def forward(self, x, positions, cache=None, valid=None):
        """``x [B, T, hidden]`` (an array), ``positions [B, T]``.
        ``cache``: the :class:`nn.RecurrentCache` (state and ``pos``, no
        tail) to continue from, else a zero state; ``valid [B, T]`` bool:
        positions that are real tokens (right padding is False and does
        not advance the state). One token a row is the step, more a
        prefill by chunks. Returns ``y`` or ``(y, new_cache)``."""
        from .gqa import apply_rotary, rms_norm

        f32 = jnp.float32
        b, t, _ = x.shape
        nh, hd = self.num_heads, self.head_dim
        with jax.named_scope("lightning"):
            q, k, v = (jnp.matmul(x, m._array).reshape(b, t, nh, hd)
                       for m in (self.wq, self.wk, self.wv))
            q = apply_rotary(rms_norm(q, self.q_norm._array, self.norm_eps),
                             positions, self.rope_theta)
            k = apply_rotary(rms_norm(k, self.k_norm._array, self.norm_eps),
                             positions, self.rope_theta)
            g = jnp.broadcast_to(-jnp.asarray(self.slopes), (b, t, nh))
            if valid is not None:
                g = jnp.where(valid[..., None], g, 0.0)
                k = jnp.where(valid[..., None, None], k, 0)
            s = (cache[0] if cache is not None
                 else jnp.zeros((b, nh, hd, hd), f32))
            if t == 1:
                with jax.named_scope("lightning_step"):
                    s, o = lightning_step(s, q[:, 0], k[:, 0], v[:, 0],
                                          g[:, 0])
                    o = o[:, None]
            else:
                with jax.named_scope("lightning_scan"):
                    s, o = lightning_chunked(s, q, k, v, g, self.chunk)
            o = (o.astype(f32) * hd ** -0.5).reshape(b, t, nh * hd)
            o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                                  + self.norm_eps) \
                * self.o_norm._array.astype(f32)
            # a prompt's gate leaves its product in the activations'
            # dtype: [T, hidden] float32 is 0.5 GB a layer at 32 k tokens
            gate = jax.nn.sigmoid(jnp.matmul(
                x, self.wz._array,
                preferred_element_type=f32 if t == 1 else None).astype(f32))
            y = jnp.matmul((o * gate).astype(x.dtype), self.wo._array)
        if cache is None:
            return y
        return y, type(cache)(s, *cache[1:])

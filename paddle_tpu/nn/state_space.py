"""A Mamba-2 mixer: a selective state-space recurrence in place of a
cache.

Per head ``h`` (``P`` channels) the layer keeps a ``[P, N]`` float32
state and updates it once a token::

    S_t = a_t S_{t-1} + dt_t x_t B_t^T        a_t = exp(-exp(A_log) dt_t)
    y_t = S_t C_t + D x_t

with a scalar decay ``a_t`` in (0, 1) and a step ``dt_t > 0`` a head and
token, and ``B_t``, ``C_t`` in ``R^N`` shared by the ``heads / groups``
heads of a group. Decoding is :func:`ssm_step`: one token a slot, the
state read and rewritten, nothing that grows with the context. A prompt
goes through :func:`ssm_chunked` (the SSD form): inside a chunk of
``chunk`` tokens the outputs are ``(L o (C B^T)) (dt x)`` with ``L_ts =
prod_{s<r<=t} a_r``, matrix products batched over chunks and heads; each
chunk's own contribution to the state is one more product; only the
states at the chunk borders are carried, by a scan of elementwise
updates; it ends in the state the recurrence would reach.
:func:`ssm_recurrent` is the recurrence itself over a sequence, for
tests. A position with ``dt == 0`` leaves the state as it was: that is
how right padding is masked and how a ragged last chunk is filled.

:class:`Mamba2Mixer` is the mixer built on them: one input projection to
``[z | x B C | dt]``, a short causal depthwise convolution (with bias)
and SiLU on ``x B C``, ``softplus(dt + dt_bias)``, the recurrence, the
gate ``y SiLU(z)`` and then an RMSNorm over each of ``groups`` groups of
channels, and the output projection. With a :class:`nn.RecurrentCache`
it runs incrementally: the cache's state and convolution tail go in, the
updated ones come out.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..framework.tensor import Parameter
from .layer_base import Layer
from .linear_attention import normal_or_zeros
from .transformer import RecurrentCache

__all__ = ["Mamba2Mixer", "ssm_step", "ssm_recurrent", "ssm_chunked"]

_HIGHEST = jax.lax.Precision.HIGHEST


def _to_heads(bc, heads):
    """``[..., G, N]`` as ``[..., H, N]``: head ``h`` uses group ``h //
    (H / G)``."""
    return jnp.repeat(bc, heads // bc.shape[-2], axis=-2)


def ssm_step(s, x, b, c, dt, a, d):
    """One token of the recurrence for every row: ``s [B, H, P, N]``
    float32, ``x [B, H, P]``, ``b``/``c [B, G, N]``, ``dt [B, H]`` (the
    step, after its softplus), ``a [H]`` (``-exp(A_log)``, < 0) and ``d
    [H]``. Returns ``(s_new, y [B, H, P])``. Products and sums over the
    state stay elementwise float32: the state is never rounded on its
    way through a matrix unit."""
    f32 = jnp.float32
    x, b, c, dt, a, d = (v.astype(f32) for v in (x, b, c, dt, a, d))
    h = x.shape[-2]
    s = jnp.exp(a * dt)[..., None, None] * s + (
        (dt[..., None] * x)[..., None] * _to_heads(b, h)[..., None, :])
    y = (s * _to_heads(c, h)[..., None, :]).sum(-1)
    return s, y + d[:, None] * x


def ssm_recurrent(s, x, b, c, dt, a, d):
    """:func:`ssm_step` over ``[B, T, ...]`` sequences, token by token.
    Returns ``(s_final, y [B, T, H, P])``."""
    def body(s, xs):
        return ssm_step(s, *xs, a, d)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, b, c, dt))
    s, y = jax.lax.scan(body, s.astype(jnp.float32), xs)
    return s, jnp.moveaxis(y, 0, 1)


def ssm_chunked(s, x, b, c, dt, a, d, chunk=128):
    """The recurrence over ``[B, T, ...]`` sequences in chunks of
    ``chunk`` tokens: ``x [B, T, H, P]``, ``b``/``c [B, T, G, N]``, ``dt
    [B, T, H]``. Returns ``(s_final, y [B, T, H, P])`` as
    :func:`ssm_recurrent` does. Every exponent is a sum of ``a dt <= 0``
    over a span inside one chunk, so nothing leaves float32."""
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    g, n = b.shape[-2:]
    r = h // g
    nc = -(-t // chunk)
    pad = nc * chunk - t

    def split(v):  # [B, T, ...] -> [B, chunks, chunk, ...]
        v = v.astype(f32)
        if pad:
            v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        return v.reshape((bsz, nc, chunk) + v.shape[2:])

    x, b, c, dt = split(x), split(b), split(c), split(dt)
    a, d = a.astype(f32), d.astype(f32)
    cum = jnp.cumsum(a * dt, axis=2)                    # [B, n, C, H]
    xdt = (x * dt[..., None]).reshape(bsz, nc, chunk, g, r, p)
    # inside a chunk: y_t = sum_{s <= t} L_ts (C_t . B_s) dt_s x_s
    cb = jnp.einsum("bctgn,bcsgn->bcgts", c, b, precision=_HIGHEST)
    i = np.arange(chunk)
    decay = jnp.exp(jnp.where(
        (i[:, None] >= i[None, :])[:, :, None],
        cum[:, :, :, None, :] - cum[:, :, None, :, :], -jnp.inf))
    m = decay.reshape(bsz, nc, chunk, chunk, g, r) \
        * jnp.moveaxis(cb, 2, -1)[..., None]            # [B, n, t, s, G, r]
    y = jnp.einsum("bctsgr,bcsgrp->bctgrp", m, xdt, precision=_HIGHEST)
    # what each chunk adds to the state by its end
    last = cum[:, :, -1]                                # [B, n, H]
    to_end = jnp.exp(last[:, :, None] - cum).reshape(bsz, nc, chunk, g, r)
    add = jnp.einsum("bcsgrp,bcsgn->bcgrpn", xdt * to_end[..., None], b,
                     precision=_HIGHEST).reshape(bsz, nc, h, p, n)

    def border(s, xs):  # the state at each chunk's start
        gain, add = xs
        return gain[..., None, None] * s + add, s

    s, starts = jax.lax.scan(
        border, s.astype(f32),
        (jnp.moveaxis(jnp.exp(last), 1, 0), jnp.moveaxis(add, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1).reshape(bsz, nc, g, r, p, n)
    y = y + jnp.exp(cum).reshape(bsz, nc, chunk, g, r)[..., None] \
        * jnp.einsum("bctgn,bcgrpn->bctgrp", c, starts, precision=_HIGHEST)
    y = y.reshape(bsz, nc * chunk, h, p)[:, :t]
    return s, y + d[:, None] * x.reshape(bsz, nc * chunk, h, p)[:, :t]


class Mamba2Mixer(Layer):
    """The state-space mixer. ``hidden -> hidden``; ``num_heads`` heads
    of ``head_dim`` channels with a state of ``state_size`` a channel,
    ``B`` and ``C`` shared inside each of ``groups`` groups of heads, a
    causal depthwise convolution of ``conv_size`` steps over ``x B C``,
    prompts in chunks of ``chunk`` tokens. Weights are ``[in, out]`` and
    only the convolution has a bias; ``dtype`` is the parameters' and
    the activations'; ``initializer_range`` None leaves the matrices and
    taps zero (:func:`normal_or_zeros`). ``dt_limits``: the steps that
    ``dt_bias`` starts at are spread log-uniformly between them and held
    above ``dt_floor``."""

    def __init__(self, hidden_size, num_heads, head_dim, state_size,
                 groups=1, conv_size=4, chunk=128, norm_eps=1e-5,
                 dt_limits=(1e-3, 1e-1), dt_floor=1e-4,
                 initializer_range=0.02, dtype="float32"):
        super().__init__()
        self.hidden_size, self.num_heads = int(hidden_size), int(num_heads)
        self.head_dim, self.state_size = int(head_dim), int(state_size)
        self.groups, self.conv_size = int(groups), int(conv_size)
        self.chunk, self.norm_eps = int(chunk), float(norm_eps)
        h, nh = self.hidden_size, self.num_heads
        d = self.inner = nh * self.head_dim
        self.conv_dim = d + 2 * self.groups * self.state_size
        std = initializer_range

        def param(name, shape, value=None, scale=std):
            arr = (normal_or_zeros(shape, scale, dtype) if value is None
                   else jnp.asarray(value, dtype))
            setattr(self, name, Parameter.from_array(arr, name=name))

        param("in_proj", (h, d + self.conv_dim + nh))
        param("conv_w", (self.conv_size, self.conv_dim),
              scale=None if std is None else 0.5)
        param("conv_b", (self.conv_dim,), np.zeros(self.conv_dim))
        step = np.maximum(np.geomspace(*dt_limits, nh), dt_floor)
        param("dt_bias", (nh,), step + np.log(-np.expm1(-step)))
        param("a_log", (nh,), np.log(np.linspace(1.0, 16.0, nh)))
        param("d_skip", (nh,), np.ones(nh))
        param("norm", (d,), np.ones(d))
        param("out_proj", (d, h))

    def cache_shapes(self):
        """``(shapes, dtypes)`` of what one slot keeps: the state and the
        convolution's tail (``conv_size - 1`` inputs, channels minor)."""
        return (((self.num_heads, self.head_dim, self.state_size),
                 (self.conv_size - 1, self.conv_dim)),
                ("float32", str(self.in_proj._array.dtype)))

    def forward(self, x, cache=None, valid=None):
        """``x [B, T, hidden]`` (an array). ``cache``: the
        :class:`nn.RecurrentCache` to continue from (else a zero state);
        ``valid [B, T]`` bool: positions that are real tokens (right
        padding is False and does not advance state or tail). One token
        a row is the step; more is a prompt from the cache's state by
        chunks. Returns ``y`` or, with a cache, ``(y, new_cache)``."""
        f32 = jnp.float32
        w = {n: p._array for n, p in self.named_parameters()}
        b, t, _ = x.shape
        nh, hd, kc = self.num_heads, self.head_dim, self.conv_size
        d, g, n = self.inner, self.groups, self.state_size
        with jax.named_scope("ssm"):
            proj = jnp.matmul(x, w["in_proj"])
            z, xbc, dt = (proj[..., :d], proj[..., d:d + self.conv_dim],
                          proj[..., d + self.conv_dim:])
            tail = (cache.conv_tail.astype(x.dtype) if cache is not None
                    else jnp.zeros((b, kc - 1, self.conv_dim), x.dtype))
            u = jnp.concatenate([tail, xbc], axis=1)     # [B, K-1+T, conv]
            cw = w["conv_w"].astype(f32)
            xbc = jax.nn.silu(
                sum(u[:, j:j + t].astype(f32) * cw[j] for j in range(kc))
                + w["conv_b"].astype(f32))
            xs = xbc[..., :d].reshape(b, t, nh, hd)
            bs = xbc[..., d:d + g * n].reshape(b, t, g, n)
            cs = xbc[..., d + g * n:].reshape(b, t, g, n)
            dt = jax.nn.softplus(dt.astype(f32) + w["dt_bias"].astype(f32))
            if valid is not None:
                dt = jnp.where(valid[..., None], dt, 0.0)
            a = -jnp.exp(w["a_log"].astype(f32))
            skip = w["d_skip"].astype(f32)
            s = (cache.state if cache is not None
                 else jnp.zeros((b, nh, hd, n), f32))
            if t == 1:
                with jax.named_scope("ssm_step"):
                    s, y = ssm_step(s, xs[:, 0], bs[:, 0], cs[:, 0],
                                    dt[:, 0], a, skip)
                y = y[:, None]
            else:
                with jax.named_scope("ssm_scan"):
                    s, y = ssm_chunked(s, xs, bs, cs, dt, a, skip,
                                       self.chunk)
            # the gate first, then the statistics of each group
            y = (y.reshape(b, t, d) * jax.nn.silu(z.astype(f32))).reshape(
                b, t, g, d // g)
            y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True)
                                  + self.norm_eps)
            y = y.reshape(b, t, d) * w["norm"].astype(f32)
            y = jnp.matmul(y.astype(x.dtype), w["out_proj"])
            if cache is None:
                return y
            if valid is None:
                tail = u[:, t:]
            else:  # the K-1 inputs before the first padded position
                last = valid.sum(-1).astype(jnp.int32)
                tail = jax.vmap(lambda v, i: jax.lax.dynamic_slice_in_dim(
                    v, i, kc - 1, axis=0))(u, last)
            return y, RecurrentCache(s, tail.astype(cache.conv_tail.dtype),
                                     cache.pos)

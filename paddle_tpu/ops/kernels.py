"""Pure-JAX op kernels.

Reference parity: paddle/fluid/operators/ (~457 op types; SURVEY.md §2.2).
Each kernel is a pure function over jax arrays; XLA fuses elementwise chains
into surrounding matmuls automatically, so kernels stay simple and the
executor jits whole blocks (SURVEY.md §7 step 2). CUDA kernels in the
reference map to jnp/lax here; hand-fused CUDA ops map to XLA fusion or
pallas kernels (ops/pallas_kernels.py).

Conventions:
- positional args are tensor (traced) inputs; keyword args are static attrs
  (except PRNG keys, which are traced values passed as kwargs — they carry
  no gradient so keeping them out of the vjp positional list is free).
- NCHW is the default conv/pool layout, matching fluid.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op

# ---------------------------------------------------------------------------
# Elementwise binary (operators/elementwise/)
# ---------------------------------------------------------------------------


def _register_binary(name, fn):
    register_op(name)(fn)


_register_binary("elementwise_add", lambda x, y, **kw: jnp.add(x, y))
_register_binary("elementwise_sub", lambda x, y, **kw: jnp.subtract(x, y))
_register_binary("elementwise_mul", lambda x, y, **kw: jnp.multiply(x, y))
_register_binary("elementwise_div", lambda x, y, **kw: jnp.divide(x, y))
_register_binary("elementwise_pow", lambda x, y, **kw: jnp.power(x, y))
_register_binary("elementwise_max", lambda x, y, **kw: jnp.maximum(x, y))
_register_binary("elementwise_min", lambda x, y, **kw: jnp.minimum(x, y))
_register_binary("elementwise_mod", lambda x, y, **kw: jnp.mod(x, y))
_register_binary("elementwise_floordiv", lambda x, y, **kw: jnp.floor_divide(x, y))
_register_binary("atan2", lambda x, y, **kw: jnp.arctan2(x, y))

_register_binary("equal", lambda x, y, **kw: jnp.equal(x, y))
_register_binary("not_equal", lambda x, y, **kw: jnp.not_equal(x, y))
_register_binary("less_than", lambda x, y, **kw: jnp.less(x, y))
_register_binary("less_equal", lambda x, y, **kw: jnp.less_equal(x, y))
_register_binary("greater_than", lambda x, y, **kw: jnp.greater(x, y))
_register_binary("greater_equal", lambda x, y, **kw: jnp.greater_equal(x, y))

_register_binary("logical_and", lambda x, y, **kw: jnp.logical_and(x, y))
_register_binary("logical_or", lambda x, y, **kw: jnp.logical_or(x, y))
_register_binary("logical_xor", lambda x, y, **kw: jnp.logical_xor(x, y))
register_op("logical_not")(lambda x, **kw: jnp.logical_not(x))

_register_binary("bitwise_and", lambda x, y, **kw: jnp.bitwise_and(x, y))
_register_binary("bitwise_or", lambda x, y, **kw: jnp.bitwise_or(x, y))
_register_binary("bitwise_xor", lambda x, y, **kw: jnp.bitwise_xor(x, y))
register_op("bitwise_not")(lambda x, **kw: jnp.bitwise_not(x))

# ---------------------------------------------------------------------------
# Elementwise unary (operators/activation_op.cc and friends)
# ---------------------------------------------------------------------------

_UNARY = {
    "abs": jnp.abs,
    "exp": jnp.exp,
    "expm1": jnp.expm1,
    "log": jnp.log,
    "log2": jnp.log2,
    "log10": jnp.log10,
    "log1p": jnp.log1p,
    "sqrt": jnp.sqrt,
    "rsqrt": lambda x: lax.rsqrt(x),
    "square": jnp.square,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "asin": jnp.arcsin,
    "acos": jnp.arccos,
    "atan": jnp.arctan,
    "sinh": jnp.sinh,
    "cosh": jnp.cosh,
    "asinh": jnp.arcsinh,
    "acosh": jnp.arccosh,
    "atanh": jnp.arctanh,
    "tanh": jnp.tanh,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "round": jnp.round,
    "sign": jnp.sign,
    "reciprocal": lambda x: 1.0 / x,
    "erf": jax.scipy.special.erf,
    "erfinv": jax.scipy.special.erfinv,
    "digamma": jax.scipy.special.digamma,
    "lgamma": jax.scipy.special.gammaln,
    "sigmoid": jax.nn.sigmoid,
    "logsigmoid": jax.nn.log_sigmoid,
    "softsign": jax.nn.soft_sign,
    "isnan": jnp.isnan,
    "isinf": jnp.isinf,
    "isfinite": jnp.isfinite,
    "trunc": jnp.trunc,
}
for _name, _fn in _UNARY.items():
    register_op(_name)(partial(lambda f, x, **kw: f(x), _fn))


@register_op("scale")
def scale(x, *, scale=1.0, bias=0.0, bias_after_scale=True):
    # operators/scale_op.cc
    if bias_after_scale:
        return x * scale + bias
    return (x + bias) * scale


@register_op("clip")
def clip(x, *, min=None, max=None):
    return jnp.clip(x, min, max)


@register_op("pow")
def pow_(x, *, factor=1.0):
    return jnp.power(x, factor)


# Activations with attrs ----------------------------------------------------


@register_op("relu")
def relu(x, **kw):
    return jax.nn.relu(x)


@register_op("relu6")
def relu6(x, *, threshold=6.0):
    return jnp.clip(x, 0.0, threshold)


@register_op("leaky_relu")
def leaky_relu(x, *, alpha=0.01):
    return jnp.where(x >= 0, x, alpha * x)


@register_op("elu")
def elu(x, *, alpha=1.0):
    return jax.nn.elu(x, alpha)


@register_op("selu")
def selu(x, **kw):
    return jax.nn.selu(x)


@register_op("celu")
def celu(x, *, alpha=1.0):
    return jax.nn.celu(x, alpha)


@register_op("gelu")
def gelu(x, *, approximate=False):
    return jax.nn.gelu(x, approximate=approximate)


@register_op("hard_sigmoid")
def hard_sigmoid(x, *, slope=0.1666667, offset=0.5):
    return jnp.clip(x * slope + offset, 0.0, 1.0)


@register_op("hard_swish")
def hard_swish(x, *, threshold=6.0, scale=6.0, offset=3.0):
    return x * jnp.clip(x + offset, 0.0, threshold) / scale


@register_op("hard_tanh")
def hard_tanh(x, *, min=-1.0, max=1.0):
    return jnp.clip(x, min, max)


@register_op("hard_shrink")
def hard_shrink(x, *, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


@register_op("softshrink")
def softshrink(x, *, lambda_=0.5):
    return jnp.where(x > lambda_, x - lambda_, jnp.where(x < -lambda_, x + lambda_, 0.0))


@register_op("tanh_shrink")
def tanh_shrink(x, **kw):
    return x - jnp.tanh(x)


@register_op("swish")
def swish(x, **kw):
    return jax.nn.silu(x)


@register_op("mish")
def mish(x, **kw):
    return x * jnp.tanh(jax.nn.softplus(x))


@register_op("softplus")
def softplus(x, *, beta=1.0, threshold=20.0):
    scaled = beta * x
    return jnp.where(scaled > threshold, x, jax.nn.softplus(scaled) / beta)


@register_op("prelu")
def prelu(x, alpha, **kw):
    return jnp.where(x >= 0, x, alpha * x)


@register_op("softmax")
def softmax(x, *, axis=-1):
    return jax.nn.softmax(x, axis=axis)


@register_op("log_softmax")
def log_softmax(x, *, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


@register_op("maxout")
def maxout(x, *, groups, axis=1):
    shape = list(x.shape)
    c = shape[axis]
    shape[axis : axis + 1] = [c // groups, groups]
    return jnp.max(jnp.reshape(x, shape), axis=axis + 1)


# ---------------------------------------------------------------------------
# Matrix ops (operators/matmul_op.cc, mul_op.cc, bmm, dot)
# ---------------------------------------------------------------------------


@register_op("matmul")
def matmul(x, y, *, transpose_x=False, transpose_y=False):
    if transpose_x:
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if transpose_y:
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    return jnp.matmul(x, y)


@register_op("mul")
def mul(x, y, *, x_num_col_dims=1, y_num_col_dims=1):
    # operators/mul_op.cc — flatten then 2D matmul
    xs = x.reshape((math.prod(x.shape[:x_num_col_dims]), -1))
    ys = y.reshape((math.prod(y.shape[:y_num_col_dims]), -1))
    out = xs @ ys
    return out.reshape(x.shape[:x_num_col_dims] + y.shape[y_num_col_dims:])


@register_op("bmm")
def bmm(x, y, **kw):
    return jnp.matmul(x, y)


@register_op("dot")
def dot(x, y, **kw):
    return jnp.sum(x * y, axis=-1)


@register_op("addmm")
def addmm(input, x, y, *, beta=1.0, alpha=1.0):
    return beta * input + alpha * (x @ y)


@register_op("linear")
def linear(x, w, b=None, **kw):
    # fused x@w+b — the fc_fuse_pass equivalent falls out of XLA fusion
    out = jnp.matmul(x, w)
    if b is not None:
        out = out + b
    return out


@register_op("cross")
def cross(x, y, *, axis=-1):
    return jnp.cross(x, y, axis=axis)


@register_op("cholesky")
def cholesky(x, *, upper=False):
    l = jnp.linalg.cholesky(x)
    return jnp.swapaxes(l, -1, -2) if upper else l


@register_op("matrix_power")
def matrix_power(x, *, n):
    return jnp.linalg.matrix_power(x, n)


@register_op("inverse")
def inverse(x, **kw):
    return jnp.linalg.inv(x)


@register_op("einsum")
def einsum(*operands, equation):
    return jnp.einsum(equation, *operands)


# ---------------------------------------------------------------------------
# Reductions (operators/reduce_ops/)
# ---------------------------------------------------------------------------


def _norm_axis(axis):
    if axis is None:
        return None
    if isinstance(axis, (list, tuple)):
        return tuple(axis)
    return int(axis)


@register_op("reduce_sum")
def reduce_sum(x, *, dim=None, keep_dim=False):
    return jnp.sum(x, axis=_norm_axis(dim), keepdims=keep_dim)


@register_op("reduce_mean")
def reduce_mean(x, *, dim=None, keep_dim=False):
    return jnp.mean(x, axis=_norm_axis(dim), keepdims=keep_dim)


@register_op("reduce_max")
def reduce_max(x, *, dim=None, keep_dim=False):
    return jnp.max(x, axis=_norm_axis(dim), keepdims=keep_dim)


@register_op("reduce_min")
def reduce_min(x, *, dim=None, keep_dim=False):
    return jnp.min(x, axis=_norm_axis(dim), keepdims=keep_dim)


@register_op("reduce_prod")
def reduce_prod(x, *, dim=None, keep_dim=False):
    return jnp.prod(x, axis=_norm_axis(dim), keepdims=keep_dim)


@register_op("reduce_any")
def reduce_any(x, *, dim=None, keep_dim=False):
    return jnp.any(x, axis=_norm_axis(dim), keepdims=keep_dim)


@register_op("reduce_all")
def reduce_all(x, *, dim=None, keep_dim=False):
    return jnp.all(x, axis=_norm_axis(dim), keepdims=keep_dim)


@register_op("logsumexp")
def logsumexp(x, *, axis=None, keepdim=False):
    return jax.scipy.special.logsumexp(x, axis=_norm_axis(axis), keepdims=keepdim)


@register_op("arg_max")
def arg_max(x, *, axis=None, keepdims=False, dtype="int64"):
    out = jnp.argmax(x, axis=axis, keepdims=keepdims if axis is not None else False)
    return out.astype(dtype)


@register_op("arg_min")
def arg_min(x, *, axis=None, keepdims=False, dtype="int64"):
    out = jnp.argmin(x, axis=axis, keepdims=keepdims if axis is not None else False)
    return out.astype(dtype)


@register_op("p_norm")
def p_norm(x, *, porder=2.0, axis=None, keepdim=False, epsilon=1e-12):
    axis = _norm_axis(axis)
    if porder == float("inf"):
        return jnp.max(jnp.abs(x), axis=axis, keepdims=keepdim)
    if porder == float("-inf"):
        return jnp.min(jnp.abs(x), axis=axis, keepdims=keepdim)
    return jnp.power(
        jnp.sum(jnp.power(jnp.abs(x), porder), axis=axis, keepdims=keepdim) + epsilon,
        1.0 / porder,
    )


@register_op("cumsum")
def cumsum(x, *, axis=None, flatten=False):
    if axis is None or flatten:
        return jnp.cumsum(x.reshape(-1))
    return jnp.cumsum(x, axis=axis)


@register_op("cumprod")
def cumprod(x, *, dim=None):
    return jnp.cumprod(x, axis=dim)


@register_op("mean_all")
def mean_all(x, **kw):
    # operators/mean_op.cc — full mean to scalar
    return jnp.mean(x)


# ---------------------------------------------------------------------------
# Tensor manipulation (reshape/transpose/concat/split/…)
# ---------------------------------------------------------------------------


@register_op("reshape")
def reshape(x, *, shape):
    return jnp.reshape(x, shape)


@register_op("transpose")
def transpose(x, *, perm):
    return jnp.transpose(x, perm)


@register_op("flatten")
def flatten(x, *, start_axis=0, stop_axis=-1):
    nd = x.ndim
    if nd == 0:
        return x.reshape((1,))
    start = start_axis % nd
    stop = stop_axis % nd
    shape = list(x.shape[:start]) + [-1] + list(x.shape[stop + 1 :])
    return jnp.reshape(x, shape)


@register_op("squeeze")
def squeeze(x, *, axes=None):
    if axes is None or axes == []:
        return jnp.squeeze(x)
    axes = [axes] if isinstance(axes, int) else list(axes)
    axes = tuple(a % x.ndim for a in axes if x.shape[a % x.ndim] == 1)
    return jnp.squeeze(x, axis=axes) if axes else x


@register_op("unsqueeze")
def unsqueeze(x, *, axes):
    axes = [axes] if isinstance(axes, int) else list(axes)
    out = x
    for a in axes:
        out = jnp.expand_dims(out, a)
    return out


@register_op("concat")
def concat(*xs, axis=0):
    return jnp.concatenate(xs, axis=axis)


@register_op("split", num_outputs=-1)
def split(x, *, num_or_sections, axis=0):
    if isinstance(num_or_sections, int):
        return tuple(jnp.split(x, num_or_sections, axis=axis))
    sections = list(num_or_sections)
    total = x.shape[axis]
    if any(s in (-1, None) for s in sections):
        known = sum(s for s in sections if s not in (-1, None))
        sections = [total - known if s in (-1, None) else s for s in sections]
    idx = []
    acc = 0
    for s in sections[:-1]:
        acc += s
        idx.append(acc)
    return tuple(jnp.split(x, idx, axis=axis))


@register_op("stack")
def stack(*xs, axis=0):
    return jnp.stack(xs, axis=axis)


@register_op("unstack", num_outputs=-1)
def unstack(x, *, axis=0, num=None):
    n = num or x.shape[axis]
    return tuple(jnp.squeeze(s, axis=axis) for s in jnp.split(x, n, axis=axis))


@register_op("slice")
def slice_(x, *, axes, starts, ends, strides=None):
    # operators/slice_op.cc semantics (clamped ends, negative indices)
    out = x
    strides = strides or [1] * len(axes)
    index = [slice(None)] * x.ndim
    for ax, st, en, sd in zip(axes, starts, ends, strides):
        index[ax] = slice(st, en, sd)
    return out[tuple(index)]


@register_op("strided_slice")
def strided_slice(x, *, axes, starts, ends, strides):
    return slice_(x, axes=axes, starts=starts, ends=ends, strides=strides)


@register_op("getitem")
def getitem(x, *, idx):
    return x[idx]


@register_op("gather")
def gather(x, index, *, axis=0):
    if index.ndim == 0:
        index = index[None]
    return jnp.take(x, index, axis=axis)


@register_op("gather_nd")
def gather_nd(x, index, **kw):
    idx = tuple(jnp.moveaxis(index, -1, 0))
    return x[idx]


@register_op("scatter")
def scatter(x, index, updates, *, overwrite=True):
    if overwrite:
        return x.at[index].set(updates)
    # paddle scatter(overwrite=False) accumulates on zeroed rows
    zeroed = x.at[index].set(jnp.zeros_like(updates))
    return zeroed.at[index].add(updates)


@register_op("scatter_nd_add")
def scatter_nd_add(x, index, updates, **kw):
    idx = tuple(jnp.moveaxis(index, -1, 0))
    return x.at[idx].add(updates)


@register_op("index_select")
def index_select(x, index, *, axis=0):
    return jnp.take(x, index, axis=axis)


@register_op("index_sample")
def index_sample(x, index, **kw):
    return jnp.take_along_axis(x, index, axis=-1)


@register_op("take_along_axis")
def take_along_axis(x, index, *, axis):
    return jnp.take_along_axis(x, index, axis=axis)


@register_op("tile")
def tile(x, *, repeat_times):
    return jnp.tile(x, repeat_times)


@register_op("expand")
def expand(x, *, shape):
    # -1 keeps the corresponding (trailing-aligned) input dim
    offset = len(shape) - x.ndim
    shape = [
        x.shape[i - offset] if (s == -1 and i >= offset) else s
        for i, s in enumerate(shape)
    ]
    return jnp.broadcast_to(x, shape)


@register_op("broadcast_to")
def broadcast_to(x, *, shape):
    return jnp.broadcast_to(x, shape)


@register_op("where")
def where(cond, x, y, **kw):
    return jnp.where(cond, x, y)


@register_op("masked_fill")
def masked_fill(x, mask, *, value):
    return jnp.where(mask, value, x)


@register_op("pad")
def pad(x, *, paddings, mode="constant", value=0.0):
    # paddings: flat [before0, after0, before1, after1, ...]
    pairs = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(len(paddings) // 2)]
    while len(pairs) < x.ndim:
        pairs.insert(0, (0, 0))
    if mode == "constant":
        return jnp.pad(x, pairs, mode="constant", constant_values=value)
    jmode = {"reflect": "reflect", "replicate": "edge", "circular": "wrap"}[mode]
    return jnp.pad(x, pairs, mode=jmode)


@register_op("roll")
def roll(x, *, shifts, axis=None):
    return jnp.roll(x, shifts, axis=axis)


@register_op("flip")
def flip(x, *, axis):
    return jnp.flip(x, axis=axis)


@register_op("tril")
def tril(x, *, diagonal=0):
    return jnp.tril(x, k=diagonal)


@register_op("triu")
def triu(x, *, diagonal=0):
    return jnp.triu(x, k=diagonal)


@register_op("diag")
def diag(x, *, offset=0, padding_value=0.0):
    if x.ndim == 1:
        out = jnp.diag(x, k=offset)
        if padding_value != 0.0:
            mask = jnp.diag(jnp.ones_like(x), k=offset).astype(bool)
            out = jnp.where(mask, out, padding_value)
        return out
    return jnp.diagonal(x, offset=offset)


@register_op("cast")
def cast(x, *, dtype):
    return x.astype(dtype)


@register_op("assign")
def assign(x, **kw):
    return x + 0 if jnp.issubdtype(x.dtype, jnp.number) else jnp.array(x)


@register_op("one_hot")
def one_hot(x, *, num_classes):
    return jax.nn.one_hot(x, num_classes, dtype=jnp.float32)


@register_op("top_k", num_outputs=2)
def top_k(x, *, k, axis=-1, largest=True, sorted=True):
    if largest:
        vals, idx = lax.top_k(jnp.moveaxis(x, axis, -1), k)
    else:
        vals, idx = lax.top_k(-jnp.moveaxis(x, axis, -1), k)
        vals = -vals
    return jnp.moveaxis(vals, -1, axis), jnp.moveaxis(idx, -1, axis).astype(jnp.int64)


@register_op("argsort", num_outputs=2)
def argsort(x, *, axis=-1, descending=False):
    sign = -1 if descending else 1
    idx = jnp.argsort(sign * x, axis=axis, stable=True)
    vals = jnp.take_along_axis(x, idx, axis=axis)
    return vals, idx.astype(jnp.int64)


@register_op("sort")
def sort(x, *, axis=-1, descending=False):
    out = jnp.sort(x, axis=axis)
    return jnp.flip(out, axis=axis) if descending else out


@register_op("kthvalue", num_outputs=2)
def kthvalue(x, *, k, axis=-1, keepdim=False):
    vals = jnp.sort(x, axis=axis)
    idx = jnp.argsort(x, axis=axis, stable=True)
    v = jnp.take(vals, k - 1, axis=axis)
    i = jnp.take(idx, k - 1, axis=axis)
    if keepdim:
        v, i = jnp.expand_dims(v, axis), jnp.expand_dims(i, axis)
    return v, i.astype(jnp.int64)


@register_op("unbind", num_outputs=-1)
def unbind(x, *, axis=0):
    return tuple(jnp.squeeze(s, axis) for s in jnp.split(x, x.shape[axis], axis=axis))


@register_op("meshgrid", num_outputs=-1)
def meshgrid(*xs, **kw):
    return tuple(jnp.meshgrid(*xs, indexing="ij"))


@register_op("repeat_interleave")
def repeat_interleave(x, *, repeats, axis=None):
    return jnp.repeat(x, repeats, axis=axis)


@register_op("shard_index")
def shard_index(x, *, index_num, nshards, shard_id, ignore_value=-1):
    shard_size = (index_num + nshards - 1) // nshards
    lo, hi = shard_id * shard_size, (shard_id + 1) * shard_size
    in_shard = (x >= lo) & (x < hi)
    return jnp.where(in_shard, x - lo, ignore_value)


# ---------------------------------------------------------------------------
# NN ops (conv/pool/norm/embedding/dropout) — operators/conv_op.cc etc.
# ---------------------------------------------------------------------------


def _pair(v):
    return tuple(v) if isinstance(v, (list, tuple)) else (v, v)


@register_op("conv2d")
def conv2d(x, w, *, stride=1, padding=0, dilation=1, groups=1, data_format="NCHW"):
    stride, dilation = _pair(stride), _pair(dilation)
    if isinstance(padding, str):
        pad = padding.upper()  # "SAME" / "VALID"
    elif (
        isinstance(padding, (list, tuple)) and len(padding) == 2
        and all(isinstance(q, (list, tuple)) for q in padding)
    ):
        pad = [tuple(padding[0]), tuple(padding[1])]  # [(t,b),(l,r)]
    else:
        p = _pair(padding) if not (isinstance(padding, (list, tuple)) and len(padding) == 4) else padding
        if len(p) == 2:
            pad = [(p[0], p[0]), (p[1], p[1])]
        else:
            pad = [(p[0], p[1]), (p[2], p[3])]
    # weight layout is OIHW for both data formats (paddle convention); for
    # NHWC only the activation layout changes. XLA:TPU folds the weight
    # relayout into the conv.
    dn = lax.conv_dimension_numbers(
        x.shape, w.shape, ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "OIHW", "NHWC")
    )
    return lax.conv_general_dilated(
        x, w, window_strides=stride, padding=pad, rhs_dilation=dilation,
        dimension_numbers=dn, feature_group_count=groups,
        preferred_element_type=jnp.float32 if x.dtype == jnp.float32 else None,
    )


@register_op("depthwise_conv2d")
def depthwise_conv2d(x, w, *, stride=1, padding=0, dilation=1, groups=None, data_format="NCHW"):
    c = x.shape[1] if data_format == "NCHW" else x.shape[-1]
    return conv2d(x, w, stride=stride, padding=padding, dilation=dilation, groups=c, data_format=data_format)


@register_op("conv2d_transpose")
def conv2d_transpose(x, w, *, stride=1, padding=0, output_padding=0, dilation=1, groups=1, data_format="NCHW"):
    stride, dilation = _pair(stride), _pair(dilation)
    p = _pair(padding)
    opad = _pair(output_padding)
    # w layout IOHW for paddle conv2d_transpose
    kh = (w.shape[2] - 1) * dilation[0] + 1
    kw_ = (w.shape[3] - 1) * dilation[1] + 1
    pad = [
        (kh - 1 - p[0], kh - 1 - p[0] + opad[0]),
        (kw_ - 1 - p[1], kw_ - 1 - p[1] + opad[1]),
    ]
    w_flip = jnp.flip(w, axis=(2, 3))
    w_t = jnp.swapaxes(w_flip, 0, 1)  # -> OIHW with O=out
    if groups > 1:
        # grouped transpose conv: w is (in, out//g, kh, kw)
        in_c = x.shape[1]
        w_g = w_flip.reshape(groups, in_c // groups, *w.shape[1:])
        w_t = jnp.concatenate([jnp.swapaxes(w_g[g], 0, 1) for g in range(groups)], axis=0)
    dn = lax.conv_dimension_numbers(x.shape, w_t.shape, ("NCHW", "OIHW", "NCHW"))
    return lax.conv_general_dilated(
        x, w_t, window_strides=(1, 1), padding=pad, lhs_dilation=stride,
        rhs_dilation=dilation, dimension_numbers=dn, feature_group_count=groups,
    )


@register_op("conv1d")
def conv1d(x, w, *, stride=1, padding=0, dilation=1, groups=1):
    x4 = x[:, :, None, :]
    w4 = w[:, :, None, :]
    s = stride if isinstance(stride, int) else stride[0]
    d = dilation if isinstance(dilation, int) else dilation[0]
    p = padding if isinstance(padding, int) else padding[0]
    out = conv2d(x4, w4, stride=(1, s), padding=[(0, 0), (p, p)], dilation=(1, d), groups=groups)
    return out[:, :, 0, :]


@register_op("pool2d")
def pool2d(x, *, kernel_size, stride=None, padding=0, pooling_type="max",
           ceil_mode=False, exclusive=True, adaptive=False, data_format="NCHW"):
    if adaptive:
        return _adaptive_pool2d(x, kernel_size, pooling_type, data_format)
    ks = _pair(kernel_size)
    st = _pair(stride) if stride is not None else ks
    p = _pair(padding)
    h_ax = 2 if data_format == "NCHW" else 1
    spatial = x.shape[h_ax:h_ax + 2]
    if data_format == "NCHW":
        window = (1, 1, ks[0], ks[1])
        strides = (1, 1, st[0], st[1])
    else:  # NHWC
        window = (1, ks[0], ks[1], 1)
        strides = (1, st[0], st[1], 1)
    hp, wp = (p[0], p[0]), (p[1], p[1])
    if ceil_mode:
        extra = []
        for dim, k, s, pp in zip(spatial, ks, st, p):
            out_ceil = -(-(dim + 2 * pp - k) // s) + 1
            need = (out_ceil - 1) * s + k - (dim + 2 * pp)
            extra.append(max(0, need))
        hp, wp = (p[0], p[0] + extra[0]), (p[1], p[1] + extra[1])
    if data_format == "NCHW":
        pads = ((0, 0), (0, 0), hp, wp)
    else:
        pads = ((0, 0), hp, wp, (0, 0))
    if pooling_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, strides, pads)
    # avg
    summed = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
    if exclusive and (p != (0, 0) or ceil_mode):
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return summed / counts
    return summed / (ks[0] * ks[1])


def _adaptive_pool2d(x, output_size, pooling_type, data_format="NCHW"):
    oh, ow = _pair(output_size)
    if data_format == "NHWC":
        # delegate: XLA folds the transposes into the reductions
        y = _adaptive_pool2d(jnp.moveaxis(x, 3, 1), output_size,
                             pooling_type)
        return jnp.moveaxis(y, 1, 3)
    n, c, h, w = x.shape
    if h % oh == 0 and w % ow == 0:
        xr = x.reshape(n, c, oh, h // oh, ow, w // ow)
        red = jnp.max if pooling_type == "max" else jnp.mean
        return red(xr, axis=(3, 5))
    # general adaptive pooling via per-output-window reduce
    out = jnp.zeros((n, c, oh, ow), x.dtype)
    for i in range(oh):
        hs, he = (i * h) // oh, -(-((i + 1) * h) // oh)
        for j in range(ow):
            ws, we = (j * w) // ow, -(-((j + 1) * w) // ow)
            win = x[:, :, hs:he, ws:we]
            red = jnp.max if pooling_type == "max" else jnp.mean
            out = out.at[:, :, i, j].set(red(win, axis=(2, 3)))
    return out


@register_op("adaptive_pool2d")
def adaptive_pool2d(x, *, output_size, pooling_type="avg", data_format="NCHW"):
    return _adaptive_pool2d(x, output_size, pooling_type, data_format)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _bn_train_core(x, scale, bias, epsilon, axes, shape):
    """Training-mode BN with a memory-lean VJP: the backward recomputes
    x-hat from the ORIGINAL (bf16) input instead of letting autodiff save
    the f32-upcast intermediates — on an HBM-bound conv net that halves
    the BN-related backward traffic (cudnn's bn kernels do the same:
    /root/reference/paddle/fluid/operators/batch_norm_op.cu saved_mean/
    saved_inv_var + raw x)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=axes)
    varb = jnp.var(xf, axis=axes)
    inv = lax.rsqrt(varb + epsilon)
    y = (
        (xf - mu.reshape(shape)) * inv.reshape(shape) * scale.reshape(shape)
        + bias.reshape(shape)
    ).astype(x.dtype)
    return y, mu, varb


def _bn_train_fwd(x, scale, bias, epsilon, axes, shape):
    out = _bn_train_core(x, scale, bias, epsilon, axes, shape)
    _, mu, varb = out
    inv = lax.rsqrt(varb + epsilon)
    return out, (x, mu, inv, scale)


def _bn_train_bwd(epsilon, axes, shape, res, cts):
    dy = cts[0]  # cotangents of (mu, varb) — running-stat paths — dropped,
    # matching the reference (saved stats are not differentiated through)
    x, mu, inv, scale = res
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    n = 1
    for a in axes:
        n *= x.shape[a]
    xhat = (xf - mu.reshape(shape)) * inv.reshape(shape)
    dbias = jnp.sum(dyf, axis=axes)
    dscale = jnp.sum(dyf * xhat, axis=axes)
    dx = (
        inv.reshape(shape) * scale.reshape(shape).astype(jnp.float32)
        * (dyf - (dbias / n).reshape(shape) - xhat * (dscale / n).reshape(shape))
    )
    return (dx.astype(x.dtype), dscale.astype(scale.dtype),
            dbias.astype(scale.dtype))


_bn_train_core.defvjp(_bn_train_fwd, _bn_train_bwd)


@register_op("batch_norm", num_outputs=3)
def batch_norm(x, scale, bias, mean, var, *, momentum=0.9, epsilon=1e-5,
               training=True, data_format="NCHW"):
    """Returns (y, new_running_mean, new_running_var).

    operators/batch_norm_op.cc — running stats follow paddle's
    running = momentum*running + (1-momentum)*batch.

    TPU dtype discipline: statistics accumulate in float32 regardless of
    the carrier dtype (bf16 mean/var would lose ~3 decimal digits), but
    the OUTPUT keeps x.dtype — under bf16 AMP the activation never
    round-trips through an f32 HBM buffer. ResNet-50 at batch 128 is
    HBM-bound; carrying f32 activations around every BN costs ~2x the
    step time (see COVERAGE.md ResNet-50 section).
    """
    axes = tuple(i for i in range(x.ndim) if i != (1 if data_format == "NCHW" else x.ndim - 1))
    shape = [1] * x.ndim
    caxis = 1 if data_format == "NCHW" else x.ndim - 1
    shape[caxis] = x.shape[caxis]

    if training:
        y, batch_mean, batch_var = _bn_train_core(
            x, scale, bias, epsilon, tuple(axes), tuple(shape)
        )
        new_mean = momentum * mean + (1 - momentum) * batch_mean
        new_var = momentum * var + (1 - momentum) * batch_var
        return y, new_mean, new_var

    xf = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
    inv = lax.rsqrt(var + epsilon)
    y = (xf - mean.reshape(shape)) * inv.reshape(shape) * scale.reshape(shape) + bias.reshape(shape)
    return y.astype(x.dtype), mean, var


@register_op("layer_norm")
def layer_norm(x, scale=None, bias=None, *, epsilon=1e-5, begin_norm_axis=-1):
    # operators/layer_norm_op.cc — normalize over trailing dims.
    # Statistics in f32, output in x.dtype (same bf16-carrier discipline
    # as batch_norm: no f32 activation round-trips under AMP).
    if begin_norm_axis < 0:
        begin_norm_axis = x.ndim + begin_norm_axis
    axes = tuple(range(begin_norm_axis, x.ndim))
    xf = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + epsilon)
    if scale is not None:
        y = y * scale
    if bias is not None:
        y = y + bias
    return y.astype(x.dtype)


@register_op("group_norm")
def group_norm(x, scale=None, bias=None, *, groups, epsilon=1e-5, data_format="NCHW"):
    n, c = x.shape[0], x.shape[1]
    xr = x.reshape(n, groups, c // groups, *x.shape[2:])
    axes = tuple(range(2, xr.ndim))
    mean = jnp.mean(xr, axis=axes, keepdims=True)
    var = jnp.var(xr, axis=axes, keepdims=True)
    y = ((xr - mean) * lax.rsqrt(var + epsilon)).reshape(x.shape)
    shape = [1, c] + [1] * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return y


@register_op("instance_norm")
def instance_norm(x, scale=None, bias=None, *, epsilon=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + epsilon)
    shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return y


@register_op("lookup_table")
def lookup_table(w, ids, *, padding_idx=-1):
    # operators/lookup_table_op.cc (embedding)
    out = jnp.take(w, ids, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return out


@register_op("dropout")
def dropout(x, *, p=0.5, training=True, mode="upscale_in_train", key=None):
    if not training or p == 0.0:
        return x
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, x.shape)
    if mode == "upscale_in_train":
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)
    return jnp.where(mask, x, 0.0).astype(x.dtype)


@register_op("interpolate")
def interpolate(x, *, size=None, scale_factor=None, mode="nearest", align_corners=False, data_format="NCHW"):
    n, c, h, w = x.shape
    if size is None:
        sf = _pair(scale_factor)
        size = (int(h * sf[0]), int(w * sf[1]))
    oh, ow = size
    jmode = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic"}[mode]
    xt = jnp.moveaxis(x, 1, -1)  # N H W C for image resize
    out = jax.image.resize(xt, (n, oh, ow, c), method=jmode)
    return jnp.moveaxis(out, -1, 1)


@register_op("pixel_shuffle")
def pixel_shuffle(x, *, upscale_factor, data_format="NCHW"):
    n, c, h, w = x.shape
    r = upscale_factor
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return x.reshape(n, c // (r * r), h * r, w * r)


@register_op("unfold")
def unfold(x, *, kernel_sizes, strides=1, paddings=0, dilations=1):
    ks, st, p, d = _pair(kernel_sizes), _pair(strides), _pair(paddings), _pair(dilations)
    n, c, h, w = x.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])))
    oh = (h + 2 * p[0] - d[0] * (ks[0] - 1) - 1) // st[0] + 1
    ow = (w + 2 * p[1] - d[1] * (ks[1] - 1) - 1) // st[1] + 1
    patches = []
    for i in range(ks[0]):
        for j in range(ks[1]):
            patch = xp[:, :, i * d[0] : i * d[0] + oh * st[0] : st[0], j * d[1] : j * d[1] + ow * st[1] : st[1]]
            patches.append(patch)
    out = jnp.stack(patches, axis=2)  # n, c, kh*kw, oh, ow
    return out.reshape(n, c * ks[0] * ks[1], oh * ow)


# ---------------------------------------------------------------------------
# Losses (operators/softmax_with_cross_entropy_op.cc etc.)
# ---------------------------------------------------------------------------


@register_op("softmax_with_cross_entropy")
def softmax_with_cross_entropy(logits, label, *, soft_label=False, axis=-1, ignore_index=-100):
    logp = jax.nn.log_softmax(logits, axis=axis)
    if soft_label:
        return -jnp.sum(label * logp, axis=axis, keepdims=True)
    lbl = label
    squeeze_back = False
    if lbl.ndim == logits.ndim:
        lbl = jnp.squeeze(lbl, axis=axis)
        squeeze_back = True
    picked = jnp.take_along_axis(logp, jnp.expand_dims(jnp.clip(lbl, 0, None), axis), axis=axis)
    loss = -picked
    mask = jnp.expand_dims(lbl != ignore_index, axis)
    loss = jnp.where(mask, loss, 0.0)
    if not squeeze_back:
        pass
    return loss


@register_op("cross_entropy")
def cross_entropy_kernel(logits, label, *, soft_label=False, axis=-1,
                         ignore_index=-100, reduction="mean", use_softmax=True,
                         weight=None):
    if use_softmax:
        logp = jax.nn.log_softmax(logits, axis=axis)
    else:
        logp = jnp.log(jnp.clip(logits, 1e-12, None))
    if soft_label:
        loss = -jnp.sum(label * logp, axis=axis)
        valid = jnp.ones_like(loss, dtype=bool)
    else:
        lbl = label
        if lbl.ndim == logits.ndim:
            lbl = jnp.squeeze(lbl, axis=axis)
        picked = jnp.take_along_axis(logp, jnp.expand_dims(jnp.clip(lbl, 0, None), axis), axis=axis)
        loss = -jnp.squeeze(picked, axis=axis)
        valid = lbl != ignore_index
        loss = jnp.where(valid, loss, 0.0)
        if weight is not None:
            wsel = jnp.take(weight, jnp.clip(lbl, 0, None))
            loss = loss * jnp.where(valid, wsel, 0.0)
    if reduction == "none":
        return loss
    if reduction == "sum":
        return jnp.sum(loss)
    denom = jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)
    if weight is not None and not soft_label:
        lbl2 = label if label.ndim != logits.ndim else jnp.squeeze(label, axis=axis)
        wsel = jnp.take(weight, jnp.clip(lbl2, 0, None))
        denom = jnp.maximum(jnp.sum(jnp.where(valid, wsel, 0.0)), 1e-12)
    return jnp.sum(loss) / denom


@register_op("mse_loss")
def mse_loss(x, y, *, reduction="mean"):
    loss = jnp.square(x - y)
    return _reduce_loss(loss, reduction)


@register_op("l1_loss")
def l1_loss(x, y, *, reduction="mean"):
    return _reduce_loss(jnp.abs(x - y), reduction)


@register_op("smooth_l1_loss")
def smooth_l1_loss(x, y, *, reduction="mean", delta=1.0):
    d = jnp.abs(x - y)
    loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
    return _reduce_loss(loss, reduction)


@register_op("bce_loss")
def bce_loss(x, label, *, reduction="mean"):
    eps = 1e-12
    loss = -(label * jnp.log(jnp.clip(x, eps, None)) + (1 - label) * jnp.log(jnp.clip(1 - x, eps, None)))
    return _reduce_loss(loss, reduction)


@register_op("bce_with_logits")
def bce_with_logits(logits, label, *, reduction="mean", pos_weight=None):
    max_val = jnp.clip(-logits, 0, None)
    if pos_weight is not None:
        log_weight = (pos_weight - 1) * label + 1
        loss = (1 - label) * logits + log_weight * (jnp.log(jnp.exp(-max_val) + jnp.exp(-logits - max_val)) + max_val)
    else:
        loss = (1 - label) * logits + max_val + jnp.log(jnp.exp(-max_val) + jnp.exp(-logits - max_val))
    return _reduce_loss(loss, reduction)


@register_op("nll_loss")
def nll_loss(logp, label, *, reduction="mean", ignore_index=-100):
    picked = jnp.take_along_axis(logp, jnp.expand_dims(jnp.clip(label, 0, None), 1), axis=1)
    loss = -jnp.squeeze(picked, axis=1)
    valid = label != ignore_index
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "none":
        return loss
    if reduction == "sum":
        return jnp.sum(loss)
    return jnp.sum(loss) / jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)


@register_op("kl_div")
def kl_div(x, target, *, reduction="mean"):
    loss = target * (jnp.log(jnp.clip(target, 1e-12, None)) - x)
    if reduction == "batchmean":
        return jnp.sum(loss) / x.shape[0]
    return _reduce_loss(loss, reduction)


@register_op("log_loss")
def log_loss(pred, label, *, epsilon=1e-4):
    return -label * jnp.log(pred + epsilon) - (1 - label) * jnp.log(1 - pred + epsilon)


@register_op("hinge_loss")
def hinge_loss(logits, label, **kw):
    return jnp.clip(1 - logits * (2 * label - 1), 0, None)


@register_op("square_error_cost")
def square_error_cost(x, y, **kw):
    return jnp.square(x - y)


@register_op("margin_ranking_loss")
def margin_ranking_loss(x, y, label, *, margin=0.0, reduction="mean"):
    loss = jnp.clip(-label * (x - y) + margin, 0, None)
    return _reduce_loss(loss, reduction)


@register_op("cosine_similarity")
def cosine_similarity(x, y, *, axis=1, eps=1e-8):
    dot_ = jnp.sum(x * y, axis=axis)
    nx = jnp.linalg.norm(x, axis=axis)
    ny = jnp.linalg.norm(y, axis=axis)
    return dot_ / jnp.clip(nx * ny, eps, None)


def _reduce_loss(loss, reduction):
    if reduction == "none":
        return loss
    if reduction == "sum":
        return jnp.sum(loss)
    return jnp.mean(loss)


# ---------------------------------------------------------------------------
# AMP primitive (operators/amp/amp_check_finite_and_scale_op)
# ---------------------------------------------------------------------------


@register_op("check_finite_and_unscale", num_outputs=-1)
def check_finite_and_unscale(*xs, scale):
    found_inf = jnp.zeros((), jnp.bool_)
    outs = []
    for x in xs:
        finite = jnp.all(jnp.isfinite(x))
        found_inf = found_inf | ~finite
        outs.append(x / scale)
    return tuple(outs) + (found_inf,)


@register_op("update_loss_scaling", num_outputs=3)
def update_loss_scaling(scale, good_steps, found_inf, *, incr_every_n_steps=2000,
                        decr_every_n_nan_or_inf=1, incr_ratio=2.0, decr_ratio=0.5):
    new_good = jnp.where(found_inf, 0, good_steps + 1)
    should_incr = new_good >= incr_every_n_steps
    new_scale = jnp.where(
        found_inf, jnp.maximum(scale * decr_ratio, 1.0),
        jnp.where(should_incr, scale * incr_ratio, scale),
    )
    new_good = jnp.where(should_incr, 0, new_good)
    return new_scale, new_good, found_inf


# ---------------------------------------------------------------------------
# Metrics (operators/metrics/accuracy_op.cc)
# ---------------------------------------------------------------------------


@register_op("accuracy")
def accuracy(pred_topk_idx, label, **kw):
    if label.ndim == pred_topk_idx.ndim:
        lbl = label
    else:
        lbl = label[:, None]
    correct = jnp.any(pred_topk_idx == lbl, axis=-1)
    return jnp.mean(correct.astype(jnp.float32))


# ---------------------------------------------------------------------------
# RNG ops (operators/uniform_random_op.cc, gaussian_random_op.cc, …)
# ---------------------------------------------------------------------------


@register_op("uniform_random")
def uniform_random(*, shape, min=-1.0, max=1.0, dtype="float32", key=None):
    return jax.random.uniform(key, shape, dtype=jnp.dtype(dtype), minval=min, maxval=max)


@register_op("gaussian_random")
def gaussian_random(*, shape, mean=0.0, std=1.0, dtype="float32", key=None):
    return jax.random.normal(key, shape, dtype=jnp.dtype(dtype)) * std + mean


@register_op("randint")
def randint(*, low, high, shape, dtype="int64", key=None):
    return jax.random.randint(key, shape, low, high, dtype=jnp.dtype(dtype))


@register_op("randperm")
def randperm(*, n, dtype="int64", key=None):
    return jax.random.permutation(key, n).astype(jnp.dtype(dtype))


@register_op("bernoulli")
def bernoulli(x, *, key=None):
    return jax.random.bernoulli(key, x).astype(x.dtype)


@register_op("multinomial")
def multinomial(x, *, num_samples=1, replacement=False, key=None):
    logits = jnp.log(jnp.clip(x, 1e-30, None))
    if replacement:
        return jax.random.categorical(key, logits, axis=-1, shape=(*x.shape[:-1], num_samples)).astype(jnp.int64)
    # Gumbel top-k trick for sampling without replacement
    g = jax.random.gumbel(key, x.shape, dtype=logits.dtype)
    _, idx = lax.top_k(logits + g, num_samples)
    return idx.astype(jnp.int64)


@register_op("truncated_gaussian_random")
def truncated_gaussian_random(*, shape, mean=0.0, std=1.0, dtype="float32", key=None):
    out = jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype=jnp.dtype(dtype))
    return out * std + mean


# ---------------------------------------------------------------------------
# Fill / init ops (operators/fill_constant_op.cc) + static-graph helpers
# ---------------------------------------------------------------------------


@register_op("fill_constant")
def fill_constant(*, shape, value, dtype="float32"):
    return jnp.full(tuple(shape), value, jnp.dtype(dtype))


@register_op("fill_any_like")
def fill_any_like(x, *, value):
    return jnp.full(x.shape, value, x.dtype)


@register_op("sum_n")
def sum_n(*xs, **kw):
    # grad accumulation (fluid/backward.py inserts sum ops for multi-consumer vars)
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return out


# ---------------------------------------------------------------------------
# Optimizer update ops (operators/optimizers/*.cc) — static-graph versions.
# lr is a traced scalar input so schedules don't retrigger compilation.
# ---------------------------------------------------------------------------


@register_op("sgd")
def sgd_update(param, grad, lr, **kw):
    return param - lr * grad


@register_op("momentum_update", num_outputs=2)
def momentum_update(param, grad, velocity, lr, *, mu=0.9, use_nesterov=False):
    v = mu * velocity + grad
    if use_nesterov:
        new_p = param - lr * (grad + mu * v)
    else:
        new_p = param - lr * v
    return new_p, v


@register_op("adam_update", num_outputs=3)
def adam_update(param, grad, moment1, moment2, lr, step, *, beta1=0.9, beta2=0.999,
                epsilon=1e-8):
    m = beta1 * moment1 + (1 - beta1) * grad
    v = beta2 * moment2 + (1 - beta2) * grad * grad
    t = step.astype(param.dtype)
    mhat = m / (1 - beta1**t)
    vhat = v / (1 - beta2**t)
    new_p = param - lr * mhat / (jnp.sqrt(vhat) + epsilon)
    return new_p, m, v


@register_op("increment")
def increment(x, *, value=1.0):
    return x + jnp.asarray(value, x.dtype)

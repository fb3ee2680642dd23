"""Core nn layers.

Reference parity: python/paddle/nn/layer/common.py, conv.py, norm.py,
pooling.py + fluid/dygraph/nn.py. Layers hold Parameters and dispatch to the
functional ops; everything composes under jit via functionalization.
"""
from __future__ import annotations

import numpy as np

from .. import ops
from ..framework.tensor import Parameter, Tensor
from . import functional as F
from . import initializer as I
from .layer_base import Layer


class Linear(Layer):
    def __init__(self, in_features, out_features, weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr, default_initializer=I.XavierUniform()
        )
        if bias_attr is not False:
            self.bias = self.create_parameter([out_features], attr=bias_attr, is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        return F.linear(x, self.weight, self.bias)

    def extra_repr(self):
        return f"in_features={self.in_features}, out_features={self.out_features}"


class Dropout(Layer):
    def __init__(self, p=0.5, mode="upscale_in_train", name=None):
        super().__init__()
        self.p = p
        self.mode = mode

    def forward(self, x):
        return F.dropout(x, p=self.p, training=self.training, mode=self.mode)

    def extra_repr(self):
        return f"p={self.p}"


class Embedding(Layer):
    def __init__(self, num_embeddings, embedding_dim, padding_idx=None,
                 sparse=False, weight_attr=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.padding_idx = padding_idx
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=I.Normal(0.0, 1.0) if weight_attr is None else None,
        )

    def forward(self, x):
        return F.embedding(x, self.weight, padding_idx=self.padding_idx)

    def extra_repr(self):
        return f"{self.num_embeddings}, {self.embedding_dim}"


class Flatten(Layer):
    def __init__(self, start_axis=1, stop_axis=-1):
        super().__init__()
        self.start_axis = start_axis
        self.stop_axis = stop_axis

    def forward(self, x):
        return ops.flatten(x, self.start_axis, self.stop_axis)


# -- conv --------------------------------------------------------------------


class Conv2D(Layer):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1, padding=0,
                 dilation=1, groups=1, padding_mode="zeros", weight_attr=None,
                 bias_attr=None, data_format="NCHW"):
        super().__init__()
        ks = kernel_size if isinstance(kernel_size, (list, tuple)) else (kernel_size, kernel_size)
        self._attrs = dict(stride=stride, padding=padding, dilation=dilation, groups=groups)
        self.data_format = data_format
        fan_in = in_channels // groups * ks[0] * ks[1]
        self.weight = self.create_parameter(
            [out_channels, in_channels // groups, ks[0], ks[1]], attr=weight_attr,
            default_initializer=I.KaimingUniform(fan_in=fan_in),
        )
        if bias_attr is not False:
            bound = 1.0 / np.sqrt(fan_in)
            self.bias = self.create_parameter(
                [out_channels], attr=bias_attr, is_bias=True,
                default_initializer=I.Uniform(-bound, bound) if bias_attr is None else None,
            )
        else:
            self.bias = None

    def forward(self, x):
        return F.conv2d(x, self.weight, self.bias, data_format=self.data_format, **self._attrs)


class Conv1D(Layer):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1, padding=0,
                 dilation=1, groups=1, weight_attr=None, bias_attr=None):
        super().__init__()
        self._attrs = dict(stride=stride, padding=padding, dilation=dilation, groups=groups)
        fan_in = in_channels // groups * kernel_size
        self.weight = self.create_parameter(
            [out_channels, in_channels // groups, kernel_size], attr=weight_attr,
            default_initializer=I.KaimingUniform(fan_in=fan_in),
        )
        self.bias = None if bias_attr is False else self.create_parameter(
            [out_channels], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.conv1d(x, self.weight, self.bias, **self._attrs)


class Conv2DTranspose(Layer):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1, padding=0,
                 output_padding=0, dilation=1, groups=1, weight_attr=None, bias_attr=None):
        super().__init__()
        ks = kernel_size if isinstance(kernel_size, (list, tuple)) else (kernel_size, kernel_size)
        self._attrs = dict(stride=stride, padding=padding, output_padding=output_padding,
                           dilation=dilation, groups=groups)
        fan_in = in_channels * ks[0] * ks[1] // groups
        self.weight = self.create_parameter(
            [in_channels, out_channels // groups, ks[0], ks[1]], attr=weight_attr,
            default_initializer=I.KaimingUniform(fan_in=fan_in),
        )
        self.bias = None if bias_attr is False else self.create_parameter(
            [out_channels], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.conv2d_transpose(x, self.weight, self.bias, **self._attrs)


# -- pooling -----------------------------------------------------------------


class MaxPool2D(Layer):
    def __init__(self, kernel_size, stride=None, padding=0, ceil_mode=False, data_format="NCHW"):
        super().__init__()
        self._attrs = dict(kernel_size=kernel_size, stride=stride, padding=padding,
                           ceil_mode=ceil_mode, data_format=data_format)

    def forward(self, x):
        return F.max_pool2d(x, **self._attrs)


class AvgPool2D(Layer):
    def __init__(self, kernel_size, stride=None, padding=0, ceil_mode=False, exclusive=True,
                 data_format="NCHW"):
        super().__init__()
        self._attrs = dict(kernel_size=kernel_size, stride=stride, padding=padding,
                           ceil_mode=ceil_mode, exclusive=exclusive, data_format=data_format)

    def forward(self, x):
        return F.avg_pool2d(x, **self._attrs)


class AdaptiveAvgPool2D(Layer):
    def __init__(self, output_size, data_format="NCHW"):
        super().__init__()
        self.output_size = output_size
        self.data_format = data_format

    def forward(self, x):
        return F.adaptive_avg_pool2d(x, self.output_size, data_format=self.data_format)


class AdaptiveMaxPool2D(Layer):
    def __init__(self, output_size, data_format="NCHW"):
        super().__init__()
        self.output_size = output_size
        self.data_format = data_format

    def forward(self, x):
        return F.adaptive_max_pool2d(x, self.output_size, data_format=self.data_format)


def fused_conv_bn_relu(conv, bn, x):
    """``relu(bn(conv(x)))`` through the fused pallas conv+bn+relu
    kernels (``FLAGS_use_fused_conv_bn``) when the triple is admissible:
    a bias-free, ungrouped, undilated Conv2D feeding a matching
    BatchNorm2D. Of those the kernels take the pointwise convs (1x1,
    stride 1, no padding: a bottleneck block's first); a conv with a
    spatial extent runs the fallback, as does every conv off the TPU.
    The fallback (and the unfused path here) executes the identical op
    kernels in the same order, so this is a scheduling choice, never a
    numeric one — the ``_residual_norm`` discipline applied to conv nets.

    Running statistics update exactly as ``F.batch_norm`` does in
    training (detached blend into the layer buffers).
    """
    from ..flags import flag
    from ..framework.tensor import Tensor

    attrs = conv._attrs
    if (flag("use_fused_conv_bn") and isinstance(x, Tensor)
            and conv.bias is None and attrs.get("groups", 1) == 1
            and attrs.get("dilation", 1) in (1, (1, 1), [1, 1])
            and isinstance(bn, _BatchNormBase)
            and bn.data_format == ("NCHW" if conv.data_format == "NCHW"
                                   else "NHWC")):
        from ..framework.autograd import no_grad
        from ..ops.pallas import conv_bn_relu as _fused

        # the unfused path autocasts the conv (white-listed op) but not
        # the bn params; mirror that exactly — x/weight take the AMP
        # dtype, gamma/beta/running stats stay f32
        weight = conv.weight
        from ..amp import _enabled as _amp_state

        scope = _amp_state()
        if scope is not None and "conv2d" in scope[1]:
            import jax.numpy as _jnp

            amp_dt = str(_jnp.dtype(scope[0]))
            if str(x.dtype) == "float32":
                x = x.astype(amp_dt)
            if str(weight.dtype) == "float32":
                weight = weight.astype(amp_dt)

        y, new_mean, new_var = _fused(
            x, weight, bn.weight, bn.bias, bn._mean, bn._variance,
            stride=attrs.get("stride", 1), padding=attrs.get("padding", 0),
            epsilon=bn.epsilon, momentum=bn.momentum,
            training=bn.training, data_format=conv.data_format)
        if bn.training:
            with no_grad():
                bn._mean.set_value(new_mean.detach())
                bn._variance.set_value(new_var.detach())
        return y
    return F.relu(bn(conv(x)))


# -- normalization -----------------------------------------------------------


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, data_format="NCHW"):
        super().__init__()
        self.num_features = num_features
        self.momentum = momentum
        self.epsilon = epsilon
        self.data_format = "NCHW" if data_format in ("NCHW", "NCL", "NCDHW") else "NHWC"
        self.weight = self.create_parameter(
            [num_features], attr=weight_attr, default_initializer=I.Constant(1.0))
        self.bias = self.create_parameter([num_features], attr=bias_attr, is_bias=True)
        self.register_buffer("_mean", Tensor(np.zeros(num_features, np.float32)))
        self.register_buffer("_variance", Tensor(np.ones(num_features, np.float32)))

    def forward(self, x):
        return F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self.momentum, epsilon=self.epsilon,
            data_format=self.data_format,
        )


class BatchNorm1D(_BatchNormBase):
    pass


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    pass


BatchNorm = BatchNorm2D  # fluid.dygraph.BatchNorm compat


class SyncBatchNorm(_BatchNormBase):
    """Under pjit/shard_map data parallelism the batch statistics are computed
    over the global (sharded) batch automatically when the reduction axes are
    replicated — matching nccl SyncBatchNorm semantics without extra comms
    code. Standalone eager use equals BatchNorm."""


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None, bias_attr=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self.normalized_shape = list(normalized_shape)
        self.epsilon = epsilon
        if weight_attr is not False:
            self.weight = self.create_parameter(
                self.normalized_shape, attr=weight_attr, default_initializer=I.Constant(1.0))
        else:
            self.weight = None
        if bias_attr is not False:
            self.bias = self.create_parameter(self.normalized_shape, attr=bias_attr, is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        return F.layer_norm(x, self.normalized_shape, self.weight, self.bias, self.epsilon)

    def extra_repr(self):
        return f"normalized_shape={self.normalized_shape}"


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5, weight_attr=None, bias_attr=None):
        super().__init__()
        self.num_groups = num_groups
        self.epsilon = epsilon
        self.weight = None if weight_attr is False else self.create_parameter(
            [num_channels], attr=weight_attr, default_initializer=I.Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(
            [num_channels], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.group_norm(x, self.num_groups, self.weight, self.bias, self.epsilon)


class InstanceNorm2D(Layer):
    def __init__(self, num_features, epsilon=1e-5, weight_attr=None, bias_attr=None):
        super().__init__()
        self.epsilon = epsilon
        self.weight = None if weight_attr is False else self.create_parameter(
            [num_features], attr=weight_attr, default_initializer=I.Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(
            [num_features], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, self.weight, self.bias, self.epsilon)


# -- activations as layers ---------------------------------------------------


def _act_layer(name, fn_name, **defaults):
    def forward(self, x):
        fn = getattr(F, fn_name)
        return fn(x, **{k: getattr(self, k) for k in defaults})

    def __init__(self, **kwargs):
        Layer.__init__(self)
        for k, v in defaults.items():
            setattr(self, k, kwargs.get(k, v))

    return type(name, (Layer,), {"__init__": __init__, "forward": forward})


ReLU = _act_layer("ReLU", "relu")
ReLU6 = _act_layer("ReLU6", "relu6")
LeakyReLU = _act_layer("LeakyReLU", "leaky_relu", negative_slope=0.01)
ELU = _act_layer("ELU", "elu", alpha=1.0)
CELU = _act_layer("CELU", "celu", alpha=1.0)
SELU = _act_layer("SELU", "selu")
GELU = _act_layer("GELU", "gelu", approximate=False)
Sigmoid = _act_layer("Sigmoid", "sigmoid")
LogSigmoid = _act_layer("LogSigmoid", "log_sigmoid")
Tanh = _act_layer("Tanh", "tanh")
Hardsigmoid = _act_layer("Hardsigmoid", "hardsigmoid")
Hardswish = _act_layer("Hardswish", "hardswish")
Hardtanh = _act_layer("Hardtanh", "hardtanh", min=-1.0, max=1.0)
Hardshrink = _act_layer("Hardshrink", "hardshrink", threshold=0.5)
Softshrink = _act_layer("Softshrink", "softshrink", threshold=0.5)
Softplus = _act_layer("Softplus", "softplus", beta=1.0, threshold=20.0)
Softsign = _act_layer("Softsign", "softsign")
Swish = _act_layer("Swish", "swish")
Silu = _act_layer("Silu", "silu")
Mish = _act_layer("Mish", "mish")
Tanhshrink = _act_layer("Tanhshrink", "tanh_shrink")
Softmax = _act_layer("Softmax", "softmax", axis=-1)
LogSoftmax = _act_layer("LogSoftmax", "log_softmax", axis=-1)


class PReLU(Layer):
    def __init__(self, num_parameters=1, init=0.25, weight_attr=None):
        super().__init__()
        self.weight = self.create_parameter(
            [num_parameters], attr=weight_attr, default_initializer=I.Constant(init))

    def forward(self, x):
        w = self.weight
        if w.size > 1:
            shape = [1] * x.ndim
            shape[1] = w.size
            w = ops.reshape(w, shape)
        return F.prelu(x, w)


# -- containers (fluid/dygraph/container.py) --------------------------------


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)) and not isinstance(layers[0], Layer):
            layers = layers[0]
        for i, item in enumerate(layers):
            if isinstance(item, tuple):
                name, layer = item
            else:
                name, layer = str(i), item
            self.add_sublayer(name, layer)

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x

    def __getitem__(self, idx):
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        for i, layer in enumerate(sublayers or []):
            self.add_sublayer(str(i), layer)

    def append(self, layer):
        self.add_sublayer(str(len(self._sub_layers)), layer)
        return self

    def extend(self, layers):
        for layer in layers:
            self.append(layer)
        return self

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return list(self._sub_layers.values())[idx]
        return self._sub_layers[str(idx % len(self._sub_layers))]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def forward(self, *args, **kwargs):
        raise NotImplementedError("LayerList is a container; call sublayers directly")


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        for i, p in enumerate(parameters or []):
            self.add_parameter(str(i), p)

    def append(self, p):
        self.add_parameter(str(len(self._parameters)), p)
        return self

    def __getitem__(self, idx):
        return self._parameters[str(idx % len(self._parameters))]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())


# -- losses (paddle/nn/layer/loss.py) ---------------------------------------


class CrossEntropyLoss(Layer):
    def __init__(self, weight=None, ignore_index=-100, reduction="mean",
                 soft_label=False, axis=-1, use_softmax=True):
        super().__init__()
        self.weight = weight
        self.ignore_index = ignore_index
        self.reduction = reduction
        self.soft_label = soft_label
        self.axis = axis
        self.use_softmax = use_softmax

    def forward(self, input, label):
        return F.cross_entropy(
            input, label, weight=self.weight, soft_label=self.soft_label,
            axis=self.axis, ignore_index=self.ignore_index,
            reduction=self.reduction, use_softmax=self.use_softmax,
        )


class MSELoss(Layer):
    def __init__(self, reduction="mean"):
        super().__init__()
        self.reduction = reduction

    def forward(self, input, label):
        return F.mse_loss(input, label, reduction=self.reduction)


class L1Loss(Layer):
    def __init__(self, reduction="mean"):
        super().__init__()
        self.reduction = reduction

    def forward(self, input, label):
        return F.l1_loss(input, label, reduction=self.reduction)


class SmoothL1Loss(Layer):
    def __init__(self, reduction="mean", delta=1.0):
        super().__init__()
        self.reduction = reduction
        self.delta = delta

    def forward(self, input, label):
        return F.smooth_l1_loss(input, label, reduction=self.reduction, delta=self.delta)


class BCELoss(Layer):
    def __init__(self, reduction="mean"):
        super().__init__()
        self.reduction = reduction

    def forward(self, input, label):
        return F.binary_cross_entropy(input, label, reduction=self.reduction)


class BCEWithLogitsLoss(Layer):
    def __init__(self, reduction="mean", pos_weight=None):
        super().__init__()
        self.reduction = reduction
        self.pos_weight = pos_weight

    def forward(self, logits, label):
        return F.binary_cross_entropy_with_logits(
            logits, label, reduction=self.reduction, pos_weight=self.pos_weight)


class NLLLoss(Layer):
    def __init__(self, reduction="mean", ignore_index=-100):
        super().__init__()
        self.reduction = reduction
        self.ignore_index = ignore_index

    def forward(self, input, label):
        return F.nll_loss(input, label, reduction=self.reduction, ignore_index=self.ignore_index)


class KLDivLoss(Layer):
    def __init__(self, reduction="mean"):
        super().__init__()
        self.reduction = reduction

    def forward(self, input, label):
        return F.kl_div(input, label, reduction=self.reduction)


class MarginRankingLoss(Layer):
    def __init__(self, margin=0.0, reduction="mean"):
        super().__init__()
        self.margin = margin
        self.reduction = reduction

    def forward(self, input, other, label):
        return F.margin_ranking_loss(input, other, label, margin=self.margin, reduction=self.reduction)


# -- misc --------------------------------------------------------------------


class Upsample(Layer):
    def __init__(self, size=None, scale_factor=None, mode="nearest", align_corners=False):
        super().__init__()
        self._attrs = dict(size=size, scale_factor=scale_factor, mode=mode, align_corners=align_corners)

    def forward(self, x):
        return F.interpolate(x, **self._attrs)


class PixelShuffle(Layer):
    def __init__(self, upscale_factor):
        super().__init__()
        self.upscale_factor = upscale_factor

    def forward(self, x):
        return F.pixel_shuffle(x, self.upscale_factor)


class Pad2D(Layer):
    def __init__(self, padding, mode="constant", value=0.0):
        super().__init__()
        if isinstance(padding, int):
            padding = [padding] * 4
        # paddle Pad2D: [left, right, top, bottom] over NCHW spatial dims
        l, r, t, b = padding
        self.paddings = [0, 0, 0, 0, t, b, l, r]
        self.mode = mode
        self.value = value

    def forward(self, x):
        return ops.pad(x, self.paddings, mode=self.mode, value=self.value)


class CosineSimilarity(Layer):
    """paddle.nn.CosineSimilarity (nn/layer/distance.py)."""

    def __init__(self, axis=1, eps=1e-8):
        super().__init__()
        self._axis, self._eps = axis, eps

    def forward(self, x1, x2):
        import jax.numpy as jnp

        a, b = x1._array, x2._array
        num = jnp.sum(a * b, axis=self._axis)
        den = jnp.maximum(
            jnp.linalg.norm(a, axis=self._axis)
            * jnp.linalg.norm(b, axis=self._axis),
            self._eps,
        )
        return Tensor._from_array(num / den)


class PairwiseDistance(Layer):
    """paddle.nn.PairwiseDistance (nn/layer/distance.py)."""

    def __init__(self, p=2.0, epsilon=1e-6, keepdim=False):
        super().__init__()
        self._p, self._eps, self._keepdim = p, epsilon, keepdim

    def forward(self, x, y):
        import jax.numpy as jnp

        d = x._array - y._array + self._eps
        out = jnp.linalg.norm(d, ord=self._p, axis=-1,
                              keepdims=self._keepdim)
        return Tensor._from_array(out)


class Bilinear(Layer):
    """paddle.nn.Bilinear: out_k = x1 @ W_k @ x2 + b_k
    (nn/layer/common.py Bilinear; operators/bilinear_tensor_product_op.cc)."""

    def __init__(self, in1_features, in2_features, out_features,
                 weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        self.weight = self.create_parameter(
            [out_features, in1_features, in2_features], attr=weight_attr,
            default_initializer=I.XavierUniform(),
        )
        self.bias = (None if bias_attr is False else self.create_parameter(
            [out_features], attr=bias_attr, is_bias=True))

    def forward(self, x1, x2):
        import jax.numpy as jnp

        out = jnp.einsum("bi,oij,bj->bo", x1._array, self.weight._array,
                         x2._array)
        if self.bias is not None:
            out = out + self.bias._array
        return Tensor._from_array(out)


class SpectralNorm(Layer):
    """paddle.nn.SpectralNorm (nn/layer/norm.py; spectral_norm_op.cc):
    normalizes a weight tensor by its largest singular value, keeping the
    power-iteration vectors as buffers."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12):
        super().__init__()
        import numpy as _np

        self._dim, self._iters, self._eps = dim, power_iters, eps
        h = weight_shape[dim]
        w = int(_np.prod(weight_shape)) // h
        rng = _np.random.RandomState(0)
        self.register_buffer(
            "weight_u", Tensor((rng.randn(h) / _np.sqrt(h)).astype("float32"))
        )
        self.register_buffer(
            "weight_v", Tensor((rng.randn(w) / _np.sqrt(w)).astype("float32"))
        )

    def forward(self, weight):
        from ..ops.registry import kernel

        w = weight._array if isinstance(weight, Tensor) else weight
        out = kernel("spectral_norm")(
            w, self.weight_u._array, self.weight_v._array,
            dim=self._dim, power_iters=self._iters, eps=self._eps,
        )
        return Tensor._from_array(out)


class Unfold(Layer):
    """paddle.nn.Unfold (im2col, nn/layer/common.py): [N,C,H,W] ->
    [N, C*kh*kw, L]."""

    def __init__(self, kernel_sizes, strides=1, paddings=0, dilations=1):
        super().__init__()
        pair = lambda v: tuple(v) if isinstance(v, (list, tuple)) else (v, v)
        self._ks = pair(kernel_sizes)
        self._st = pair(strides)
        self._pd = pair(paddings)
        self._dl = pair(dilations)

    def forward(self, x):
        import jax.numpy as jnp

        from ..ops.registry import kernel

        # one im2col implementation: the im2sequence kernel (compat.py)
        # produces [N, L, C*kh*kw]; Unfold's layout is the transpose
        p = self._pd
        rows = kernel("im2sequence")(
            x._array, kernels=self._ks, strides=self._st,
            paddings=(p[0], p[1], p[0], p[1]), dilations=self._dl,
        )
        return Tensor._from_array(jnp.swapaxes(rows, 1, 2))


class Fold(Layer):
    """paddle.nn.Fold (col2im): inverse of Unfold — overlapping patches
    sum back into the [N, C, H, W] image."""

    def __init__(self, output_sizes, kernel_sizes, strides=1, paddings=0,
                 dilations=1):
        super().__init__()
        pair = lambda v: tuple(v) if isinstance(v, (list, tuple)) else (v, v)
        self._out = pair(output_sizes)
        self._ks = pair(kernel_sizes)
        self._st = pair(strides)
        self._pd = pair(paddings)
        self._dl = pair(dilations)

    def forward(self, x):
        import jax.numpy as jnp

        arr = x._array  # [N, C*kh*kw, L]
        kh, kw = self._ks
        oh, ow = self._out
        ph, pw = self._pd
        n, ckk, l = arr.shape
        c = ckk // (kh * kw)
        hh = oh + 2 * ph
        ww = ow + 2 * pw
        n_h = (hh - (self._dl[0] * (kh - 1) + 1)) // self._st[0] + 1
        n_w = (ww - (self._dl[1] * (kw - 1) + 1)) // self._st[1] + 1
        cols = arr.reshape(n, c, kh, kw, n_h, n_w)
        out = jnp.zeros((n, c, hh, ww), arr.dtype)
        for i in range(kh):
            for j in range(kw):
                yi = i * self._dl[0]
                xj = j * self._dl[1]
                out = out.at[
                    :, :,
                    yi:yi + n_h * self._st[0]:self._st[0],
                    xj:xj + n_w * self._st[1]:self._st[1],
                ].add(cols[:, :, i, j])
        out = out[:, :, ph:ph + oh, pw:pw + ow]
        return Tensor._from_array(out)

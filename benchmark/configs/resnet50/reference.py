"""ResNet-50 (He et al. 2015, arXiv:1512.03385, table 1, 50-layer
column) in plain jax.numpy: 7x7/2 stem, 3x3/2 max pool, four stages of
bottleneck blocks (1x1, 3x3, 1x1 with batch norm after each conv, ReLU,
identity or projection shortcut), global average pool, 1000-way linear
layer, softmax cross-entropy. Training mode: batch statistics. float32,
`highest` precision, NCHW, no kernels. It imports nothing of the program
and makes its own weights from the seed; the benchmark hands the same
weights to the program.

Departure, because the program under test makes it: a stage's stride 2
sits on the block's 3x3 conv (the paper puts it on the first 1x1).
Each block is rematerialised in the backward pass so that batch 128 in
float32 fits beside nothing else."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

EPS = 1e-5
WIDTHS = (64, 128, 256, 512)


def shapes(cfg):
    out = {"conv1.weight": (64, 3, 7, 7), "bn1.weight": (64,),
           "bn1.bias": (64,)}
    inp = 64
    for li, (planes, n) in enumerate(zip(WIDTHS, cfg["depths"]), 1):
        for b in range(n):
            p = f"layer{li}.{b}."
            out[p + "conv1.weight"] = (planes, inp, 1, 1)
            out[p + "conv2.weight"] = (planes, planes, 3, 3)
            out[p + "conv3.weight"] = (planes * 4, planes, 1, 1)
            for j, c in ((1, planes), (2, planes), (3, planes * 4)):
                out[p + f"bn{j}.weight"] = (c,)
                out[p + f"bn{j}.bias"] = (c,)
            if b == 0:
                out[p + "downsample.0.weight"] = (planes * 4, inp, 1, 1)
                out[p + "downsample.1.weight"] = (planes * 4,)
                out[p + "downsample.1.bias"] = (planes * 4,)
            inp = planes * 4
    out["fc.weight"] = (inp, cfg["num_classes"])
    out["fc.bias"] = (cfg["num_classes"],)
    return out


def weights(cfg, key):
    """Seeded random weights by the program's parameter names: He-normal
    convolutions, 1 + 0.1 n batch-norm gains, 0.1 n biases, 0.01 n for
    the linear layer."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if len(shape) == 4:
            x = x * (2.0 / (shape[1] * shape[2] * shape[3])) ** 0.5
        elif name.startswith("fc."):
            x = x * 0.01
        elif name.endswith("bn3.weight"):
            x = 0.1 * x  # each block starts near the identity
        elif name.endswith(".weight"):
            x = 1.0 + 0.1 * x
        else:
            x = 0.1 * x
        out[name] = x
    return out


def by_program_name(tree):
    return tree  # the leaves already carry the program's names


def leaf_sq_norms(tree):
    return {k: jnp.sum(jnp.square(v)) for k, v in tree.items()}


def _fp8(x):
    s = 224.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(q - x)  # the gradient passes straight


def _loss(w, x, y, cfg, control):
    r = _fp8 if control else (lambda a: a)

    def conv(x, name, stride=1, pad=0):
        return jax.lax.conv_general_dilated(
            r(x), r(w[name + ".weight"]), (stride, stride),
            ((pad, pad), (pad, pad)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    def bn(x, name):
        mu = x.mean((0, 2, 3), keepdims=True)
        var = ((x - mu) ** 2).mean((0, 2, 3), keepdims=True)
        g = w[name + ".weight"][None, :, None, None]
        b = w[name + ".bias"][None, :, None, None]
        return (x - mu) * jax.lax.rsqrt(var + EPS) * g + b

    def block(x, p, stride, project):
        out = jax.nn.relu(bn(conv(x, p + "conv1"), p + "bn1"))
        out = jax.nn.relu(bn(conv(out, p + "conv2", stride, 1), p + "bn2"))
        out = bn(conv(out, p + "conv3"), p + "bn3")
        if project:
            x = bn(conv(x, p + "downsample.0", stride), p + "downsample.1")
        return jax.nn.relu(out + x)

    with jax.default_matmul_precision("highest"):
        x = jax.nn.relu(bn(conv(x, "conv1", 2, 3), "bn1"))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
            ((0, 0), (0, 0), (1, 1), (1, 1)))
        for li, n in enumerate(cfg["depths"], 1):
            for b in range(n):
                stride = 2 if (b == 0 and li > 1) else 1
                x = jax.checkpoint(
                    lambda x, p=f"layer{li}.{b}.", s=stride, pr=(b == 0):
                    block(x, p, s, pr))(x)
        x = x.mean((2, 3))
        logits = jnp.matmul(r(x), r(w["fc.weight"])) + w["fc.bias"]
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, y[:, None], -1)[:, 0]
    return nll.mean()


@functools.lru_cache(maxsize=None)
def _program(cfg_json, control):
    cfg = json.loads(cfg_json)
    return jax.jit(jax.value_and_grad(
        lambda w, x, y: _loss(w, x, y, cfg, control)))


def value_and_grad(w, batch, cfg, control=False):
    x, y = batch
    fn = _program(json.dumps(cfg, sort_keys=True), control)
    return fn(w, jnp.asarray(x), jnp.asarray(y).reshape(-1))

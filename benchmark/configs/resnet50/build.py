"""Builds the ResNet-50 training step through the program's normal entry
points (chip_smoke.py's `leg_resnet` recipe): models.resnet50, Momentum
0.9 at lr 0.02, bf16 AMP, default flags (so `use_fused_conv_bn` is on, as
users get it), `framework.jit.train_step`. The weights are the
benchmark's (reference.py's ``weights`` from the seed)."""
from __future__ import annotations

import os
from types import SimpleNamespace

import jax
import numpy as np

from benchmark.lib import common

_HERE = os.path.dirname(os.path.abspath(__file__))
reference = common.load_module(os.path.join(_HERE, "reference.py"))


def feed(cfg, mix, seed):
    """batch(i) -> (images [b, 3, s, s] float32, labels [b] int32): the
    class is carried by a seeded per-class mean pattern (an 8 x 8 grid
    per channel, blown up to the image size) under unit noise."""
    b, s, c = mix["batch"], mix["image"], cfg["num_classes"]
    grid = 8 if s % 8 == 0 else 1
    pattern = common.host_rng(seed, 50).standard_normal(
        (c, 3, grid, grid), dtype=np.float32)

    def batch(i):
        rng = common.host_rng(seed, 100 + i)
        y = rng.integers(0, c, b).astype("int32")
        x = rng.standard_normal((b, 3, s, s), dtype=np.float32)
        x += np.kron(pattern[y], np.ones((s // grid, s // grid), np.float32))
        return x, y

    return batch


def trainer(cfg, mix, seed, devices):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu import amp
    from paddle_tpu.framework import jit as fjit
    from paddle_tpu.models.resnet import BottleneckBlock, ResNet

    paddle.seed(int(seed) % (2 ** 31 - 1))
    model = ResNet(BottleneckBlock, list(cfg["depths"]),
                   num_classes=cfg["num_classes"])
    w = jax.jit(lambda k: reference.weights(cfg, k))(common.seed_key(seed))
    common.assign_weights(model, w)
    del w
    o = cfg["optimizer"]
    optimizer = opt.Momentum(learning_rate=o["lr"], momentum=o["momentum"],
                             parameters=model.parameters())

    def loss_fn(m, x, y):
        with amp.auto_cast():
            logits = m(x)
        return F.cross_entropy(logits.astype("float32"), y).mean()

    step = fjit.train_step(model, optimizer, loss_fn)
    ids = {id(p): n for n, p in model.named_parameters()}
    return SimpleNamespace(
        step=step, rng=None, feed=feed(cfg, mix, seed),
        samples_per_step=mix["batch"],
        accum_names=[ids[id(p)] for p in optimizer._parameter_list],
        first_moment="velocity", first_moment_scale=1.0)


def reference_batch(batch):
    return batch

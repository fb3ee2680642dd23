"""2-process DP trainer fixture (reference: dist_mnist.py-style runners
driven by tests/unittests/test_dist_base.py:506).

Launched by paddle_tpu.distributed.launch with PADDLE_TRAINER_ID /
PADDLE_COORDINATOR env; fleet.init() performs the jax.distributed
handshake (the gen_nccl_id rendezvous equivalent), after which the global
mesh spans both processes' devices and the GSPMD step's gradient mean
rides the cross-process collective.

Prints one JSON line: {"rank": r, "world": n, "losses": [...]}.
"""
import json
import sys

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu import parallel
from paddle_tpu.distributed import fleet


class MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(16, 32)
        self.fc2 = nn.Linear(32, 4)

    def forward(self, x):
        return self.fc2(F.relu(self.fc1(x)))


def loss_fn(m, x, y):
    return F.cross_entropy(m(x), y).mean()


def main():
    fleet.fleet.init(is_collective=True)  # jax.distributed rendezvous
    import jax

    rng = np.random.RandomState(0)  # same global batch everywhere
    X = rng.randn(32, 16).astype("float32")
    Y = rng.randint(0, 4, (32,)).astype("int64")

    paddle.seed(5)
    model = MLP()
    optimizer = opt.SGD(learning_rate=0.1, parameters=model.parameters())
    mesh = parallel.create_mesh(dp=len(jax.devices()))
    step = parallel.sharded_train_step(model, optimizer, loss_fn, mesh)
    losses = [float(step(X, Y)["loss"]) for _ in range(5)]
    # ONE write (payload < PIPE_BUF) — the launch CLI's children share
    # the parent's stdout pipe, and print()'s separate payload/newline
    # writes interleave across ranks under load, corrupting the line
    sys.stdout.write(json.dumps({
        "rank": fleet.fleet.worker_index(),
        "world": fleet.fleet.worker_num(),
        "n_devices": len(jax.devices()),
        "losses": losses,
    }) + "\n")
    sys.stdout.flush()


if __name__ == "__main__":
    main()

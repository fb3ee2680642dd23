"""Elastic-training fixture: checkpoint-every-step trainer that survives
kill -9 and resumes RESHARDED at whatever world size it is relaunched at.

Driven by test_dist_multiprocess.py (2-proc → 1-proc → 2-proc phases)
and test_elastic_checkpoint.py (one-process world resize + mid-save kill).
Each launch:

  1. joins the world (fleet.init — jax.distributed when nproc > 1),
  2. builds a dp mesh over ALL visible devices + a ZeRO-1 Adam
     ShardedTrainStep,
  3. sweeps torn .tmp snapshots, restores from the newest intact one
     (re-slicing params + dp-sharded optimizer shards onto the CURRENT
     mesh, whatever its size), and
  4. trains deterministic global steps — the batch for step s is a fixed
     function of s, so any sequence of crashes/resumes must reproduce
     the uninterrupted run's loss curve — checkpointing EVERY step
     (async by default) with FLAGS_fault_injection free to kill the
     process at any point.

Env: ELASTIC_CKPT_DIR (required), ELASTIC_TOTAL_STEPS (default 8),
ELASTIC_STOP_AFTER (exit cleanly after completing this step; default:
run to the end), ELASTIC_KEEP (rotation depth, default 3).

Prints one JSON line:
  {"rank", "world", "n_devices", "resumed_from", "steps", "losses",
   "zero1_dp_sharded", "reshards", "saves"}
"""
import json
import os
import sys

import jax

if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
    jax.config.update("jax_platforms", "cpu")

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu import parallel
from paddle_tpu.distributed import chaos
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.distributed import fleet
from paddle_tpu.monitor import registry as _reg


class MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(16, 32)
        self.fc2 = nn.Linear(32, 4)

    def forward(self, x):
        return self.fc2(F.relu(self.fc1(x)))


def loss_fn(m, x, y):
    return F.cross_entropy(m(x), y).mean()


def batch_for(step):
    """The global batch is a pure function of the global step index —
    every world size sees the same global math."""
    rng = np.random.RandomState(1000 + step)
    X = rng.randn(8, 16).astype("float32")
    Y = rng.randint(0, 4, (8,)).astype("int64")
    return X, Y


def main():
    ckpt_dir = os.environ["ELASTIC_CKPT_DIR"]
    total = int(os.environ.get("ELASTIC_TOTAL_STEPS", "8"))
    stop_after = int(os.environ.get("ELASTIC_STOP_AFTER", str(total - 1)))
    keep = int(os.environ.get("ELASTIC_KEEP", "3"))

    fleet.fleet.init(is_collective=True)  # jax.distributed rendezvous
    rank = fleet.fleet.worker_index()
    world = fleet.fleet.worker_num()

    paddle.seed(5)
    model = MLP()
    optimizer = opt.Adam(learning_rate=0.01,
                         parameters=model.parameters())
    mesh = parallel.create_mesh(dp=len(jax.devices()))
    step_fn = parallel.sharded_train_step(
        model, optimizer, loss_fn, mesh, zero1=True)

    # resume: torn tmps swept, newest INTACT snapshot re-sliced onto the
    # current (possibly different-size) mesh
    ckpt.sweep_tmp(ckpt_dir)
    path, manifest = ckpt.latest_checkpoint(ckpt_dir)
    resumed_from = -1
    if path is not None:
        manifest = ckpt.restore_train_step(step_fn, path)
        resumed_from = int(manifest["step"])
    start = resumed_from + 1

    losses = {}
    steps = []
    for s in range(start, min(stop_after, total - 1) + 1):
        chaos.inject("step", step=s, rank=rank)
        X, Y = batch_for(s)
        losses[s] = float(np.asarray(step_fn(X, Y)["loss"]))
        steps.append(s)
        step_fn.save_checkpoint(
            os.path.join(ckpt_dir, f"step_{s}"), step=s, keep=keep,
            peer_timeout_s=60.0)
    ckpt.wait_pending()  # clean exit: every captured snapshot durable

    accums = step_fn.state["opt"]["accums"]
    first = accums[sorted(accums)[0]][0]
    zero1_sharded = any(p is not None and "dp" in str(p)
                        for p in tuple(first.sharding.spec))
    # one atomic write: ranks may share the parent's stdout pipe
    sys.stdout.write(json.dumps({
        "rank": rank,
        "world": world,
        "n_devices": len(jax.devices()),
        "resumed_from": resumed_from,
        "steps": steps,
        "losses": {str(k): v for k, v in losses.items()},
        "zero1_dp_sharded": bool(zero1_sharded),
        "reshards": int(_reg.counter("checkpoint/reshards").value),
        "saves": int(_reg.counter("checkpoint/saves").value),
        "async_saves": int(_reg.counter("checkpoint/async_saves").value),
    }) + "\n")
    sys.stdout.flush()


if __name__ == "__main__":
    main()

"""Evidence-gathering for the ResNet-50 gap (VERDICT r3 item 1).

Experiments:
1. iters scaling: step-time at iters=5 vs 40 -> fixed dispatch overhead
2. jax.profiler device trace
3. forward-only vs train-step split
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def build(batch=128, size=224, data_format="NCHW"):
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu import amp
    from paddle_tpu.framework import jit as fjit
    from paddle_tpu.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=1000, data_format=data_format)
    optimizer = opt.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters())

    def loss_fn(m, x, y):
        with amp.auto_cast():
            logits = m(x)
        return F.cross_entropy(logits.astype("float32"), y).mean()

    step = fjit.train_step(model, optimizer, loss_fn)
    rng = np.random.RandomState(0)
    shape = (batch, 3, size, size) if data_format == "NCHW" else (batch, size, size, 3)
    x = jax.device_put(rng.randn(*shape).astype("float32"))
    y = jax.device_put(rng.randint(0, 1000, (batch,)).astype("int64"))
    return model, step, x, y


def timeit(step, x, y, iters):
    float(np.asarray(step(x, y)["loss"]))
    float(np.asarray(step(x, y)["loss"]))
    t0 = time.perf_counter()
    for _ in range(iters):
        m = step(x, y)
    float(np.asarray(m["loss"]))
    return (time.perf_counter() - t0) / iters


def main():
    import jax

    batch = 128
    model, step, x, y = build(batch)
    t5 = timeit(step, x, y, 5)
    t40 = timeit(step, x, y, 40)
    # t(iters) = compute*iters + fetch_overhead => per-step at high iters
    print(json.dumps({"exp": "iters_scaling", "t_per_step_5": round(t5 * 1e3, 2),
                      "t_per_step_40": round(t40 * 1e3, 2),
                      "ips_40": round(batch / t40, 1)}), flush=True)

    # forward-only timing via the jitted eval step
    from paddle_tpu.framework import jit as fjit

    fwd_step = fjit.eval_step(model, lambda m, xx: m(xx).astype("float32").sum())
    float(np.asarray(fwd_step(x)))
    t0 = time.perf_counter()
    for _ in range(20):
        r = fwd_step(x)
    float(np.asarray(r))
    tf = (time.perf_counter() - t0) / 20
    print(json.dumps({"exp": "forward_only", "t_fwd_ms": round(tf * 1e3, 2),
                      "fwd_ips": round(batch / tf, 1)}), flush=True)

    # device trace attempt
    try:
        jax.profiler.start_trace("/tmp/resnet_trace")
        for _ in range(3):
            m = step(x, y)
        float(np.asarray(m["loss"]))
        jax.profiler.stop_trace()
        print(json.dumps({"exp": "trace", "ok": True, "dir": "/tmp/resnet_trace"}),
              flush=True)
    except Exception as e:
        print(json.dumps({"exp": "trace", "ok": False, "err": str(e)[:200]}),
              flush=True)


if __name__ == "__main__":
    main()

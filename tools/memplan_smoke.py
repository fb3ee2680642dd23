#!/usr/bin/env python
"""Memplan smoke (ISSUE 14): the static peak-HBM planner, certified.

Plans BERT-, ResNet-, and GPT-shaped static smoke programs and checks,
end to end through ``Executor.run``:

1. **Accuracy envelope** — ``plan_accuracy`` (predicted peak vs XLA's
   own ``memory_analysis``: argument + output + temp − alias) lands
   inside the documented envelope (``analysis.memory.ACCURACY_ENVELOPE``
   = ±25%) on every smoke program;
2. **Strict admission** — ``FLAGS_memory_budget_check=strict`` rejects a
   deliberately over-budget program BEFORE any compile, naming the
   high-water op and top tensors, and rejects the donated-then-read
   donation-safety golden naming the offending var.

Run: ``make memplan-smoke`` (wired into ``tools/build_and_test.sh check``).
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _check(name, ok, detail=""):
    status = "ok" if ok else "FAIL"
    print(f"[memplan-smoke] {name}: {status} {detail}")
    if not ok:
        raise SystemExit(f"memplan smoke failed: {name} {detail}")


def _run_one(name, build):
    """Build one smoke program, run a step, return its CostRecord."""
    import paddle_tpu.static as static
    from paddle_tpu.monitor import cost_model

    # each program names its params param_N from 0: the shared global
    # scope must not leak a previous program's arrays into this one
    static.global_scope().clear()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        feeds, fetch = build()
        exe = static.Executor()
        exe.run_startup()
        out = exe.run(feed=feeds, fetch_list=[fetch])
        loss = float(np.asarray(out[0]))
    rec = cost_model.latest_record("executor")
    assert rec is not None, f"{name}: no cost record captured"
    plan = main.plan_memory(
        feed_names=sorted(feeds), fetch_list=[fetch],
        feed_shapes={k: np.shape(v) for k, v in feeds.items()})
    print(f"[memplan-smoke] {name}: loss={loss:.4f} "
          f"predicted={plan.peak_bytes} "
          f"(high-water op #{plan.peak_op_index} <{plan.peak_op_type}>) "
          f"actual={rec.argument_bytes + rec.output_bytes + rec.temp_bytes - rec.alias_bytes} "
          f"plan_accuracy={rec.plan_accuracy}")
    return rec, plan


def build_bert():
    """BERT-shaped: embedding + 2 fc+layernorm blocks + MLM-ish head."""
    import paddle_tpu.static as static
    from paddle_tpu import ops

    B, S, E, V = 16, 32, 64, 512
    ids = static.data("ids", [B, S], "int64")
    label = static.data("label", [B * S, 1], "int64")
    table = static.nn.create_parameter([V, E], "float32")
    h = ops.embedding(ids, table)
    h = ops.reshape(h, [B * S, E])
    for i in range(2):
        h = static.nn.layer_norm(
            static.nn.fc(h, E, activation="relu", name=f"enc{i}"))
    logits = static.nn.fc(h, V, name="mlm")
    loss = ops.mean(ops.softmax_with_cross_entropy(logits, label))
    static.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    rng = np.random.RandomState(0)
    feeds = {"ids": rng.randint(0, V, (B, S)).astype("int64"),
             "label": rng.randint(0, V, (B * S, 1)).astype("int64")}
    return feeds, loss


def build_resnet():
    """ResNet-shaped: conv+bn+relu stem, pool, fc classifier."""
    import paddle_tpu.static as static
    from paddle_tpu import ops

    B = 8
    img = static.data("img", [B, 3, 16, 16], "float32")
    label = static.data("label", [B, 1], "int64")
    h = static.nn.conv2d(img, num_filters=8, filter_size=3, padding=1,
                         name="c1")
    h = ops.relu(static.nn.batch_norm(h))
    h = static.nn.conv2d(h, num_filters=16, filter_size=3, padding=1,
                         name="c2")
    h = ops.relu(static.nn.batch_norm(h))
    h = ops.max_pool2d(h, 2, stride=2)
    logits = static.nn.fc(h, 10, name="head")
    loss = ops.mean(ops.softmax_with_cross_entropy(logits, label))
    static.optimizer.Momentum(learning_rate=1e-2).minimize(loss)
    rng = np.random.RandomState(1)
    feeds = {"img": rng.randn(B, 3, 16, 16).astype("float32"),
             "label": rng.randint(0, 10, (B, 1)).astype("int64")}
    return feeds, loss


def build_gpt():
    """GPT-shaped: tied-embedding LM head over an fc decoder stack."""
    import paddle_tpu.static as static
    from paddle_tpu import ops

    B, S, E, V = 8, 32, 64, 512
    ids = static.data("ids", [B, S], "int64")
    label = static.data("label", [B * S, 1], "int64")
    table = static.nn.create_parameter([V, E], "float32")
    h = ops.reshape(ops.embedding(ids, table), [B * S, E])
    for i in range(3):
        h = static.nn.layer_norm(
            static.nn.fc(h, E, activation="relu", name=f"blk{i}"))
    logits = ops.matmul(h, ops.transpose(table, [1, 0]))  # tied head
    loss = ops.mean(ops.softmax_with_cross_entropy(logits, label))
    static.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    rng = np.random.RandomState(2)
    feeds = {"ids": rng.randint(0, V, (B, S)).astype("int64"),
             "label": rng.randint(0, V, (B * S, 1)).astype("int64")}
    return feeds, loss


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.static as static
    from paddle_tpu.analysis import DonationError, MemoryBudgetError
    from paddle_tpu.analysis.memory import ACCURACY_ENVELOPE
    from paddle_tpu.flags import set_flags

    static.enable_static()

    # 1) plan accuracy within the documented envelope on all three
    for name, build in (("bert", build_bert), ("resnet", build_resnet),
                        ("gpt", build_gpt)):
        rec, _plan = _run_one(name, build)
        _check(f"{name} record closed", rec.plan_accuracy is not None)
        lo, hi = 1.0 / ACCURACY_ENVELOPE, ACCURACY_ENVELOPE
        _check(f"{name} plan_accuracy within ±25% envelope",
               lo <= rec.plan_accuracy <= hi,
               f"({rec.plan_accuracy:.3f} in [{lo:.2f}, {hi:.2f}])")

    # 2a) strict admission rejects a deliberately over-budget program
    #     BEFORE compile, naming the high-water op
    set_flags({"device_peaks": "hbm_bytes=4096",
               "memory_budget_check": "strict"})
    static.global_scope().clear()
    main_p, startup = static.Program(), static.Program()
    with static.program_guard(main_p, startup):
        feeds, fetch = build_gpt()
        exe = static.Executor()
        exe.run_startup()
        try:
            exe.run(feed=feeds, fetch_list=[fetch])
            _check("strict rejects over-budget program", False)
        except MemoryBudgetError as e:
            _check("strict rejects over-budget program",
                   e.op_index is not None and e.op_type is not None
                   and str(e.op_type) in str(e),
                   f"(high-water op #{e.op_index} <{e.op_type}>)")
            _check("rejection precedes compile", len(exe._cache) == 0)
    set_flags({"device_peaks": "", "memory_budget_check": "strict"})

    # 2b) donation-safety golden: donated-then-read rejected by name
    p = static.Program()
    b = p.global_block()
    b.create_var(name="v", shape=[8], dtype="float32", is_data=True)
    b.create_var(name="w", shape=[8], dtype="float32")
    b.create_var(name="z", shape=[8], dtype="float32")
    b.append_op("relu", {"X": ["v"]}, {"Out": ["w"]},
                {"__inplace__": ["v"]})
    b.append_op("tanh", {"X": ["v"]}, {"Out": ["z"]}, {})
    exe = static.Executor()
    try:
        exe.run(p, feed={"v": np.ones(8, "f")}, fetch_list=["z"])
        _check("strict rejects donated-then-read", False)
    except DonationError as e:
        _check("strict rejects donated-then-read",
               e.var == "v" and "use-after-donation" in str(e),
               f"(op #{e.op_index} <{e.op_type}> var {e.var!r})")
    set_flags({"memory_budget_check": "warn"})

    print("[memplan-smoke] PASS")


if __name__ == "__main__":
    main()

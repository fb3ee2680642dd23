"""HLO-cost-backed model summary (the contrib/model_stat.py:1 role,
strictly better: FLOPs/bytes come from XLA's own cost analysis of each
layer's lowered HLO, not a hand-maintained formula)."""
import io
import contextlib

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn


def test_summary_cost_columns_tiny_model():
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        r = paddle.summary(net, (2, 16), cost=True)
    text = buf.getvalue()
    assert "FLOPs" in text and "Bytes" in text
    # linear1 matmul 2*2*16*32=2048 plus bias/second layer
    assert 2048 <= r["total_flops"] <= 4600
    assert r["total_bytes"] > 0
    assert set(r["layer_costs"]) == {"0", "1", "2"}
    # without cost: unchanged legacy shape
    with contextlib.redirect_stdout(io.StringIO()):
        r2 = paddle.summary(net, (2, 16))
    assert "total_flops" not in r2 and r2["total_params"] == r["total_params"]


def test_summary_cost_requires_input_size():
    import pytest

    with pytest.raises(ValueError, match="input_size"):
        paddle.summary(nn.Linear(2, 2), cost=True)


def test_resnet50_totals_match_hlo_census():
    """Pins the ResNet-50 numbers: 25.557M params; forward cost at
    batch 1 ~= 8.0 GFLOP (2x the published 4.09 GMACs — XLA counts
    multiply+add separately). The per-layer sum must also agree with an
    independent whole-model lowering within fusion slack."""
    import jax

    from paddle_tpu.framework import jit as fjit
    from paddle_tpu.models import resnet50

    paddle.seed(0)
    net = resnet50(num_classes=1000)
    with contextlib.redirect_stdout(io.StringIO()):
        r = paddle.summary(net, (1, 3, 224, 224), cost=True)
    assert r["total_params"] == 25_557_032
    assert 7.0e9 <= r["total_flops"] <= 9.0e9, r["total_flops"]

    # independent whole-model census
    state = fjit.capture_state(net)

    def fwd(state, x):
        out, _ = fjit.functional_call(net, state, x)
        return out

    net.eval()
    lowered = jax.jit(fwd).lower(
        state, np.zeros((1, 3, 224, 224), np.float32))
    ca = lowered.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    whole = float(ca["flops"])
    # whole-model fusion can only reduce the op count vs per-layer sums
    assert whole <= r["total_flops"] * 1.05
    assert abs(whole - r["total_flops"]) / whole < 0.25


def test_memory_usage_and_op_freq():
    """contrib/memory_usage_calc.py:46 + op_frequence.py:23 parity."""
    import pytest

    import paddle_tpu.static as static
    from paddle_tpu.incubate import memory_usage, op_freq_statistic

    static.reset_default_programs()
    static.enable_static()
    try:
        x = static.data("x", [None, 13], "float32")
        h = static.nn.fc(x, 32, activation="relu")
        static.nn.fc(h, 1)
        prog = static.default_main_program()
        low, high, unit = memory_usage(prog, batch_size=64)
        assert 0 < low < high and unit in ("B", "KB", "MB", "GB")
        uni, adj = op_freq_statistic(prog)
        assert uni["mul"] == 2 and uni["relu"] == 1
        assert next(iter(uni)) == max(uni, key=uni.get)
        assert any("relu" in k for k in adj)
        with pytest.raises(ValueError, match="positive"):
            memory_usage(prog, 0)
        with pytest.raises(TypeError):
            memory_usage("not a program", 1)
        with pytest.raises(TypeError):
            op_freq_statistic(42)
    finally:
        static.disable_static()
        static.reset_default_programs()

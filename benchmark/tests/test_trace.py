"""The reduction from device events to numbers (lib/tracing.py): idle
share, kernel time by name with nesting, the
attribution of idle gaps to host spans - on events written out here -
and the reading of a small `.xplane.pb` recorded on the chip."""
import os

import pytest

from benchmark.lib import tracing

HERE = os.path.dirname(os.path.abspath(__file__))


def trace(devices, marks=()):
    return tracing.DeviceTrace({"devices": devices, "marks": list(marks),
                                "modules": {}})


def test_idle_share_is_one_minus_the_union():
    tr = trace({"/device:TPU:0": [
        ("fusion.1", 0.0, 40.0, ""), ("fusion.2", 30.0, 30.0, ""),  # overlap
        ("copy.3", 80.0, 20.0, "")]})
    assert tr.window_ns == 100.0
    assert tr.busy_ns() == 80.0  # [0, 60] + [80, 100]
    assert tracing.union_ns([(0, 1), (1, 2), (5, 6)]) == 3


def test_kernel_time_by_name_does_not_count_a_loop_and_its_body_twice():
    evs = [("while.1", 0.0, 100.0, ""),
           ("layernorm_residual_fwd.1", 10.0, 30.0,
            '%layernorm_residual_fwd.1 = bf16[8] custom-call(bf16[8] %x), '
            'custom_call_target="tpu_custom_call"'),
           ("fusion.2", 50.0, 40.0, ""),
           ("layernorm_residual_bwd.3", 120.0, 20.0, "")]
    tr = trace({"/device:TPU:0": evs})
    ln = lambda n, x: "layernorm_residual" in n
    assert tr.time_by(ln) == 50.0
    assert tr.count_by(ln) == 2
    assert tr.time_by(tracing.is_mosaic) == 30.0
    assert tracing.op_name("%fusion.3 = f32[8]{0} fusion(%a)") == "fusion.3"
    selfs = dict((n, t) for n, t, _ in tracing.self_times(evs))
    assert selfs["while.1"] == 30.0  # 100 less the 70 nested inside it
    top = dict(tr.top_ops(4))
    assert top["fusion"] == pytest.approx(40e-9)
    assert "layernorm_residual_bwd" in top


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    us = 1e3
    evs = [("fusion.1", 0.0, 100 * us, ""), ("fusion.2", 400 * us, 100 * us, ""),
           ("fusion.3", 1000 * us, 50 * us, "")]
    tr = trace({"/device:TPU:0": evs})
    offset = 7e6  # host clock = device clock + offset
    spans = [("generation::prefill", offset + 90 * us, offset + 390 * us),
             ("generation::decode", offset + 600 * us, offset + 900 * us)]
    gaps = dict(tr.idle_gaps(spans, offset))
    assert gaps["generation::prefill"] == pytest.approx(300e-6)
    assert gaps["generation::decode"] == pytest.approx(500e-6)


@pytest.mark.skipif(
    not os.path.exists(os.path.join(HERE, "data", "small.xplane.pb")),
    reason="no recorded trace in the tree")
def test_recorded_chip_trace_reads():
    """A few steps of a small jitted function with one fused layer-norm
    kernel, recorded on the v5e by tests/record_trace.py."""
    tr = tracing.DeviceTrace(tracing.read_xplane(
        os.path.join(HERE, "data", "small.xplane.pb")))
    assert list(tr.devices) == ["/device:TPU:0"]
    assert 0 < tr.busy_ns() <= tr.window_ns
    ln = lambda n, x: "layernorm_residual_fwd" in n
    assert tr.count_by(ln) == 8 and tr.time_by(ln) > 0
    assert tr.time_by(tracing.is_mosaic) == tr.time_by(ln)
    assert tr.marks and tr.module_runs("small_step")
    assert tr.top_ops(3)

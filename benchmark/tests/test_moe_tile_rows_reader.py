"""The reader of the non-gated experts' kernel's counter (PR 41),
`moe_tile_rows_over_pairs`: on counter samples written out here, `None`
where the program has no such counter (the parent of PR 41, the gated
families, any run off the chip); then two rehearsal `--trace 1` runs of
`nemotron-3-super-120b.agentturn-overload` at tiny size on the CPU, as
it is (`jax.lax.ragged_dot` runs: the line leaves the metric out) and
with the kernel's gate opened (interpreted: the line carries it)."""
import os

import pytest

from benchmark.lib import common
from benchmark.tests import test_nemotron_h as cell_tests
from benchmark.tests import tiny
from paddle_tpu import profiler

root = cell_tests.root  # the cell's tiny checkout
NAME = "moe_tile_rows_over_pairs"


def _read():
    class Cell:
        dir = tiny.BENCH

    reader = common.load_module(os.path.join(tiny.BENCH, "layer_metrics",
                                             NAME + ".py"))
    return reader.read({"cell": Cell, "res": {"window": (0.0, 1e9)}})


@pytest.fixture()
def samples():
    """`record(name, value)` while the profiler is on; reset after."""
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    yield profiler.record_counter
    profiler.stop_profiler()
    profiler.reset_profiler()


def test_the_ratio_is_mean_rows_over_mean_pairs(samples):
    assert _read() is None
    # the parent's program, or a gated family's: pairs, no tile rows
    samples("moe::pairs_here", [70, 80, 90, 75, 85])
    samples("moe::pairs_here", [60, 90, 100, 70, 80])
    assert _read() is None
    samples("moe::tile_rows", [336, 352, 400, 320, 368])
    samples("moe::tile_rows", [304, 384, 416, 336, 352])
    assert _read() == pytest.approx((1776 + 1792) / (400 + 400))
    # a step the window's edge cut in two: one sample more of the one
    samples("moe::pairs_here", [80, 80, 80, 80, 80])
    assert _read() == pytest.approx(1784 / 400)


def test_no_pairs_is_nothing(samples):
    samples("moe::pairs_here", [0, 0, 0, 0, 0])
    samples("moe::tile_rows", [0, 0, 0, 0, 0])
    assert _read() is None


def test_the_entry_is_the_cells_alone():
    bench = common.load_json(os.path.join(tiny.ROOT, "BENCHMARK.json"))
    assert bench["per_layer"][-1] == dict(
        name=NAME, unit="ratio", better="lower", source="program_counter",
        layer="model code", moves="itl_p95_ms", workloads=[cell_tests.CELL])


@pytest.mark.parametrize("kernel", [False, True])
def test_rehearsal_traced_run_prints_the_ratio_where_the_kernel_runs(
        root, kernel, monkeypatch):
    if kernel:
        from paddle_tpu.parallel import moe

        monkeypatch.setattr(moe, "can_emit_mosaic", lambda: True)
        monkeypatch.setattr(moe, "grouped_relu2_supported", lambda *a: True)
    res, text = cell_tests._run(root, trace=1)
    assert res["correct"], text
    m = res["metrics"]
    assert (NAME in m) == kernel
    if kernel:
        # 6 slots x 6 pairs a step over 8 held experts, 8-row tiles
        assert m[NAME]["unit"] == "ratio" and 1.0 <= m[NAME]["value"] <= 8.0

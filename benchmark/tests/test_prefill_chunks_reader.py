"""The reader of the engine's admission counter (PR 45),
`prefill_chunks_per_admission`: on counter samples written out here,
`None` where the program takes no such samples (the parent of PR 45) or
no admission finished in the window; then a rehearsal `--trace 1` run of
`k-exaone-236b.longdoc-overload` at tiny size on the CPU, whose cache
keeps K/V rings alone and whose prompts are mostly longer than the
ladder's second bucket, the engine's chunk: the line carries the metric, above 1."""
import os

import pytest

from benchmark.lib import common
from benchmark.tests import test_k_exaone as cell_tests
from benchmark.tests import tiny
from paddle_tpu import profiler

root = cell_tests.root  # the cell's tiny checkout
NAME = "prefill_chunks_per_admission"


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


def test_the_ratio_is_programs_over_admissions_finished(samples):
    assert _read() is None
    # a prompt whose last chunk lies beyond the window's end: nothing yet
    samples("generation::prefill_chunks", [1, 0])
    samples("generation::prefill_chunks", [2, 0])
    assert _read() is None
    samples("generation::prefill_chunks", [3, 1])
    assert _read() == 3.0
    # prompts admitted whole, each one program
    samples("generation::prefill_chunks", [1, 1])
    samples("generation::prefill_chunks", [1, 1])
    assert _read() == pytest.approx(5 / 3)
    # a prompt that began before the window counts with all its programs
    samples("generation::prefill_chunks", [7, 1])
    assert _read() == pytest.approx(12 / 4)


def test_the_entry_lists_the_serving_cells():
    bench = common.load_json(os.path.join(tiny.ROOT, "BENCHMARK.json"))
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    prefill = next(m for m in bench["per_layer"] if m["name"] == "prefill_ms")
    assert entry == dict(
        name=NAME, unit="ratio", better="higher", source="program_counter",
        layer="generation engine", moves="itl_p95_ms",
        workloads=prefill["workloads"])


def test_rehearsal_traced_run_prints_chunks_where_the_kinds_allow(root):
    res, text = cell_tests._run(root, trace=1)
    assert res["correct"], text
    m = res["metrics"]
    # chunks of 16 tokens, prompts of 9-60 (median 20)
    assert 1.0 < m[NAME]["value"] <= 4.0
    assert m["compiles_in_window.serve"]["value"] == 0

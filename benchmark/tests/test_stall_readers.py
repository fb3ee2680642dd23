"""The two readers of the program's stall records (PR 38): each on a
recorder with two records, one before the window, for the sum and the
printed lines; a ring that evicted events of the window says so; a
program that writes no such record reads None; then rehearsal `--trace 1`
runs at tiny size on the CPU, which must print both metrics at 0.0."""
import io
import json
import os
import time
from types import SimpleNamespace

import pytest

from benchmark import run as harness
from benchmark.lib import common
from benchmark.tests import tiny
from paddle_tpu.monitor import flight_recorder

METRICS = os.path.join(tiny.BENCH, "layer_metrics")


def reader(name):
    return common.load_module(os.path.join(METRICS, name + ".py"))


def record(kind, t_ns, lost_ms, **more):
    fields = dict(t_ns=int(t_ns), held_phase="runtime::launch",
                  held_ms=lost_ms + 590.0, usual_ms=590.0, lost_ms=lost_ms,
                  held_by="runtime", program="prefill/16384",
                  program_runs=1, program_idle_s=41.5, thread_cpu_ms=3.2,
                  process_cpu_ms=40.1, run_delay_ms=0.4, steal_ms=0.0,
                  gc_ms=0.0, bytes_in_use=11_000, bytes_in_use_before=9_000)
    fields.update(more)
    flight_recorder.record_event(kind, **fields)


@pytest.fixture()
def recorder():
    flight_recorder.reset_recorder()
    yield
    flight_recorder.reset_recorder()


def serve_ctx(w0_perf_ns):
    mono = time.monotonic() - (time.perf_counter_ns() - w0_perf_ns) / 1e9
    return {"res": {"window": (mono, mono + 30.0)},
            "cell": SimpleNamespace(dir=tiny.BENCH)}


def test_serving_reader_sums_from_the_windows_start_through_the_drain(
        recorder, capsys):
    now = time.perf_counter_ns()
    w0 = now - 40e9  # the window opened 40 s ago and closed 10 s ago
    record("generation_stall", w0 - 5e9, 7000.0)        # set-up: not counted
    record("generation_stall", w0 + 12.5e9, 13410.0)    # inside the window
    record("generation_stall", w0 + 36e9, 0.0,          # in the drain
           held_phase="generation::prefill_fetch", held_by="device",
           program="prefill/512")
    record("train_stall", w0 + 1e9, 99.0)               # the other loop's
    record("generation_admit", w0 + 2e9, 5.0)           # no stall record
    assert reader("stall_lost_ms.serve").read(serve_ctx(w0)) == \
        pytest.approx(13410.0)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("stall: ")]
    assert len(lines) == 2
    assert lines[0].startswith(
        "stall: 12.500 s prefill/16384 runtime::launch 14000.0 ms "
        "(usual 590.0) lost 13410.0 held_by runtime cpu 3.2/40.1 ms "
        "run_delay 0.4 steal 0.0 gc 0.0 alloc 9000 -> 11000 runs 1 "
        "idle 41.5 s")
    assert "36.000 s prefill/512 generation::prefill_fetch" in lines[1]
    assert "held_by device" in lines[1]


def test_training_reader_sums_inside_the_window_only(recorder, capsys):
    now = time.perf_counter_ns()
    w0 = now - 30e9
    record("train_stall", w0 - 3e9, 25000.0, held_phase="outside")
    record("train_stall", w0 + 17.2e9, 1970.0, held_phase="outside",
           held_by="blocked", program="train_step")
    record("train_stall", w0 + 31e9, 500.0)  # after the window's end
    # the harness's stop_trace, 0.2 s after the last device event at
    # 2.9 s: the step saw 3.2 s of `outside` from 2.5 s on
    record("train_stall", w0 + 2.5e9, 2610.0, held_phase="outside",
           held_ms=3200.0, held_by="python", program="train_step")
    offset = 5e9  # the device trace's clock is this far behind
    ctx = {"res": {"window": (w0 / 1e9, w0 / 1e9 + 30.0)},
           "trace": SimpleNamespace(t0=w0 + 0.5e9 - offset,
                                    t1=w0 + 2.9e9 - offset),
           "clock_offset_ns": offset,
           "cell": SimpleNamespace(dir=tiny.BENCH)}
    assert reader("stall_lost_ms.train").read(ctx) == pytest.approx(1970.0)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("stall: ")]
    assert len(lines) == 2  # in the order they were recorded
    assert "17.200 s train_step outside" in lines[0]
    assert "held_by blocked" in lines[0] and "not counted" not in lines[0]
    assert "2.500 s train_step outside 3200.0 ms" in lines[1]
    assert lines[1].endswith("(the trace's own start or stop: not counted)")


def test_no_record_reads_zero_and_a_program_without_them_reads_none(
        recorder, monkeypatch, capsys):
    w0 = time.perf_counter_ns() - 30e9
    assert reader("stall_lost_ms.serve").read(serve_ctx(w0)) == 0.0
    assert "stall" not in capsys.readouterr().out
    # the parent of PR 38: its generation_stall events name no held phase
    record("generation_stall", w0 + 1e9, 10.0)
    monkeypatch.delattr(flight_recorder, "record_stall")
    assert reader("stall_lost_ms.serve").read(serve_ctx(w0)) is None


def test_a_ring_that_evicted_events_of_the_window_says_so(
        recorder, monkeypatch, capsys):
    small = flight_recorder.FlightRecorder(capacity=4)
    monkeypatch.setattr(flight_recorder, "_RECORDER", small)
    w0 = time.perf_counter_ns() - 1e9
    record("generation_stall", w0 + 0.1e9, 2000.0)
    for _ in range(6):
        flight_recorder.record_event("generation_admit")
    assert reader("stall_lost_ms.serve").read(serve_ctx(w0)) == 0.0
    out = capsys.readouterr().out
    assert "evicted events of this window (3 gone in all)" in out
    assert "may read low" in out


def _traced(root, workload, seconds):
    out = io.StringIO()
    res = harness.run_cell(root, workload, 2**31 + 79, seconds, 1,
                           require_chip=False, out=out)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == res
    return res


def test_rehearsal_traced_runs_print_both_metrics(tmp_path):
    root = tiny.checkout(tmp_path)
    res = _traced(root, "bert-base.pretrain-seq128", 2.0)
    assert res["metrics"]["stall_lost_ms.train"] == {"value": 0.0,
                                                     "unit": "ms"}
    path = os.path.join(root, "benchmark", "traffic", "chat-overload.json")
    with open(path) as f:
        mix = json.load(f)
    mix.update(trace_after_s=0.0, trace_s=1.5, backlog_at_start=0)
    with open(path, "w") as f:
        json.dump(mix, f)
    res = _traced(root, "gpt2-large.chat-overload", 4.0)
    assert res["metrics"]["stall_lost_ms.serve"] == {"value": 0.0,
                                                     "unit": "ms"}

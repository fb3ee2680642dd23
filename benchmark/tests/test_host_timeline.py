"""The readers of the program's own host timeline (PR 24): each on
hand-made spans and samples with a known answer, a window and a traced
interval that cut spans in two included; then rehearsal `--trace 1` runs
at tiny size on the CPU, which must print every new metric."""
import io
import json
import os
import time
from types import SimpleNamespace

import pytest

from benchmark import run as harness
from benchmark.lib import common
from benchmark.tests import tiny

METRICS = os.path.join(tiny.BENCH, "layer_metrics")
MS = 1e6  # ns


def reader(name):
    return common.load_module(os.path.join(METRICS, name + ".py"))


def make_ctx(spans, window_s=(0.0, 1.0), traced_ms=(400.0, 600.0),
             slots=4, cache_len=100, clock_offset_ns=7e9):
    """A ctx whose window starts now: ``spans`` are (name, start_ms,
    end_ms) from the window's first instant; the traced interval is given
    the same way and handed over on the profiler's clock, offset away."""
    mono = time.monotonic()
    base = (mono + time.perf_counter() - time.monotonic()) * 1e9
    host = [(n, base + s * MS, base + e * MS) for n, s, e in spans]
    trace = SimpleNamespace(t0=base + traced_ms[0] * MS - clock_offset_ns,
                            t1=base + traced_ms[1] * MS - clock_offset_ns)
    return {"spans": SimpleNamespace(host=host),
            "res": {"window": (mono + window_s[0], mono + window_s[1]),
                    "slots": slots},
            "trace": trace, "clock_offset_ns": clock_offset_ns,
            "cell": SimpleNamespace(
                dir=tiny.BENCH, cfg={"engine": {"cache_len": cache_len}}),
            "base_ns": base}


def loop(n, period=100.0, enqueue=6.0, device=80.0, slow_from=None,
         slow_to=None, slow_period=140.0, prefill_at=()):
    """n decode iterations: decode (enqueue) then decode_fetch (device),
    the rest of the period split over deliver and pick; iterations that
    start in [slow_from, slow_to) take ``slow_period``; an iteration in
    ``prefill_at`` carries a 20 ms admission before its decode."""
    spans, t = [], 0.0
    for i in range(n):
        p = slow_period if (slow_from is not None
                            and slow_from <= t < slow_to) else period
        if i in prefill_at:
            spans += [("generation::prefill", t, t + 4.0),
                      ("generation::prefill_fetch", t + 4.0, t + 20.0),
                      ("serving::install", t + 20.0, t + 21.0)]
            t += 21.0
        spans += [("generation::decode", t, t + enqueue),
                  ("generation::args", t + 1.0, t + 3.0),
                  ("generation::decode_fetch", t + enqueue,
                   t + enqueue + device),
                  ("serving::deliver", t + enqueue + device, t + p - 1.0),
                  ("serving::pick", t + p - 1.0, t + p)]
        t += p
    return spans


def test_host_gap_is_fetch_end_to_next_enqueue_end_less_admissions():
    # period 100: fetch ends at 86, the next decode ends at 106: gap 20,
    # whether or not a 21 ms admission lies between
    gaps = reader("host_gap_ms.serve")
    assert gaps.read(make_ctx(loop(10))) == pytest.approx(20.0, abs=1e-3)
    ctx = make_ctx(loop(10, prefill_at=(3, 7)))
    # an admission's install phase (1 ms) is host time; its prefill and
    # prefill_fetch (20 ms) are not: the median over 9 gaps stays 20
    assert gaps.read(ctx) == pytest.approx(20.0, abs=1e-3)
    every = make_ctx(loop(10, prefill_at=tuple(range(10))))
    assert gaps.read(every) == pytest.approx(21.0, abs=1e-3)


def test_host_gap_leaves_out_the_traced_interval_and_what_the_window_cuts():
    # iterations inside 400-700 ms run slow (host gap 60, not 20); the
    # traced interval covers them, so the median reads the plain ones
    spans = loop(10, slow_from=400.0, slow_to=700.0)
    ctx = make_ctx(spans, traced_ms=(395.0, 830.0))
    assert reader("host_gap_ms.serve").read(ctx) == pytest.approx(20.0,
                                                                   abs=1e-3)
    # a window that ends inside the slow stretch, nothing traced there
    cut = make_ctx(spans, window_s=(0.0, 0.45), traced_ms=(2000.0, 2100.0))
    assert reader("host_gap_ms.serve").read(cut) == pytest.approx(20.0,
                                                                   abs=1e-3)
    assert reader("host_gap_ms.serve").read(make_ctx(
        [("generation::decode", 0.0, 6.0)])) is None  # the parent's spans


def test_trace_overhead_is_traced_over_plain_start_to_start():
    spans = loop(12, slow_from=400.0, slow_to=820.0)  # 3 slow iterations
    ctx = make_ctx(spans, window_s=(0.0, 1.5), traced_ms=(395.0, 830.0))
    assert reader("trace_overhead_pct.serve").read(ctx) == pytest.approx(
        40.0, abs=1e-3)
    # a pair that straddles an edge of the traced interval counts on
    # neither side; with nothing wholly inside there is no reading
    assert reader("trace_overhead_pct.serve").read(
        make_ctx(spans, traced_ms=(450.0, 500.0))) is None


def test_prefill_share_is_clipped_to_the_window_and_leaves_idle_out():
    spans = [("serving::idle_wait", 0.0, 500.0),
             ("generation::prefill", 500.0, 510.0),
             ("generation::prefill_fetch", 510.0, 550.0),
             ("generation::decode", 550.0, 990.0),
             # cut in two by the window's end at 1,000 ms
             ("generation::prefill", 990.0, 1010.0),
             ("generation::prefill_fetch", 1010.0, 1100.0)]
    ctx = make_ctx(spans)
    # (10 + 40 + 10) of (1,000 - 500) working ms
    assert reader("prefill_share_pct").read(ctx) == pytest.approx(12.0,
                                                                   abs=0.01)
    assert reader("prefill_share_pct").read(make_ctx(
        [("generation::prefill", 0.0, 6.0)])) is None


def test_loop_stall_names_the_longest_phase_and_when(capsys):
    spans = loop(8) + [("serving::idle_wait", 800.0, 5800.0),
                       ("generation::decode_fetch", 806.0, 3406.0),
                       ("generation::args", 900.0, 3500.0),
                       # begins after the window: not this window's
                       ("serving::deliver", 1200.0, 9000.0)]
    ctx = make_ctx(spans, traced_ms=(5000.0, 5100.0))
    assert reader("loop_stall_max_ms").read(ctx) == pytest.approx(
        2600.0, abs=1e-3)
    line = capsys.readouterr().out.strip()
    assert line.startswith("loop_stall_max_ms: generation::decode_fetch "
                           "2600.000 ms, 0.806 s into the window")
    assert reader("loop_stall_max_ms").read(make_ctx(
        [("generation::decode", 0.0, 6.0)])) is None


def test_loop_stall_leaves_out_the_phases_at_the_traced_intervals_edges(
        capsys):
    # one fetch start_trace held (120 ms, ending 220 ms before the first
    # device event), the phase open at that event (300 ms), and the one
    # stop_trace held (150 ms, begun 30 ms after the last device event):
    # the trace's doing. The plain loop's worst is a fetch of 95 ms.
    spans = loop(3) + [("generation::decode_fetch", 1210.0, 1330.0),
                       ("generation::decode_fetch", 1400.0, 1700.0),
                       ("serving::deliver", 1700.0, 1701.0),
                       ("generation::decode", 2030.0, 2180.0),
                       ("generation::decode_fetch", 2180.0, 2260.0),
                       ("serving::deliver", 2600.0, 2601.0),
                       ("generation::decode_fetch", 2700.0, 2795.0)]
    ctx = make_ctx(spans, window_s=(0.0, 3.0), traced_ms=(1550.0, 2000.0))
    assert reader("loop_stall_max_ms").read(ctx) == pytest.approx(
        95.0, abs=1e-3)
    assert "generation::decode_fetch 95.000 ms, 2.700 s" in \
        capsys.readouterr().out


def samples(base_ns, points):
    return [{"name": n, "ph": "C", "ts": (base_ns + t * MS) / 1e3,
             "args": {"value": v}} for n, t, v in points]


def test_counts_are_time_weighted_over_the_window(monkeypatch):
    from paddle_tpu import profiler

    ctx = make_ctx([], slots=4, cache_len=100)
    pts = [("serving::slots_busy", -100.0, 4),  # held into the window
           ("serving::slots_busy", 250.0, 2),
           ("serving::slots_busy", 750.0, 4),
           ("serving::slots_busy", 1500.0, 0),   # after the window
           ("serving::kv_live_tokens", 0.0, 100),
           ("serving::kv_live_tokens", 500.0, 300),
           ("some::other_count", 0.0, 9)]
    monkeypatch.setattr(profiler, "counter_samples",
                        lambda: samples(ctx["base_ns"], pts))
    # 4 for 250 ms, 2 for 500 ms, 4 for 250 ms: mean 3 of 4 slots
    assert reader("slots_busy_pct.sched").read(ctx) == pytest.approx(
        75.0, abs=0.01)
    # 100 then 300 tokens, half the window each, of 4 x 100 positions
    assert reader("kv_live_pct").read(ctx) == pytest.approx(50.0, abs=0.01)
    monkeypatch.setattr(profiler, "counter_samples", lambda: [])
    assert reader("slots_busy_pct.sched").read(ctx) is None
    monkeypatch.delattr(profiler, "counter_samples")  # the parent
    assert reader("kv_live_pct").read(ctx) is None


def test_decode_program_bytes_come_from_its_cost_record(monkeypatch):
    from paddle_tpu.monitor import cost_model

    mem = {"argument_size_in_bytes": 600, "output_size_in_bytes": 300,
           "temp_size_in_bytes": 50, "alias_size_in_bytes": 250}
    rec = cost_model.CostRecord("k", "generation_decode", {"flops": 1.0},
                                mem, {})
    monkeypatch.setattr(cost_model, "latest_record",
                        lambda label: rec if label == "generation_decode"
                        else None)
    assert reader("program_hbm_bytes.decode").read({}) == 700
    monkeypatch.setattr(cost_model, "latest_record", lambda label: None)
    assert reader("program_hbm_bytes.decode").read({}) is None


def test_train_phase_medians_read_the_window_only():
    now = time.perf_counter()
    base = now * 1e9
    host = [("train::shard_batch", base + s * MS, base + e * MS)
            for s, e in ((-50.0, -10.0), (10.0, 12.0), (110.0, 114.0),
                         (210.0, 213.0), (1300.0, 1390.0))]
    host += [("train::step_dispatch", base + s * MS, base + s * MS + 5 * MS)
             for s in (12.0, 114.0, 213.0)]
    ctx = {"spans": SimpleNamespace(host=host),
           "res": {"window": (now, now + 1.0)},
           "cell": SimpleNamespace(dir=tiny.BENCH)}
    assert reader("h2d_ms.train").read(ctx) == pytest.approx(3.0, abs=1e-3)
    assert reader("dispatch_ms.train").read(ctx) == pytest.approx(5.0,
                                                                   abs=1e-3)
    ctx["spans"] = SimpleNamespace(host=[("bench::step_group", base,
                                          base + MS)])
    assert reader("h2d_ms.train").read(ctx) is None


SERVING = ("host_gap_ms.serve", "prefill_share_pct",
           "trace_overhead_pct.serve", "loop_stall_max_ms",
           "slots_busy_pct.sched", "kv_live_pct", "program_hbm_bytes.decode")


def _traced(root, workload, seconds):
    out = io.StringIO()
    res = harness.run_cell(root, workload, 2**31 + 77, seconds, 1,
                           require_chip=False, out=out)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == res
    return res


def test_rehearsal_traced_serving_run_prints_every_new_metric(tmp_path):
    root = tiny.checkout(tmp_path)
    path = os.path.join(root, "benchmark", "traffic", "chat-overload.json")
    with open(path) as f:
        mix = json.load(f)
    # at tiny size a backlog is served in an instant: spread the
    # arrivals, and trace the window's first part
    mix.update(trace_after_s=0.0, trace_s=1.5, backlog_at_start=0)
    with open(path, "w") as f:
        json.dump(mix, f)
    res = _traced(root, "gpt2-large.chat-overload", 4.0)
    assert res["correct"]
    got = res["metrics"]
    assert set(SERVING) <= set(got), sorted(set(SERVING) - set(got))
    # collect_program_spans ran with counter samples present, and the
    # samples never reached its span list
    assert "queue_wait_p95_ms" in got
    assert 0 < got["slots_busy_pct.sched"]["value"] <= 100
    assert 0 < got["kv_live_pct"]["value"] <= 100
    assert 0 <= got["prefill_share_pct"]["value"] < 100
    assert got["host_gap_ms.serve"]["value"] > 0
    assert got["program_hbm_bytes.decode"]["value"] > 0
    names = {n for n, _ in res["breakdown"]["idle_gaps"]}
    assert not any("iteration" in n for n in names)


def test_rehearsal_traced_training_run_prints_the_two_host_phases(tmp_path):
    root = tiny.checkout(tmp_path)
    res = _traced(root, "bert-base.pretrain-seq128", 2.0)
    assert res["correct"]
    assert res["metrics"]["h2d_ms.train"]["value"] > 0
    assert res["metrics"]["dispatch_ms.train"]["value"] > 0

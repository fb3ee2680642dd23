"""Per-op device-time attribution (monitor.opprof): stamp grammar,
trace-parser edge table, replay profiler, /profilez, and the
profiler double-start guard."""
import gzip
import json
import os
import re

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import monitor, ops, profiler
from paddle_tpu.monitor import opprof
import paddle_tpu.static as static


def _small_program():
    """Tiny fc+relu inference program, executed once so the scope holds
    its parameters and the executor cache holds its compiled entry."""
    static.enable_static()
    static.global_scope().clear()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = static.data("x", [8, 16], "float32")
        h = static.nn.fc(x, 8, name="l1")
        out = ops.relu(h)
    exe = static.Executor()
    exe.run_startup(startup)
    feeds = {"x": np.ones((8, 16), np.float32)}
    exe.run(main, feed=feeds, fetch_list=[out])
    return main, feeds, out, exe


# ---------------------------------------------------------------------------
# stamp grammar
# ---------------------------------------------------------------------------


def test_stamp_round_trip():
    s = opprof.op_scope_name("matmul", 0, 3)
    assert s == "matmul#0/3"
    assert opprof.parse_op_scope(s) == ("matmul", 0, 3)


def test_stamp_parses_inside_scope_paths():
    # HLO location metadata and CPU-trace event names embed the stamp in
    # longer paths; the parser must find it either way
    assert opprof.parse_op_scope(
        "jit(block)/jit(main)/matmul#0/3/dot_general") == ("matmul", 0, 3)
    assert opprof.parse_op_scope(
        "PjitFunction(grad::mul#2/17)") == ("grad::mul", 2, 17)
    assert opprof.parse_op_scope("no stamp here") is None
    assert opprof.parse_op_scope("trailing#only") is None


def test_executor_lowering_carries_stamps():
    # the executor's named_scope stamping must survive into the compiled
    # module's HLO text: per-op identity, not just op type
    _, _, _, exe = _small_program()
    entry = next(iter(exe._cache.values()))
    assert entry.aot is not None
    txt = entry.aot.as_text()
    stamps = set(re.findall(r"[a-z_0-9:]+#\d+/\d+", txt))
    assert any(s.startswith("mul#0/") for s in stamps), stamps
    assert any(s.startswith("relu#0/") for s in stamps), stamps
    # distinct ops of the same block carry distinct indices
    assert len(stamps) >= 3


# ---------------------------------------------------------------------------
# trace-parser edge table
# ---------------------------------------------------------------------------


def _write_trace(dirpath, events, name="t.trace.json.gz"):
    fn = os.path.join(dirpath, name)
    with gzip.open(fn, "wt") as f:
        json.dump({"traceEvents": events}, f)
    return fn


def _ev(name, ts, dur, tid=1, pid=1, ph="X"):
    return {"name": name, "ts": ts, "dur": dur, "tid": tid, "pid": pid,
            "ph": ph}


def test_attribute_trace_empty_dir_is_no_data(tmp_path):
    table = opprof.attribute_trace(str(tmp_path))
    assert table["status"] == "no-data"
    assert table["coverage"] is None
    assert table["ops"] == []
    # a missing dir degrades the same way
    assert opprof.attribute_trace(str(tmp_path / "nope"))["status"] == \
        "no-data"


def test_attribute_trace_truncated_gzip_skipped(tmp_path):
    _write_trace(str(tmp_path), [_ev("mul#0/0", 0, 100)], "good.trace.json.gz")
    # gzip-truncated file: valid header, chopped body
    bad = tmp_path / "bad.trace.json.gz"
    with gzip.open(str(bad), "wt") as f:
        f.write('{"traceEvents": [{"name": "mul#0/1"')
    blob = bad.read_bytes()
    bad.write_bytes(blob[: len(blob) // 2])
    table = opprof.attribute_trace(str(tmp_path))
    assert table["files"] == 1
    assert table["files_skipped"] == 1
    assert table["status"] == "ok"
    assert table["ops"][0]["scope"] == "mul#0/0"


def test_attribute_trace_unstamped_counts_against_coverage(tmp_path):
    _write_trace(str(tmp_path), [
        _ev("mul#0/0", 0, 100),
        _ev("some_xla_thunk", 200, 100),   # no stamp: against coverage
        _ev("$builtins next", 400, 500),   # python tracer: excluded
    ])
    table = opprof.attribute_trace(str(tmp_path))
    assert table["total_us"] == pytest.approx(200.0)
    assert table["stamped_us"] == pytest.approx(100.0)
    assert table["coverage"] == pytest.approx(0.5)
    assert table["unattributed_us"] == pytest.approx(100.0)


def test_attribute_trace_cross_block_collisions_stay_distinct(tmp_path):
    # same op type and index in different blocks: the stamp keeps them
    # apart (the whole point of the #<block>/<index> grammar)
    _write_trace(str(tmp_path), [
        _ev("relu#0/2", 0, 100),
        _ev("relu#1/2", 200, 50),
    ])
    table = opprof.attribute_trace(str(tmp_path))
    scopes = {r["scope"]: r["time_us"] for r in table["ops"]}
    assert scopes == {"relu#0/2": 100.0, "relu#1/2": 50.0}


def test_attribute_trace_folds_nested_scopes(tmp_path):
    # a stamped scope nested inside another stamped scope must not
    # double count its interval
    _write_trace(str(tmp_path), [
        _ev("scan#0/0", 0, 100),
        _ev("mul#1/0", 10, 20),
    ])
    table = opprof.attribute_trace(str(tmp_path))
    assert table["total_us"] == pytest.approx(100.0)
    assert table["stamped_us"] == pytest.approx(100.0)
    assert table["coverage"] == pytest.approx(1.0)
    # per-op self times still report both
    scopes = {r["scope"]: r["time_us"] for r in table["ops"]}
    assert scopes["scan#0/0"] == 100.0
    assert scopes["mul#1/0"] == 20.0


def test_attribute_trace_only_scores_stamped_timelines(tmp_path):
    # a timeline with no stamped event at all (host bookkeeping thread)
    # is not scored — it must not dilute coverage
    _write_trace(str(tmp_path), [
        _ev("mul#0/0", 0, 100, tid=1),
        _ev("epoll_wait", 0, 10_000, tid=2),
    ])
    table = opprof.attribute_trace(str(tmp_path))
    assert table["timelines"] == 1
    assert table["coverage"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# replay profiler + closures
# ---------------------------------------------------------------------------


def test_profile_program_replay_and_closures():
    main, feeds, _, _ = _small_program()
    prof = opprof.profile_program(main, feeds, name="small",
                                  with_trace=False)
    assert prof["replayed_ops"] == prof["n_ops"] > 0
    replayed = [r for r in prof["ops"] if r["replayed"]]
    for row in replayed:
        assert row["time_us"] > 0
        assert 0.0 <= row["share"] <= 1.0
        assert row["roofline"] in ("compute-bound", "memory-bound",
                                   "unknown")
        assert row["predicted_us"] > 0
        assert row["mfu"] >= 0.0
    assert prof["total_us"] == pytest.approx(
        sum(r["time_us"] for r in replayed), rel=1e-6)
    # the time-accuracy closure landed on the executor's CostRecord
    # (the plan_accuracy discipline) and rides /costz's to_dict
    rec = monitor.cost_model.latest_record("executor")
    assert rec.time_accuracy == prof["time_accuracy"] is not None
    assert rec.measured_op_us == prof["total_us"]
    d = rec.to_dict()
    assert d["time_accuracy"] == rec.time_accuracy
    assert d["predicted_op_us"] == rec.predicted_op_us
    # and the histogram family is on the exporter, with op_type labels
    txt = monitor.prometheus_text()
    assert "opprof_op_time_ms" in txt
    assert 'op_type="mul"' in txt


def test_profile_program_trace_coverage():
    main, feeds, _, _ = _small_program()
    prof = opprof.profile_program(main, feeds, name="covered")
    att = prof["attribution"]
    assert att["status"] == "ok"
    # the stamped-jit naming makes replay traces self-identifying even
    # on CPU: coverage must clear the smoke gate's bar
    assert prof["coverage"] is not None and prof["coverage"] >= 0.9
    assert any(r["op_type"] == "mul" for r in att["ops"])


def test_profile_program_skips_grad_ops_cleanly():
    static.enable_static()
    static.global_scope().clear()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = static.data("x", [4, 4], "float32")
        h = static.nn.fc(x, 4, name="g1")
        loss = ops.mean(h)
        static.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = static.Executor()
    exe.run_startup(startup)
    feeds = {"x": np.ones((4, 4), np.float32)}
    exe.run(main, feed=feeds, fetch_list=[loss])
    prof = opprof.profile_program(main, feeds, name="train",
                                  with_trace=False)
    skipped = [r for r in prof["ops"] if not r["replayed"]]
    assert any("grad" in r["scope"] for r in skipped)
    for r in skipped:
        assert r["reason"]
    assert prof["replayed_ops"] > 0  # the forward half still profiles


def test_measure_pass_deltas_profiles_both_sides_of_the_rewrite():
    """``measure_pass_deltas`` replays the program before and after the
    pass pipeline: the fused op has a time only after, the chain it
    replaced only before, and the op with the most FLOPs on either side
    is the convolution. Which side is faster is not asserted: a time
    taken here says nothing of the chip."""
    from paddle_tpu.analysis import measure_pass_deltas

    static.enable_static()
    static.global_scope().clear()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        img = static.data("img", [2, 3, 8, 8], "float32")
        h = static.nn.conv2d(img, num_filters=4, filter_size=3, padding=1,
                             bias_attr=False, name="c1")
        out = static.nn.fc(ops.relu(static.nn.batch_norm(h, is_test=True)),
                           5, name="head")
    exe = static.Executor()
    exe.run_startup(startup)
    feeds = {"img": np.ones((2, 3, 8, 8), np.float32)}
    exe.run(main, feed=feeds, fetch_list=[out])

    got = measure_pass_deltas(main, feeds, [out.name], level=1,
                              name="convnet", warmup=1, repeats=2)
    assert got["changed"]
    assert any(p["name"] == "fuse_conv_bn_relu" and p["ops_rewritten"] >= 1
               for p in got["passes"])
    fused = got["deltas"]["fused_conv_bn_relu"]
    assert fused["before_ops"] == 0 and fused["after_ops"] == 1
    assert fused["after_us"] > 0
    for op_type in ("conv2d", "batch_norm", "relu"):
        row = got["deltas"][op_type]
        assert row["before_ops"] == 1 and row["after_ops"] == 0
        assert row["before_us"] > 0 and row["after_us"] == 0
    assert got["before_us"] > 0 and got["after_us"] > 0
    for side, top in (("pre", "conv2d"), ("post", "fused_conv_bn_relu")):
        rows = [r for r in opprof.latest_profile(f"convnet@{side}")["ops"]
                if r["replayed"]]
        assert max(rows, key=lambda r: r["flops"] or 0)["op_type"] == top


def test_chrome_events_track():
    main, feeds, _, _ = _small_program()
    opprof.profile_program(main, feeds, name="tracked", with_trace=False)
    events = opprof.chrome_events()
    ops_events = [e for e in events if e.get("cat") == "opprof"]
    assert ops_events
    assert all(opprof.parse_op_scope(e["name"]) for e in ops_events)
    meta = [e for e in events if e.get("ph") == "M"]
    assert any("tracked" in str(e["args"]) for e in meta)


# ---------------------------------------------------------------------------
# /profilez payloads (store + HTTP)
# ---------------------------------------------------------------------------


def test_profilez_payload_no_data_then_populated():
    status, payload = opprof.profilez_payload({})
    assert status == 200 and payload["status"] == "no-data"
    main, feeds, _, _ = _small_program()
    opprof.profile_program(main, feeds, name="zpage", with_trace=False)
    status, payload = opprof.profilez_payload({})
    assert status == 200 and payload["status"] == "ok"
    assert payload["program"] == "zpage"
    assert payload["summary"]["time_accuracy_envelope"] == \
        opprof.TIME_ACCURACY_ENVELOPE
    status, payload = opprof.profilez_payload({"program": "ghost"})
    assert status == 404 and payload["status"] == "unknown-program"
    status, payload = opprof.profilez_payload({"topk": "2"})
    assert len(payload["ops"]) <= 2


def test_profilez_served_by_debug_server():
    import urllib.request

    main, feeds, _, _ = _small_program()
    opprof.profile_program(main, feeds, name="http", with_trace=False)
    srv = monitor.start_debug_server(port=0)
    try:
        body = json.load(urllib.request.urlopen(srv.url + "/profilez"))
        assert body["status"] == "ok" and "http" in body["programs"]
        body = json.load(urllib.request.urlopen(
            srv.url + "/profilez?program=http&topk=1"))
        assert len(body["ops"]) == 1
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/profilez?program=ghost")
        assert ei.value.code == 404
        index = urllib.request.urlopen(srv.url + "/").read().decode()
        assert "/profilez" in index
    finally:
        monitor.stop_debug_server()


def test_top_ops_table():
    main, feeds, _, _ = _small_program()
    opprof.profile_program(main, feeds, name="topk", with_trace=False)
    top = opprof.top_ops(2)
    assert len(top) == 2
    assert top[0]["time_us"] >= top[1]["time_us"]
    stats = opprof.opprof_stats()
    assert stats["latest"]["name"] == "topk"
    assert stats["top_ops"]


# ---------------------------------------------------------------------------
# profiler double-start guard (satellite)
# ---------------------------------------------------------------------------


def test_double_start_is_noop_with_flight_event():
    profiler.reset_counters()
    try:
        profiler.start_profiler(trace_dir="/tmp/ptpu_test_trace_a")
        first_dir = profiler.device_trace_dir()
        # second start: no raise, no dir clobber, flight event + counter
        profiler.start_profiler(trace_dir="/tmp/ptpu_test_trace_b")
        assert profiler.device_trace_dir() == first_dir
        assert profiler.counters().get("profiler::double_start", 0) >= 1
        events = monitor.flight_recorder.get_recorder().events()
        assert any(
            getattr(e, "kind", None) == "profiler_double_start"
            or (isinstance(e, dict) and e.get("kind") ==
                "profiler_double_start")
            for e in events)
    finally:
        profiler.stop_profiler()
    # device_trace_dir() persists past stop by design (the chrome-trace
    # exporter reads the most recent trace from it) — the live-trace
    # state, however, must be clear: a fresh start is NOT a double start
    before = profiler.counters().get("profiler::double_start", 0)
    profiler.start_profiler(trace_dir="/tmp/ptpu_test_trace_c")
    try:
        assert profiler.counters().get(
            "profiler::double_start", 0) == before
    finally:
        profiler.stop_profiler()


def test_stop_without_start_is_clean():
    profiler.stop_profiler()  # no live trace: must not raise
    profiler.stop_profiler()  # and stays idempotent

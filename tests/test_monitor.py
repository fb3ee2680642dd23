"""Monitor subsystem: registry, HBM gauges, whole-stack spans, the
TrainingMonitor periodic line, and both exporters.

Acceptance pins (ISSUE 2): histogram bucketing, HBM gauge population,
executor/dataloader/collective spans in an exported merged chrome trace,
TrainingMonitor line fields, Prometheus dump parseability.
"""
import gzip
import json
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, profiler


class FakeDevice:
    """PJRT-device stand-in: publishes arena counters."""

    def __init__(self, in_use=100, peak=200, limit=1000):
        self._stats = {
            "bytes_in_use": in_use,
            "peak_bytes_in_use": peak,
            "bytes_limit": limit,
        }

    def memory_stats(self):
        return self._stats


class NoStatsDevice:
    def memory_stats(self):
        return None  # the CPU backend publishes nothing


# -- registry ----------------------------------------------------------------

def test_counter_gauge_basics():
    c = monitor.counter("t/c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = monitor.gauge("t/g")
    g.set(2.5)
    g.add(0.5)
    assert g.value == 3.0
    snap = monitor.registry_snapshot()
    assert snap["t/c"] == {"kind": "counter", "value": 5}
    assert snap["t/g"] == {"kind": "gauge", "value": 3.0}


def test_histogram_bucketing():
    h = monitor.histogram("t/h_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.2, 0.9, 5.0, 10.0, 99.0, 1e4):
        h.observe(v)
    # le semantics: boundary value lands IN its bucket (10.0 -> le=10)
    assert h.bucket_counts() == [2, 2, 1, 1]
    assert h.cumulative_counts() == [2, 4, 5, 6]
    assert h.count == 6
    assert h.sum == pytest.approx(0.2 + 0.9 + 5.0 + 10.0 + 99.0 + 1e4)


def test_metric_kind_collision_raises():
    monitor.counter("t/collide")
    with pytest.raises(TypeError):
        monitor.gauge("t/collide")


def test_histogram_bounds_mismatch_raises():
    monitor.histogram("t/hb", buckets=(1.0, 10.0))
    monitor.histogram("t/hb")  # no explicit bounds: reuse is fine
    monitor.histogram("t/hb", buckets=(10.0, 1.0))  # same set, any order
    with pytest.raises(ValueError):
        monitor.histogram("t/hb", buckets=(5.0, 50.0))


def test_get_or_create_returns_same_object():
    assert monitor.counter("t/same") is monitor.counter("t/same")


def test_stat_int_parity():
    """STAT_INT/STAT_ADD/STAT_RESET (platform/monitor.h macro surface)."""
    monitor.stat_add("sparse_rows", 10)
    monitor.stat_add("sparse_rows", 5)
    assert monitor.STAT_INT("sparse_rows").value == 15
    monitor.stat_reset("sparse_rows")
    assert monitor.STAT_INT("sparse_rows").value == 0
    monitor.STAT_FLOAT("loss").set(0.25)
    assert monitor.registry_snapshot()["stat/float/loss"]["value"] == 0.25


def test_reset_registry_zeroes_and_unregisters():
    monitor.counter("t/r").inc(9)
    monitor.reset_registry()
    assert monitor.counter("t/r").value == 0  # zeroed, still registered
    monitor.reset_registry(unregister=True)
    assert "t/r" not in monitor.all_metrics()


# -- HBM gauges --------------------------------------------------------------

def test_hbm_gauge_population():
    vals = monitor.collect_hbm_gauges([FakeDevice(), FakeDevice(peak=900)])
    assert vals["hbm/device0/bytes_in_use"] == 100
    assert vals["hbm/device1/peak_bytes_in_use"] == 900
    # the gauges landed in the registry, not just the return value
    snap = monitor.registry_snapshot()
    assert snap["hbm/device0/bytes_limit"]["value"] == 1000
    assert monitor.hbm_watermark_bytes(
        [FakeDevice(peak=300), FakeDevice(peak=700)]) == 700


def test_hbm_gauges_skip_statless_backends():
    # no counters published -> nothing recorded (a zero gauge would read
    # as "no memory in use")
    assert monitor.collect_hbm_gauges([NoStatsDevice()]) == {}
    assert monitor.hbm_watermark_bytes([NoStatsDevice()]) == 0


def test_hbm_gauges_real_devices_never_raise():
    monitor.collect_hbm_gauges()  # CPU backend: publishes nothing


# -- jax.monitoring listeners -------------------------------------------------

def test_jax_monitoring_events_become_metrics():
    import jax

    assert monitor.install_jax_listeners()
    jax.monitoring.record_event("/test/retrace")
    jax.monitoring.record_event("/test/retrace")
    jax.monitoring.record_event_duration_secs("/test/compile", 0.05)
    snap = monitor.registry_snapshot()
    assert snap["jax/test/retrace"]["value"] == 2
    assert snap["jax/test/compile"]["value"] == 1
    h = snap["jax/test/compile/duration_ms"]
    assert h["kind"] == "histogram" and h["count"] == 1
    assert h["sum"] == pytest.approx(50.0)


def test_real_jit_compile_is_counted():
    import jax
    import jax.numpy as jnp

    assert monitor.install_jax_listeners()
    before = sum(
        m.value for name, m in monitor.all_metrics().items()
        if name.startswith("jax/") and "compile" in name
        and m.kind == "counter")

    @jax.jit
    def f(x):
        return x * 2 + 1

    f(jnp.arange(7)).block_until_ready()
    after = sum(
        m.value for name, m in monitor.all_metrics().items()
        if name.startswith("jax/") and "compile" in name
        and m.kind == "counter")
    assert after > before


# -- whole-stack spans in the merged chrome trace ----------------------------

def test_merged_trace_has_executor_dataloader_collective_spans(tmp_path):
    import paddle_tpu.distributed as dist
    import paddle_tpu.static as static
    from paddle_tpu.io import DataLoader

    profiler.reset_profiler()
    static.reset_default_programs()
    static.enable_static()
    try:
        x = static.data("x", [4, 3], "float32")
        y = paddle.multiply(x, x)
        exe = static.Executor()
        profiler.start_profiler(state="CPU")
        for _ in range(2):
            exe.run(feed={"x": np.ones((4, 3), np.float32)},
                    fetch_list=[y])

        class DS:
            def __len__(self):
                return 8

            def __getitem__(self, i):
                return np.full((3,), i, np.float32)

        for _ in DataLoader(DS(), batch_size=4):
            pass
        dist.all_reduce(paddle.to_tensor(np.ones((2, 2), np.float32)))
        profiler.stop_profiler()
    finally:
        static.disable_static()
        static.reset_default_programs()

    path = str(tmp_path / "merged.json")
    monitor.export_merged_chrome_trace(path)
    trace = json.load(open(path))
    names = {e.get("name") for e in trace["traceEvents"]}
    for expected in ("executor::plan", "executor::feed",
                     "executor::dispatch", "executor::jit_compile",
                     "executor::writeback", "dataloader::prefetch_fill",
                     "dataloader::h2d", "collective::all_reduce"):
        assert expected in names, (expected, sorted(names))
    # byte/latency accounting rode along with the collective span
    snap = monitor.registry_snapshot()
    assert snap["collective/all_reduce/calls"]["value"] == 1
    assert snap["collective/all_reduce/bytes"]["value"] == 2 * 2 * 4
    assert snap["collective/all_reduce/latency_ms"]["count"] == 1
    profiler.reset_profiler()


def test_merged_trace_includes_device_trace_files(tmp_path):
    """Device-side .trace.json.gz files (the jax.profiler layout) merge
    into the same traceEvents list as the host spans."""
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    with profiler.RecordEvent("host_side"):
        pass
    profiler.stop_profiler()
    run_dir = tmp_path / "plugins" / "profile" / "run1"
    os.makedirs(run_dir)
    dev_event = {"name": "fusion.42", "ph": "X", "ts": 1, "dur": 5,
                 "pid": 7, "tid": 0}
    with gzip.open(run_dir / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": [dev_event]}, f)
    path = str(tmp_path / "merged.json")
    monitor.export_merged_chrome_trace(path,
                                       device_trace_dir=str(tmp_path))
    events = json.load(open(path))["traceEvents"]
    by_name = {e.get("name"): e for e in events}
    assert "host_side" in by_name and "fusion.42" in by_name
    # device clock re-based onto the host track: the device event (raw
    # ts=1, its own epoch) must land AT the earliest host span, not an
    # enormous offset away in its original clock domain
    assert by_name["fusion.42"]["ts"] == by_name["host_side"]["ts"]
    profiler.reset_profiler()


# -- TrainingMonitor ----------------------------------------------------------

def test_training_monitor_periodic_line_fields():
    lines = []
    mon = monitor.TrainingMonitor(
        "unit", interval=2, devices=[FakeDevice(peak=12345)],
        log_fn=lines.append)
    out = []
    for i in range(4):
        with mon.step(examples=16):
            monitor.record_input_wait_ms(1.0)
        out.append(mon.last_line)
    assert len(lines) == 2  # steps 2 and 4
    line = lines[-1]
    assert line == mon.last_line
    m = re.match(
        r"\[monitor:unit\] step=(\d+) step_ms=([\d.]+) "
        r"examples_per_sec=([\d.]+) input_wait_ratio=([\d.]+) "
        r"plan_cache_hit_rate=([\d.]+) jit_cache_hit_rate=([\d.]+) "
        r"compiles=(\d+) hbm_peak_bytes=(\d+) "
        r"mfu=([\d.e+-]+) hbm_bw_util=([\d.e+-]+) "
        r"roofline=(compute-bound|memory-bound|unknown)$", line)
    assert m, line
    assert int(m.group(1)) == 4
    assert float(m.group(3)) > 0  # examples/sec
    assert 0.0 < float(m.group(4)) <= 1.0  # input-wait ratio saw the 1ms
    assert int(m.group(8)) == 12345  # HBM watermark from the fake device
    # aggregates also landed in the registry (exporters see them too)
    snap = monitor.registry_snapshot()
    assert snap["monitor/unit/steps"]["value"] == 4
    assert snap["monitor/unit/examples"]["value"] == 64
    assert snap["monitor/unit/step_ms"]["count"] == 4


def test_training_monitor_interval_flag_and_silence():
    paddle.set_flags({"monitor_interval": 3})
    try:
        lines = []
        mon = monitor.TrainingMonitor("flagged", log_fn=lines.append)
        for _ in range(6):
            with mon.step():
                pass
        assert len(lines) == 2
        paddle.set_flags({"monitor_interval": 0})  # silent, still counting
        for _ in range(5):
            with mon.step():
                pass
        assert len(lines) == 2
        assert mon.step_count == 11
    finally:
        paddle.set_flags({"monitor_interval": 100})


def test_training_monitor_cache_hit_rates_from_executor():
    import paddle_tpu.static as static

    static.reset_default_programs()
    static.enable_static()
    try:
        x = static.data("x", [2, 2], "float32")
        y = paddle.add(x, x)
        exe = static.Executor()
        feed = {"x": np.ones((2, 2), np.float32)}
        exe.run(feed=feed, fetch_list=[y])  # compile outside the window
        lines = []
        mon = monitor.TrainingMonitor("exec", interval=3,
                                      log_fn=lines.append)
        for _ in range(3):
            with mon.step(examples=2):
                exe.run(feed=feed, fetch_list=[y])
        assert len(lines) == 1
        # steady state: every run in the window hit both caches
        assert "plan_cache_hit_rate=1.000" in lines[0]
        assert "jit_cache_hit_rate=1.000" in lines[0]
    finally:
        static.disable_static()
        static.reset_default_programs()


def test_monitored_steps_under_the_profiler_reach_both_exports(tmp_path):
    """Three executor steps inside ``mon.step()`` with the profiler on:
    the merged chrome trace carries the monitor's own step span beside
    the executor's, and the Prometheus dump the step histogram."""
    import paddle_tpu.static as static

    static.reset_default_programs()
    static.enable_static()
    try:
        x = static.data("x", [2, 2], "float32")
        y = paddle.add(x, x)
        exe = static.Executor()
        profiler.reset_profiler()
        profiler.start_profiler(state="CPU")
        mon = monitor.TrainingMonitor("both", interval=3,
                                      log_fn=lambda line: None)
        for _ in range(3):
            with mon.step(examples=2):
                exe.run(feed={"x": np.ones((2, 2), np.float32)},
                        fetch_list=[y])
        profiler.stop_profiler()
    finally:
        static.disable_static()
        static.reset_default_programs()
    monitor.export_merged_chrome_trace(str(tmp_path / "merged.json"))
    with open(tmp_path / "merged.json") as f:
        names = [e.get("name") for e in json.load(f)["traceEvents"]]
    assert names.count("monitor::both::step") == 3
    assert "executor::dispatch" in names
    prom = monitor.export_prometheus(str(tmp_path / "metrics.prom"))
    assert 'monitor_both_step_ms_bucket{le="+Inf"} 3' in prom
    assert "step=3" in mon.last_line
    profiler.reset_profiler()


def test_training_monitor_step_end_without_begin_raises():
    mon = monitor.TrainingMonitor("bad", interval=0)
    with pytest.raises(RuntimeError):
        mon.step_end()


def test_training_monitor_failed_step_is_discarded():
    mon = monitor.TrainingMonitor("aborts", interval=0)
    with mon.step(examples=4):
        pass
    with pytest.raises(ValueError):
        with mon.step(examples=4):
            raise ValueError("step body blew up")
    # the failed step neither counted nor left the begin-state armed
    assert mon.step_count == 1
    snap = monitor.registry_snapshot()
    assert snap["monitor/aborts/step_ms"]["count"] == 1
    assert snap["monitor/aborts/aborted_steps"]["value"] == 1
    with pytest.raises(RuntimeError):
        mon.step_end()  # stale _t_begin would have made this "succeed"


# -- PS RPC accounting --------------------------------------------------------

def test_ps_rpc_and_serve_metrics():
    from paddle_tpu.distributed.ps.client import PSClient
    from paddle_tpu.distributed.ps.server import TableServer

    srv = TableServer().start()
    try:
        cli = PSClient(srv.endpoint)
        cli.create_table("emb", 4)
        cli.pull("emb", [1, 2, 3])
        cli.push_grad("emb", [1], np.ones((1, 4), np.float32), 0.1)
        snap = monitor.registry_snapshot()
        # client-side round trips and server-side handling both recorded
        assert snap["ps/rpc/pull/ms"]["count"] == 1
        assert snap["ps/rpc/push_grad/ms"]["count"] == 1
        assert snap["ps/serve/pull/ms"]["count"] == 1
        cli.close()
    finally:
        srv.stop()


def test_ps_malformed_message_gets_structured_error_reply():
    """A validly-framed message that is not an (op, ...) tuple still gets
    the ('err', ...) reply — never a bare connection drop — and lands in
    the malformed accounting."""
    import socket

    from paddle_tpu.distributed.ps.server import (
        TableServer, _recv_msg, _send_msg,
    )

    srv = TableServer().start()
    try:
        host, port = srv.endpoint.rsplit(":", 1)
        with socket.create_connection((host, int(port)), timeout=10) as s:
            _send_msg(s, 42)  # scalar: no op field at all
            status, payload = _recv_msg(s)
            assert status == "err", (status, payload)
            _send_msg(s, ())  # empty tuple
            status, _ = _recv_msg(s)
            assert status == "err"
        snap = monitor.registry_snapshot()
        assert snap["ps/serve/malformed/errors"]["value"] == 2
    finally:
        srv.stop()


def test_ps_unknown_ops_share_one_metric_bucket():
    """Wire-supplied op strings never become metric names verbatim: a
    peer cycling unique bogus ops cannot grow the registry unboundedly."""
    from paddle_tpu.distributed.ps.client import PSClient
    from paddle_tpu.distributed.ps.server import TableServer

    srv = TableServer().start()
    try:
        cli = PSClient(srv.endpoint)
        for i in range(5):
            with pytest.raises(RuntimeError):
                cli.request(f"bogus_op_{i}")
        snap = monitor.registry_snapshot()
        assert snap["ps/serve/unknown/errors"]["value"] == 5
        # (the client names its own rpc metrics — that side is not
        # attacker-controlled; only the serve side must be bounded)
        assert not any(k.startswith("ps/serve/") and "bogus_op" in k
                       for k in snap)
        cli.close()
    finally:
        srv.stop()


# -- Prometheus export --------------------------------------------------------

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{le=\"[^\"]+\"\})? -?[0-9.e+-]+$")


def test_prometheus_dump_parseable(tmp_path):
    monitor.counter("prom/c").inc(3)
    monitor.gauge("prom/g").set(1.5)
    h = monitor.histogram("prom/h", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(100.0)
    profiler.bump_counter("executor::plan_cache_hit", 2)
    path = str(tmp_path / "metrics.prom")
    text = monitor.export_prometheus(path)
    assert open(path).read() == text
    families = {}
    for line in text.strip().splitlines():
        if line.startswith("# HELP "):
            continue  # free-form docstring (escaped), not a sample line
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            assert kind in ("counter", "gauge", "histogram")
            families[name] = kind
        else:
            assert _PROM_LINE.match(line), line
            base = line.split("{")[0].split()[0]
            root = re.sub(r"_(bucket|sum|count)$", "", base)
            assert base in families or root in families, line
    assert families["prom_c"] == "counter"
    assert families["prom_h"] == "histogram"
    # histogram exposition: cumulative buckets + +Inf + sum/count
    assert 'prom_h_bucket{le="1.0"} 1' in text
    assert 'prom_h_bucket{le="+Inf"} 2' in text
    assert "prom_h_count 2" in text
    # the profiler's always-on counters export under the same roof
    assert "executor__plan_cache_hit 2" in text


def test_prometheus_dump_empty_registry():
    monitor.reset_registry(unregister=True)
    profiler.reset_counters()
    assert monitor.prometheus_text() == "\n"


def test_prometheus_dump_nonfinite_values():
    """inf/nan metric values render as exposition-format literals
    instead of crashing every later export (AMP loss-scale sentinels)."""
    monitor.gauge("nf/inf").set(float("inf"))
    monitor.gauge("nf/ninf").set(float("-inf"))
    monitor.histogram("nf/h", buckets=(1.0,)).observe(float("nan"))
    text = monitor.prometheus_text()
    assert "nf_inf +Inf" in text
    assert "nf_ninf -Inf" in text
    assert "nf_h_sum NaN" in text


def test_ps_rpc_error_counter_on_dead_server():
    """Wire failures (server gone mid-request) still land in the rpc
    latency histogram and error counter — the failure mode these
    metrics exist to diagnose."""
    from paddle_tpu.distributed.ps.client import PSClient
    from paddle_tpu.distributed.ps.server import TableServer

    srv = TableServer().start()
    cli = PSClient(srv.endpoint)
    cli.create_table("emb", 2)
    srv.stop()
    with pytest.raises((ConnectionError, OSError, RuntimeError)):
        for _ in range(50):  # until the dead socket surfaces
            cli.pull("emb", [1])
    snap = monitor.registry_snapshot()
    assert snap["ps/rpc/pull/errors"]["value"] >= 1
    assert snap["ps/rpc/pull/ms"]["count"] >= 1
    cli.close()

"""Hardware-utilization accounting: cost-model capture/goldens, MFU math,
device peaks, TrainingMonitor utilization fields + close(), collective
algorithmic-bytes accounting, straggler detection, debug endpoints."""
import json
from urllib.request import urlopen

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor import cluster, cost_model


# -- analysis normalization (the shared guard) -------------------------------

class _FakeStage:
    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        if isinstance(self._ca, Exception):
            raise self._ca
        return self._ca


def test_analyze_cost_normalizes_list_and_guards_none():
    assert cost_model.analyze_cost(None) is None
    assert cost_model.analyze_cost(_FakeStage(None)) is None
    assert cost_model.analyze_cost(_FakeStage([])) is None
    assert cost_model.analyze_cost(_FakeStage({})) is None
    assert cost_model.analyze_cost(_FakeStage(RuntimeError("nope"))) is None
    # per-partition list form collapses to the first entry
    got = cost_model.analyze_cost(_FakeStage([{"flops": 7.0}]))
    assert got == {"flops": 7.0}
    assert cost_model.analyze_cost(_FakeStage({"flops": 3.0})) == {
        "flops": 3.0}


def test_flops_and_bytes_guard():
    assert cost_model.flops_and_bytes(_FakeStage(None)) is None
    assert cost_model.flops_and_bytes(
        _FakeStage({"flops": 2.0, "bytes accessed": 8.0})) == (2.0, 8.0)
    # partial analysis: missing keys degrade to 0.0, not KeyError
    assert cost_model.flops_and_bytes(_FakeStage({"other": 1.0})) == (
        0.0, 0.0)


def test_capture_partial_backend_still_records():
    rec = cost_model.capture("partial_backend", lowered=_FakeStage(None),
                             compiled=None, key="partial")
    assert rec.partial is True
    assert rec.flops == 0.0 and rec.peak_hbm_bytes == 0
    # a partial record is a free no-op on the ledger
    cost_model.note_run(rec)
    assert monitor.counter("cost/executed_flops").value == 0


# -- matmul golden + MFU math ------------------------------------------------

def test_matmul_flops_golden_and_mfu_math():
    M, K, N = 64, 128, 32

    def f(a, b):
        return a @ b

    lowered = jax.jit(f).lower(jnp.zeros((M, K), jnp.float32),
                               jnp.zeros((K, N), jnp.float32))
    rec = cost_model.capture("golden", lowered=lowered,
                             compiled=lowered.compile(), key="golden")
    want = 2.0 * M * N * K
    assert rec.flops == pytest.approx(want, rel=0.05)
    assert rec.bytes_accessed > 0
    # memory analysis: arguments are the two operands, output the product
    assert rec.argument_bytes == (M * K + K * N) * 4
    assert rec.output_bytes == M * N * 4

    # MFU == measured FLOP/s over an explicit peak (no table guesswork)
    paddle.set_flags({"device_peaks":
                      "flops=1e9,hbm_bw=1e9,ici_bw=1e9"})
    try:
        peaks = cost_model.device_peaks()
        assert peaks["flops"] == 1e9 and peaks["nominal"] is False
        steps_per_sec = 10.0
        assert cost_model.mfu(rec.flops * steps_per_sec, peaks) == \
            pytest.approx(rec.flops * steps_per_sec / 1e9)
        assert cost_model.hbm_bw_util(rec.bytes_accessed * 2.0, peaks) == \
            pytest.approx(rec.bytes_accessed * 2.0 / 1e9)
    finally:
        paddle.set_flags({"device_peaks": ""})


def test_device_peaks_table_and_flag_override():
    v4 = cost_model.device_peaks(kind="TPU v4")
    assert v4["flops"] == 275e12 and v4["nominal"] is False
    # what the v5e reports for device_kind (chip run, PR 21)
    v5e = cost_model.device_peaks(kind="TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bytes"] == 16.9e9
    cpu = cost_model.device_peaks(kind="cpu")
    assert cpu["nominal"] is True
    assert cost_model.device_peaks()["kind"] == "cpu"  # the suite's device
    paddle.set_flags({"device_peaks": "flops=5e13, hbm_bw=2e12"})
    try:
        p = cost_model.device_peaks(kind="cpu")
        # any subset overrides; the rest keeps the table's values
        assert p["flops"] == 5e13 and p["hbm_bw"] == 2e12
        assert p["ici_bw"] == cpu["ici_bw"]
        assert p["nominal"] is False
        # garbage entries degrade, never raise
        paddle.set_flags({"device_peaks": "flops=oops,junk,=3"})
        assert cost_model.device_peaks(kind="TPU v4")["flops"] == 275e12
    finally:
        paddle.set_flags({"device_peaks": ""})


def test_unknown_accelerator_kind_is_an_error():
    """An accelerator the table does not know must not get made-up
    peaks: the error names the kind. Only a complete FLAGS_device_peaks
    (new silicon, all four numbers asserted) stands in for a table row."""
    with pytest.raises(ValueError, match="warp-drive-9000"):
        cost_model.device_peaks(kind="warp-drive-9000")
    paddle.set_flags({"device_peaks": "flops=5e13, hbm_bw=2e12"})
    try:
        with pytest.raises(ValueError, match="warp-drive-9000"):
            cost_model.device_peaks(kind="warp-drive-9000")
        paddle.set_flags({"device_peaks":
                          "flops=5e13,hbm_bw=2e12,ici_bw=1e11,hbm_bytes=3e10"})
        p = cost_model.device_peaks(kind="warp-drive-9000")
        assert p["hbm_bytes"] == 3e10 and p["nominal"] is False
    finally:
        paddle.set_flags({"device_peaks": ""})


def test_roofline_classification():
    peaks = {"flops": 100.0, "hbm_bw": 10.0, "ici_bw": 1.0}  # ridge = 10
    assert cost_model.roofline_class(1000.0, 10.0, peaks) == "compute-bound"
    assert cost_model.roofline_class(50.0, 10.0, peaks) == "memory-bound"
    assert cost_model.roofline_class(0.0, 10.0, peaks) == "unknown"
    assert cost_model.roofline_class(10.0, 0.0, peaks) == "unknown"


# -- executor integration ----------------------------------------------------

def _tiny_static_loop(steps=3, mon=None):
    import paddle_tpu.static as static
    from paddle_tpu import ops

    static.enable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    try:
        x = static.data("x", [8, 16], "float32")
        y = static.data("y", [8, 1], "float32")
        w = static.nn.create_parameter([16, 1], "float32")
        loss = ops.mean(ops.square(ops.subtract(ops.matmul(x, w), y)))
        opt = static.optimizer.SGD(learning_rate=0.1)
        opt.minimize(loss)
        exe = static.Executor()
        exe.run_startup()
        rng = np.random.RandomState(0)
        X = rng.randn(8, 16).astype("float32")
        Y = rng.randn(8, 1).astype("float32")
        out = None
        for _ in range(steps):
            if mon is not None:
                with mon.step(examples=8):
                    out = exe.run(feed={"x": X, "y": Y},
                                  fetch_list=[loss])
            else:
                out = exe.run(feed={"x": X, "y": Y}, fetch_list=[loss])
        return float(np.asarray(out[0]))
    finally:
        static.disable_static()
        static.reset_default_programs()
        static.global_scope().clear()


def test_executor_compile_captures_cost_record_and_ledger():
    _tiny_static_loop(steps=4)
    rec = cost_model.latest_record("executor")
    assert rec is not None and rec.partial is False
    assert rec.flops > 0 and rec.bytes_accessed > 0
    assert rec.runs == 4  # one compile, four dispatches
    snap = monitor.registry_snapshot()
    assert snap["cost/executed_flops"]["value"] == pytest.approx(
        4 * rec.flops)
    assert snap["cost/executed_bytes"]["value"] == pytest.approx(
        4 * rec.bytes_accessed)
    # per-label gauges feed the Prometheus dump
    assert snap["cost/executor/flops"]["value"] == rec.flops
    prom = monitor.prometheus_text()
    assert "cost_executed_flops" in prom
    assert "cost_executor_peak_hbm_bytes" in prom
    # the capture left a flight-recorder breadcrumb
    kinds = {e["kind"] for e in monitor.flight_recorder.events()}
    assert "cost_capture" in kinds


def test_monitor_line_gains_utilization_fields():
    lines = []
    mon = monitor.TrainingMonitor("util", interval=2, log_fn=lines.append)
    _tiny_static_loop(steps=2, mon=mon)
    assert lines, "no monitor line emitted"
    line = lines[-1]
    for field in ("mfu=", "hbm_bw_util=", "roofline="):
        assert field in line, (field, line)
    s = mon.snapshot()
    assert "mfu" in s and "hbm_bw_util" in s and "roofline" in s
    # the window consumed real executed FLOPs, so gauges were set
    snap = monitor.registry_snapshot()
    assert "monitor/util/mfu" in snap
    assert "monitor/util/hbm_bw_util" in snap


def test_train_step_captures_cost_record():
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as popt
    from paddle_tpu.framework import jit as fjit

    paddle.seed(0)
    net = nn.Linear(8, 4)
    optimizer = popt.SGD(learning_rate=0.1, parameters=net.parameters())

    def loss_fn(m, a, b):
        return ((m(a) - b) ** 2).mean()

    step = fjit.train_step(net, optimizer, loss_fn)
    rng = np.random.RandomState(0)
    a = rng.randn(4, 8).astype("float32")
    b = rng.randn(4, 4).astype("float32")
    losses = [float(np.asarray(step(a, b)["loss"])) for _ in range(4)]
    assert losses[-1] < losses[0]  # the AOT dispatch path still trains
    rec = cost_model.latest_record("train_step")
    assert rec is not None and rec.flops > 0
    assert rec.runs == 4


# -- TrainingMonitor close() / empty-window guards ---------------------------

def test_monitor_close_flushes_partial_window():
    lines = []
    mon = monitor.TrainingMonitor("short", interval=100,
                                  log_fn=lines.append)
    for _ in range(3):  # run length < interval: silent without close()
        with mon.step(examples=4):
            pass
    assert lines == []
    line = mon.close()
    assert line is not None and "step=3" in line
    assert lines == [line]
    # idempotent: a second close neither re-emits nor double-counts
    assert mon.close() is None
    assert len(lines) == 1


def test_monitor_close_respects_silence_and_empty_window():
    lines = []
    mon = monitor.TrainingMonitor("silent", interval=0,
                                  log_fn=lines.append)
    with mon.step():
        pass
    assert mon.close() is None and lines == []  # 0 means silent
    # empty window: snapshot never divides by zero
    mon2 = monitor.TrainingMonitor("empty", interval=5)
    s = mon2.snapshot()
    assert s["step_ms"] == 0.0 and s["mfu"] == 0.0
    assert s["roofline"] == "unknown"
    assert mon2.close() is None  # nothing to flush


def test_monitor_close_detaches_active_slot():
    mon = monitor.TrainingMonitor("detach", interval=0)
    assert monitor.active_monitor() is mon
    mon.close()
    # a closed monitor must stop feeding cluster snapshots
    assert monitor.active_monitor() is None
    row = cluster.local_snapshot()
    assert row["step"] == 0  # identity row, not the dead window
    # a newer monitor is never displaced by an older one closing
    m1 = monitor.TrainingMonitor("detach1", interval=0)
    m2 = monitor.TrainingMonitor("detach2", interval=0)
    m1.close()
    assert monitor.active_monitor() is m2


def test_monitor_close_aborts_inflight_step():
    mon = monitor.TrainingMonitor("abort", interval=100)
    mon.step_begin()
    mon.close()
    snap = monitor.registry_snapshot()
    assert snap["monitor/abort/aborted_steps"]["value"] == 1
    with pytest.raises(RuntimeError):
        mon.step_end()


# -- collective algorithmic bytes --------------------------------------------

def test_collective_algo_bytes_factors():
    from paddle_tpu.distributed import collective as coll

    assert coll._algo_bytes("all_reduce", 100, 1) == 0  # lone rank: no wire
    assert coll._algo_bytes("all_reduce", 800, 8) == 1400  # 2*(7/8)*800
    assert coll._algo_bytes("all_gather", 100, 4) == 300   # (n-1)*B
    assert coll._algo_bytes("reduce_scatter", 800, 8) == 700
    assert coll._algo_bytes("broadcast", 800, 8) == 700
    assert coll._algo_bytes("p2p", 100, 4) == 100
    assert coll._algo_bytes("barrier", 0, 8) == 0
    assert coll._algo_bytes("wait", 100, 8) == 0  # rank-local sync


def test_collective_traced_algo_bytes_and_bus_util():
    import paddle_tpu.distributed as dist
    from paddle_tpu import parallel
    from paddle_tpu.distributed import collective as coll

    mesh = parallel.create_mesh(dp=8)
    with parallel.mesh_scope(mesh):
        # trace-time: the accounting fires in _account.__enter__ before
        # psum needs a bound axis (which make_jaxpr cannot provide)
        try:
            jax.make_jaxpr(lambda a: dist.all_reduce(a))(
                jnp.ones((16,), jnp.float32))
        except Exception:
            pass
    snap = monitor.registry_snapshot()
    # traced call, 8-way dp group: 2*(8-1)/8 * 64 payload bytes — the
    # per-execution ICI volume of the compiled program
    assert snap["collective/all_reduce/traced_algo_bytes"]["value"] == 112
    assert coll.per_execution_algo_bytes() == {"all_reduce": 112}
    # bus utilization at a given step rate against an explicit ICI peak
    util = coll.ici_bus_util(
        100.0, peaks={"ici_bw": 112 * 1000.0, "kind": "t", "flops": 1,
                      "hbm_bw": 1, "nominal": False})
    assert util["all_reduce"] == pytest.approx(0.1)
    assert util["total"] == pytest.approx(0.1)
    snap = monitor.registry_snapshot()
    assert snap["collective/all_reduce/bus_util"]["value"] == \
        pytest.approx(0.1)


def test_collective_eager_identity_moves_no_algo_bytes():
    import paddle_tpu.distributed as dist
    from paddle_tpu import parallel
    from paddle_tpu.distributed import collective as coll

    # eager collectives are identity transforms in the single-controller
    # runtime — even under a mesh they move no wire bytes, so accounting
    # them would fabricate utilization
    mesh = parallel.create_mesh(dp=8)
    with parallel.mesh_scope(mesh):
        dist.all_reduce(paddle.to_tensor(np.ones((16,), np.float32)))
    snap = monitor.registry_snapshot()
    assert snap["collective/all_reduce/bytes"]["value"] == 64
    assert "collective/all_reduce/algo_bytes" not in snap
    assert "collective/all_reduce/bus_util" not in snap
    assert coll.ici_bus_util(100.0) == {}


# -- cluster aggregation / straggler detection -------------------------------

def _snap(rank, step_ms, step=10):
    return {"rank": rank, "step": step, "step_ms": step_ms, "mfu": 0.1,
            "hbm_bw_util": 0.05, "input_wait_ratio": 0.0}


def test_detect_stragglers_flags_slow_rank():
    by_rank = {0: _snap(0, 10.0), 1: _snap(1, 11.0), 2: _snap(2, 9.5),
               3: _snap(3, 52.0)}
    stragglers, median = cluster.detect_stragglers(by_rank, threshold=2.0)
    assert median == pytest.approx(10.5)
    assert [s["rank"] for s in stragglers] == [3]
    assert stragglers[0]["ratio_to_median"] == pytest.approx(52.0 / 10.5,
                                                             rel=1e-3)
    # nobody past the threshold: no verdict
    assert cluster.detect_stragglers(
        {0: _snap(0, 10.0), 1: _snap(1, 12.0)}, threshold=2.0) == ([], 11.0)


def test_detect_stragglers_ignores_cold_ranks():
    # a rank with no steps yet is missing evidence, not "infinitely fast"
    by_rank = {0: _snap(0, 0.0, step=0), 1: _snap(1, 10.0),
               2: _snap(2, 30.0)}
    stragglers, median = cluster.detect_stragglers(by_rank, threshold=1.4)
    assert median == pytest.approx(20.0)
    assert [s["rank"] for s in stragglers] == [2]
    # fewer than 2 reporting ranks: nothing to compare against
    assert cluster.detect_stragglers({0: _snap(0, 10.0)}) == ([], 0.0)


def test_detect_stragglers_threshold_flag():
    by_rank = {0: _snap(0, 10.0), 1: _snap(1, 18.0)}
    paddle.set_flags({"straggler_threshold": 1.2})
    try:
        stragglers, _ = cluster.detect_stragglers(by_rank)
        assert [s["rank"] for s in stragglers] == [1]
    finally:
        paddle.set_flags({"straggler_threshold": 1.5})


def test_clusterz_payload_single_process_and_flight_event():
    mon = monitor.TrainingMonitor("clusterz_unit", interval=0)
    with mon.step(examples=8):
        pass
    payload = cluster.clusterz_payload()
    assert payload["world"] == 1
    assert len(payload["ranks"]) == 1
    row = payload["ranks"][0]
    assert row["step"] == 1 and "mfu" in row and "step_ms" in row
    assert payload["stragglers"] == [] and payload["missing_ranks"] == []
    # no straggler, no missing rank -> no verdict event polluting the ring
    kinds = {e["kind"] for e in monitor.flight_recorder.events()}
    assert "straggler_verdict" not in kinds


class _DictChannel:
    """Injectable KV channel (the cross-rank store, minus the fleet)."""

    def __init__(self):
        self.kv = {}

    def set(self, key, value):
        self.kv[key] = value

    def get(self, key, timeout_s):
        if key not in self.kv:
            raise TimeoutError(key)
        return self.kv[key]


def test_clusterz_payload_injected_world_flags_straggler(monkeypatch):
    ch = _DictChannel()
    # peers 1 (healthy) and 2 (slow) already published; rank 3 is dead
    # and never will; rank 0 (this process, no steps yet) publishes its
    # own cold row on the way in
    for r, ms in ((1, 10.0), (2, 120.0)):
        ch.set(f"ptpu/cluster/metrics/{r}", json.dumps(_snap(r, ms)))
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
    payload = cluster.clusterz_payload(timeout_s=0.3, channel=ch)
    assert payload["world"] == 4
    assert [r["rank"] for r in payload["ranks"]] == [0, 1, 2]
    assert payload["missing_ranks"] == [3]  # a dead peer is evidence
    # median over reporting ranks {10, 120} = 65; 120 > 1.5*65
    assert [s["rank"] for s in payload["stragglers"]] == [2]
    # the verdict landed in the flight recorder for the post-mortem
    evs = [e for e in monitor.flight_recorder.events()
           if e["kind"] == "straggler_verdict"]
    assert evs and evs[-1]["stragglers"] == [2]
    assert evs[-1]["missing_ranks"] == [3]
    # rank 0 published its own snapshot on the way in
    assert "ptpu/cluster/metrics/0" in ch.kv


def test_cluster_publisher_thread_publishes():
    ch = _DictChannel()
    pub = cluster.ClusterPublisher(0.05, channel=ch).start()
    try:
        deadline = 50
        while not ch.kv and deadline:
            import time

            time.sleep(0.02)
            deadline -= 1
        assert ch.kv, "publisher never published"
    finally:
        pub.stop()
    assert pub.published >= 1 and not pub.alive


# -- debug endpoints ---------------------------------------------------------

def test_debug_server_costz_clusterz_and_metrics_content_type():
    from paddle_tpu.monitor.debug_server import DebugServer

    _tiny_static_loop(steps=2)
    srv = DebugServer(port=0).start()
    try:
        costz = json.loads(urlopen(srv.url + "/costz").read())
        assert any(r["label"] == "executor" for r in costz["records"])
        assert costz["device_peaks"]["flops"] > 0
        clusterz = json.loads(urlopen(srv.url + "/clusterz").read())
        assert len(clusterz["ranks"]) == 1
        resp = urlopen(srv.url + "/metrics")
        assert resp.headers.get("Content-Type", "").startswith(
            "text/plain; version=0.0.4")
        assert "cost_executed_flops" in resp.read().decode()
        # the index advertises the new routes
        index = urlopen(srv.url + "/").read().decode()
        assert "/costz" in index and "/clusterz" in index
    finally:
        srv.stop()

"""Fault diagnosis: flight recorder, hang watchdog, desync detection,
debug endpoint, NaN-action flag, PS dead-peer barrier release, and the
prometheus HELP/collision hardening.

The multi-process end-to-end desync run (2 real ranks, skipped
all_reduce) lives in tests/test_dist_multiprocess.py; here the same
machinery is covered in-process with injectable channels/recorders.
"""
import json
import os
import signal
import socket
import threading
import time
from urllib.error import HTTPError
from urllib.request import urlopen

import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu import monitor, ops, profiler
from paddle_tpu.flags import flag, set_flags
from paddle_tpu.monitor import debug_server as dbg
from paddle_tpu.monitor import flight_recorder as fr


# -- ring buffer --------------------------------------------------------------


def test_ring_buffer_eviction_and_indices():
    rec = fr.FlightRecorder(capacity=8)
    for i in range(20):
        rec.record("tick", n=i)
    evs = rec.events()
    assert len(evs) == 8
    # global indices are monotonic and survive eviction: the snapshot
    # says exactly how much history fell off the ring
    assert [e["i"] for e in evs] == list(range(12, 20))
    snap = rec.snapshot()
    assert snap["events_recorded"] == 20
    assert snap["dropped"] == 12


def test_record_collective_per_group_seq_and_fingerprint():
    rec = fr.FlightRecorder(capacity=32)
    assert rec.record_collective("all_reduce", "dp", shape=(4, 2),
                                 dtype="float32", reduce_op="sum") == 0
    assert rec.record_collective("all_gather", "dp", shape=(4,),
                                 dtype="float32") == 1
    # an independent group runs its own sequence
    assert rec.record_collective("alltoall", "ep", shape=(8,),
                                 dtype="bfloat16") == 0
    tails = rec.collective_tails()
    assert tails["dp"] == [(0, "all_reduce|(4, 2)|float32|sum"),
                           (1, "all_gather|(4,)|float32|")]
    assert tails["ep"] == [(0, "alltoall|(8,)|bfloat16|")]


def test_traced_collectives_do_not_consume_desync_seq():
    """Retraces are rank-asymmetric (one rank's jit-cache miss is
    another's hit): trace-time calls land in the event ring but must not
    touch the seq/tails the cross-rank comparison runs over."""
    rec = fr.FlightRecorder(capacity=32)
    assert rec.record_collective("all_reduce", "dp", shape=(4,),
                                 dtype="f32", traced=True) is None
    assert rec.record_collective("all_reduce", "dp", shape=(4,),
                                 dtype="f32", reduce_op="sum") == 0
    assert rec.record_collective("all_reduce", "dp", shape=(4,),
                                 dtype="f32", traced=True) is None
    assert rec.record_collective("all_gather", "dp", shape=(4,),
                                 dtype="f32") == 1
    tails = rec.collective_tails()
    assert [s for s, _ in tails["dp"]] == [0, 1]  # eager calls only
    traced_evs = [e for e in rec.events()
                  if e["kind"] == "collective" and e["traced"]]
    assert len(traced_evs) == 2 and all(e["seq"] is None
                                        for e in traced_evs)


def test_wait_is_rank_local_and_unsequenced():
    """dist.wait() is a local stream sync any single rank may call
    alone — it must land in the ring but never consume a desync seq."""
    import jax.numpy as jnp

    from paddle_tpu import distributed as dist
    from paddle_tpu.distributed import collective as coll

    fr.reset_recorder()
    x = jnp.ones((4,), jnp.float32)
    dist.all_reduce(x)
    coll.wait(x)
    dist.all_reduce(x)
    tails = fr.get_recorder().collective_tails()
    assert [(s, f.split("|")[0]) for s, f in tails["dp"]] == \
        [(0, "all_reduce"), (1, "all_reduce")]
    waits = [e for e in fr.events()
             if e["kind"] == "collective" and e["primitive"] == "wait"]
    assert waits and waits[0]["seq"] is None


def test_recorder_disabled_records_nothing():
    rec = fr.FlightRecorder(capacity=8)
    set_flags({"flight_recorder": False})
    try:
        assert rec.record("x") is None
        assert rec.record_collective("all_reduce", "dp") is None
        assert rec.events() == []
        assert rec.collective_tails() == {}
    finally:
        set_flags({"flight_recorder": True})


def test_dump_file_format(tmp_path):
    rec = fr.FlightRecorder(capacity=8)
    rec.record("hello", who="test")
    path = rec.dump(path=str(tmp_path / "d.json"), reason="unit")
    with open(path) as f:
        snap = json.load(f)
    assert snap["reason"] == "unit"
    assert snap["pid"] == os.getpid()
    assert snap["events"][0]["kind"] == "hello"
    assert snap["collective_tails"] == {}
    assert any("MainThread" in k for k in snap["threads"])
    assert "flight_recorder" in snap["flags"]
    # no half-written temp file left behind (atomic rename)
    assert [p.name for p in tmp_path.iterdir()] == ["d.json"]


def test_debug_dump_cli_reads_a_dump_back(tmp_path, capsys):
    """``tools/debug_dump.py`` on a dump the recorder wrote: the event
    listing, the ``--kind`` filter as JSON, and exit code 2 for a file
    that is no dump."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "debug_dump", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "debug_dump.py"))
    debug_dump = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(debug_dump)

    rec = fr.FlightRecorder(capacity=8)
    rec.record("executor_run_begin", program="p", jit_cache="miss")
    rec.record("executor_run_end", program="p", ok=True)
    path = rec.dump(path=str(tmp_path / "d.json"), reason="unit")
    assert debug_dump.main([path]) == 0
    out = capsys.readouterr().out
    assert "executor_run_begin" in out and "unit" in out
    assert debug_dump.main([path, "--kind", "executor_run_end",
                            "--json"]) == 0
    assert [e["kind"] for e in json.loads(capsys.readouterr().out)] \
        == ["executor_run_end"]
    assert debug_dump.main([path, "--threads"]) == 0
    assert "MainThread" in capsys.readouterr().out
    (tmp_path / "torn.json").write_text('{"events": [')
    assert debug_dump.main([str(tmp_path / "torn.json")]) == 2


def test_default_dump_path_uses_flag_dir(tmp_path):
    set_flags({"flight_recorder_dump_dir": str(tmp_path)})
    try:
        p = fr.default_dump_path()
        assert p.startswith(str(tmp_path))
        assert f"pid{os.getpid()}" in p
    finally:
        set_flags({"flight_recorder_dump_dir": ""})


def test_distinct_dump_reasons_never_clobber(tmp_path):
    """A barrier-failure dump carrying the desync report must survive
    the excepthook dump the re-raised error writes moments later: each
    trigger gets a reason-keyed file."""
    set_flags({"flight_recorder_dump_dir": str(tmp_path)})
    try:
        rec = fr.FlightRecorder(capacity=8)
        p1 = rec.dump(reason="ps_barrier_failed:tok",
                      desync={"divergences": [], "tag": "x"})
        p2 = rec.dump(reason="unhandled_exception:RuntimeError")
        assert p1 != p2
        with open(p1) as f:
            assert "desync" in json.load(f)  # evidence survived
        # same reason overwrites in place (bounded disk)
        assert rec.dump(reason="ps_barrier_failed:tok") == p1
    finally:
        set_flags({"flight_recorder_dump_dir": ""})


# -- subsystem wiring ---------------------------------------------------------


@pytest.fixture
def _static_env():
    static.reset_default_programs()
    static.global_scope().clear()
    static.enable_static()
    yield
    static.disable_static()
    static.reset_default_programs()
    static.global_scope().clear()


def _tiny_train(lr=0.05):
    x = static.data("x", [4, 8], "float32")
    w = static.nn.create_parameter([8, 1], "float32")
    loss = ops.mean(ops.square(ops.matmul(x, w)))
    opt = static.optimizer.Adam(learning_rate=lr)
    opt.minimize(loss)
    exe = static.Executor()
    exe.run_startup()
    return exe, loss, np.random.RandomState(0).randn(4, 8).astype("float32")


def test_executor_run_events_with_cache_disposition(_static_env):
    exe, loss, X = _tiny_train()
    fr.reset_recorder()
    exe.run(feed={"x": X}, fetch_list=[loss])
    exe.run(feed={"x": X}, fetch_list=[loss])
    begins = [e for e in fr.events() if e["kind"] == "executor_run_begin"]
    ends = [e for e in fr.events() if e["kind"] == "executor_run_end"]
    assert len(begins) == 2 and len(ends) == 2
    assert (begins[0]["plan_cache"], begins[0]["jit_cache"]) == \
        ("miss", "miss")
    assert (begins[1]["plan_cache"], begins[1]["jit_cache"]) == \
        ("hit", "hit")
    assert begins[0]["program"] == begins[1]["program"]
    assert all(e["ok"] for e in ends)
    # a completed run feeds the hang watchdog's progress clock
    assert fr.last_progress_what() == "executor_run"


def test_collective_calls_recorded_with_group_seq():
    import jax.numpy as jnp

    from paddle_tpu import distributed as dist

    fr.reset_recorder()
    dist.all_reduce(jnp.ones((4,), jnp.float32))
    dist.all_gather(None, jnp.ones((4,), jnp.float32))
    tails = fr.get_recorder().collective_tails()
    assert [s for s, _ in tails["dp"]] == [0, 1]
    assert tails["dp"][0][1] == "all_reduce|(4,)|float32|sum"
    assert tails["dp"][1][1].startswith("all_gather|(4,)|")
    assert fr.last_progress_what() == "collective:all_gather"


def test_flag_change_recorded():
    fr.reset_recorder()
    set_flags({"benchmark": True})
    try:
        evs = [e for e in fr.events() if e["kind"] == "flag_change"]
        assert evs and evs[-1]["flag"] == "benchmark"
        assert evs[-1]["value"] == "True"
    finally:
        set_flags({"benchmark": False})


def test_ps_rpc_send_recv_recorded():
    from paddle_tpu.distributed.ps.client import PSClient
    from paddle_tpu.distributed.ps.server import TableServer

    srv = TableServer().start()
    try:
        fr.reset_recorder()
        c = PSClient(srv.endpoint)
        c.create_table("t", 4)
        c.pull("t", [1, 2])
        kinds = [(e["kind"], e["op"]) for e in fr.events()
                 if e["kind"].startswith("ps_rpc")]
        assert ("ps_rpc_send", "pull") in kinds
        assert ("ps_rpc_recv", "pull") in kinds
        recvs = [e for e in fr.events() if e["kind"] == "ps_rpc_recv"]
        assert all(e["ok"] for e in recvs)
        assert fr.last_progress_what() == "ps_rpc:pull"
        c.close()
    finally:
        srv.stop()


def test_dataloader_lifecycle_events():
    from paddle_tpu.io import DataLoader
    from paddle_tpu.io.dataset import Dataset

    class Tiny(Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            return np.float32(i)

    fr.reset_recorder()
    loader = DataLoader(Tiny(), batch_size=4, use_buffer_reader=False)
    list(iter(loader))
    kinds = [e["kind"] for e in fr.events()]
    assert "dataloader_epoch" in kinds


# -- hang watchdog ------------------------------------------------------------


def test_watchdog_trips_dumps_and_rearms(tmp_path):
    set_flags({"flight_recorder_dump_dir": str(tmp_path)})
    rec = fr.FlightRecorder(capacity=64)
    wd = fr.HangWatchdog(0.25, recorder=rec, poll_interval=0.05,
                         desync=False)
    try:
        fr.notify_progress("arm")
        wd.start()
        deadline = time.time() + 10
        while wd.trips == 0 and time.time() < deadline:
            time.sleep(0.05)
    finally:
        wd.stop()
        set_flags({"flight_recorder_dump_dir": ""})
    assert wd.trips >= 1
    with open(wd.last_dump) as f:
        dump = json.load(f)
    assert dump["reason"].startswith("watchdog_timeout")
    trip = [e for e in dump["events"] if e["kind"] == "watchdog_trip"]
    assert trip and trip[0]["timeout_s"] == 0.25
    assert dump["threads"], "trip dump must include all thread stacks"


def test_watchdog_progress_prevents_trip():
    rec = fr.FlightRecorder(capacity=16)
    wd = fr.HangWatchdog(0.5, recorder=rec, poll_interval=0.05,
                         desync=False)
    fr.notify_progress("arm")
    wd.start()
    try:
        t_end = time.time() + 1.2
        while time.time() < t_end:
            fr.notify_progress("busy")
            time.sleep(0.04)
    finally:
        wd.stop()
    assert wd.trips == 0


def test_start_watchdog_flag_gate():
    fr.stop_watchdog()
    assert fr.start_watchdog() is None  # FLAGS_watchdog_timeout_s == 0
    set_flags({"watchdog_timeout_s": 30.0})
    try:
        wd = fr.start_watchdog()
        assert wd is not None and wd.alive
        assert fr.start_watchdog() is wd  # idempotent
        assert fr.watchdog() is wd
    finally:
        set_flags({"watchdog_timeout_s": 0.0})
        fr.stop_watchdog()


# -- crash / signal triggers --------------------------------------------------


def test_excepthook_dump_and_chain(tmp_path, monkeypatch):
    import sys

    seen = []
    monkeypatch.setattr(sys, "excepthook", lambda *a: seen.append(a))
    monkeypatch.setitem(fr._installed, "excepthook", False)
    set_flags({"flight_recorder_dump_dir": str(tmp_path)})
    try:
        fr.install(excepthook=True, sig=False)
        try:
            raise RuntimeError("boom")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
    finally:
        set_flags({"flight_recorder_dump_dir": ""})
    assert seen and seen[0][0] is RuntimeError  # previous hook still ran
    dumps = list(tmp_path.glob("paddle_tpu_flight_*.json"))
    assert dumps
    with open(dumps[0]) as f:
        snap = json.load(f)
    assert snap["reason"] == "unhandled_exception:RuntimeError"
    assert any(e["kind"] == "unhandled_exception" and e["message"] == "boom"
               for e in snap["events"])


def test_sigusr1_dump(tmp_path):
    if not hasattr(signal, "SIGUSR1"):
        pytest.skip("no SIGUSR1 on this platform")
    prev = signal.getsignal(signal.SIGUSR1)
    fr._installed["signal"] = False
    set_flags({"flight_recorder_dump_dir": str(tmp_path)})
    try:
        installed = fr.install(excepthook=False, sig=True)
        if not installed["signal"]:
            pytest.skip("not the main thread")
        os.kill(os.getpid(), signal.SIGUSR1)
        time.sleep(0.05)  # handler runs at the next bytecode boundary
        dumps = list(tmp_path.glob("paddle_tpu_flight_*.json"))
        assert dumps
        with open(dumps[0]) as f:
            assert json.load(f)["reason"] == "SIGUSR1"
    finally:
        set_flags({"flight_recorder_dump_dir": ""})
        signal.signal(signal.SIGUSR1, prev)
        fr._installed["signal"] = False


# -- desync detection ---------------------------------------------------------


def _tails(*pairs):
    return {"dp": [list(p) for p in pairs]}


def test_first_divergence_in_sync_is_empty():
    t = _tails((0, "all_reduce|(4,)|f32|sum"), (1, "all_gather|(4,)|f32|"))
    assert fr.first_divergence({0: t, 1: t}) == []


def test_first_divergence_names_skipped_collective():
    r0 = _tails((0, "all_reduce|(4,)|f32|sum"),
                (1, "all_reduce|(4,)|f32|sum"),
                (2, "all_gather|(4,)|f32|"))
    r1 = _tails((0, "all_reduce|(4,)|f32|sum"),
                (1, "all_gather|(4,)|f32|"))
    divs = fr.first_divergence({0: r0, 1: r1})
    assert len(divs) == 1
    d = divs[0]
    assert (d["group"], d["seq"]) == ("dp", 1)
    assert d["fingerprints"]["0"] == "all_reduce|(4,)|f32|sum"
    assert d["fingerprints"]["1"] == "all_gather|(4,)|f32|"
    assert "seq 1" in d["summary"]


def test_first_divergence_call_count_mismatch():
    r0 = _tails((0, "all_reduce|a"), (1, "all_reduce|a"),
                (2, "all_reduce|a"))
    r1 = _tails((0, "all_reduce|a"), (1, "all_reduce|a"))
    divs = fr.first_divergence({0: r0, 1: r1})
    assert len(divs) == 1
    d = divs[0]
    assert d["seq"] == 2
    assert d["fingerprints"]["1"] is None
    assert "call-count mismatch" in d["note"]


def test_first_divergence_window_intersection():
    """A seq evicted from one rank's bounded tail is not evidence: the
    comparison starts at the latest tail start across ranks."""
    r0 = _tails((5, "B"), (6, "C"))          # rank 0's ring evicted 0-4
    r1 = _tails((0, "A"), (5, "B"), (6, "C"))
    assert fr.first_divergence({0: r0, 1: r1}) == []


class _DictChannel:
    """In-process KV side-channel fake (the jax.distributed client's
    key_value_set / blocking_key_value_get surface)."""

    def __init__(self):
        self.store = {}

    def set(self, key, value):
        self.store[key] = value

    def get(self, key, timeout_s):
        if key not in self.store:
            raise TimeoutError(key)
        return self.store[key]


def test_exchange_and_diagnose_over_fake_channel():
    rec = fr.FlightRecorder(capacity=32)
    rec.record_collective("all_reduce", "dp", shape=(4,), dtype="f32",
                          reduce_op="sum")
    rec.record_collective("all_reduce", "dp", shape=(4,), dtype="f32",
                          reduce_op="sum")
    ch = _DictChannel()
    peer_tails = {"dp": [[0, "all_reduce|(4,)|f32|sum"],
                         [1, "all_gather|(4,)|f32|"]]}
    ch.set("ptpu/flight/t1/1", json.dumps(peer_tails))
    report = fr.exchange_and_diagnose(tag="t1", timeout_s=0.1, channel=ch,
                                      rank=0, world=2, recorder=rec)
    assert report["missing_ranks"] == []
    assert len(report["divergences"]) == 1
    d = report["divergences"][0]
    assert d["seq"] == 1
    assert d["fingerprints"]["0"] == "all_reduce|(4,)|f32|sum"
    assert d["fingerprints"]["1"] == "all_gather|(4,)|f32|"
    # this rank's tail was published for the peers
    assert "ptpu/flight/t1/0" in ch.store


def test_exchange_reports_missing_ranks():
    rec = fr.FlightRecorder(capacity=8)
    rec.record_collective("all_reduce", "dp")
    ch = _DictChannel()
    report = fr.exchange_and_diagnose(tag="t2", timeout_s=0.01, channel=ch,
                                      rank=0, world=3, recorder=rec)
    assert report["missing_ranks"] == [1, 2]  # dead peers ARE evidence


def test_exchange_single_process_is_none():
    assert fr.exchange_and_diagnose(rank=0, world=1) is None


def test_exchange_shares_one_deadline_across_missing_ranks():
    """A hung fleet must not pay timeout_s PER missing rank: the whole
    exchange shares one deadline, so the watchdog's dump is not held
    hostage for world * timeout_s."""
    rec = fr.FlightRecorder(capacity=8)
    rec.record_collective("all_reduce", "dp")

    class _SlowChannel(_DictChannel):
        def get(self, key, timeout_s):
            if key not in self.store:
                time.sleep(timeout_s)  # honest blocking get
                raise TimeoutError(key)
            return self.store[key]

    t0 = time.monotonic()
    report = fr.exchange_and_diagnose(tag="t3", timeout_s=0.4,
                                      channel=_SlowChannel(), rank=0,
                                      world=8, recorder=rec)
    elapsed = time.monotonic() - t0
    assert report["missing_ranks"] == list(range(1, 8))
    assert elapsed < 0.4 * 3, f"exchange took {elapsed:.1f}s for world=8"


def test_exchange_dead_low_rank_does_not_starve_available_peers():
    """Rank 0 dead before publishing must not eat the whole deadline:
    higher ranks' already-published tails still get read (the quick
    first-pass sweep), so the diagnosis survives the dead rank."""
    rec = fr.FlightRecorder(capacity=8)
    rec.record_collective("all_reduce", "dp", shape=(4,), dtype="f32",
                          reduce_op="sum")

    class _SlowChannel(_DictChannel):
        def get(self, key, timeout_s):
            if key not in self.store:
                time.sleep(timeout_s)
                raise TimeoutError(key)
            return self.store[key]

    ch = _SlowChannel()
    for r in (1, 2):
        ch.set(f"ptpu/flight/t5/{r}",
               json.dumps({"dp": [[0, "all_gather|(4,)|f32|"]]}))
    report = fr.exchange_and_diagnose(tag="t5", timeout_s=0.6, channel=ch,
                                      rank=3, world=4, recorder=rec)
    assert report["missing_ranks"] == [0]
    assert set(report["tails_by_rank"]) == {"1", "2", "3"}
    assert report["divergences"], "available peers' evidence was lost"


def test_exchange_survives_publish_failure():
    """Write-once KV stores (retried tag) must not kill the diagnosis:
    peers' already-published tails still get read."""
    rec = fr.FlightRecorder(capacity=8)
    rec.record_collective("all_reduce", "dp", shape=(4,), dtype="f32",
                          reduce_op="sum")

    class _WriteOnce(_DictChannel):
        def set(self, key, value):
            raise RuntimeError("ALREADY_EXISTS")

    ch = _WriteOnce()
    ch.store["ptpu/flight/t4/1"] = json.dumps(
        {"dp": [[0, "all_gather|(4,)|f32|"]]})
    report = fr.exchange_and_diagnose(tag="t4", timeout_s=0.1, channel=ch,
                                      rank=0, world=2, recorder=rec)
    # rank 0's own get fails (publish failed) but rank 1's tail arrived
    assert report["missing_ranks"] == [0]
    assert "1" in report["tails_by_rank"]
    assert any(e["kind"] == "desync_publish_failed" for e in rec.events())


# -- debug endpoint -----------------------------------------------------------


def test_debug_server_endpoints():
    fr.reset_recorder()
    fr.record_event("probe", n=7)
    monitor.counter("dbgz/c").inc(3)
    srv = dbg.DebugServer(port=0).start()
    try:
        health = json.loads(urlopen(srv.url + "/healthz").read())
        assert health["ok"] is True
        assert health["pid"] == os.getpid()
        assert "last_progress_age_s" in health
        assert health["flight_recorder"]["enabled"] is True

        snap = json.loads(urlopen(srv.url + "/flightrecorder").read())
        assert any(e["kind"] == "probe" for e in snap["events"])
        assert snap["reason"] == "debugz"

        text = urlopen(srv.url + "/metrics").read().decode()
        assert "dbgz_c 3" in text

        threadz = urlopen(srv.url + "/threadz").read().decode()
        assert "MainThread" in threadz

        flagz = json.loads(urlopen(srv.url + "/flagz").read())
        assert "debug_port" in flagz and "watchdog_timeout_s" in flagz

        index = urlopen(srv.url + "/").read().decode()
        assert "/healthz" in index

        with pytest.raises(HTTPError) as ei:
            urlopen(srv.url + "/nope")
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_start_debug_server_flag_gate():
    # FLAGS_debug_port defaults to 0: disabled
    assert flag("debug_port") == 0
    assert dbg.start_debug_server() is None
    assert dbg.debug_server() is None


# -- FLAGS_check_nan_inf_action ----------------------------------------------


def _nan_program():
    x = static.data("x", [3], "float32")
    y = ops.log(x)  # log of a negative input → nan
    z = ops.add(y, ops.full([3], 1.0))
    return z, np.array([-1.0, 1.0, 2.0], np.float32)


def test_nan_action_warn_continues_and_counts(_static_env):
    z, X = _nan_program()
    set_flags({"check_nan_inf": True, "check_nan_inf_action": "warn"})
    exe = static.Executor()
    try:
        with pytest.warns(RuntimeWarning, match="check_nan_inf"):
            out = exe.run(feed={"x": X}, fetch_list=[z])
        assert np.isnan(np.asarray(out[0])).any()  # run completed
        assert monitor.counter("debug/nan_events").value == 1
        assert any(e["kind"] == "nan_inf" and e["action"] == "warn"
                   for e in fr.events())
    finally:
        set_flags({"check_nan_inf": False, "check_nan_inf_action": "raise"})


def test_nan_action_dump_writes_snapshot_then_raises(_static_env, tmp_path):
    from paddle_tpu import errors

    z, X = _nan_program()
    set_flags({"check_nan_inf": True, "check_nan_inf_action": "dump",
               "flight_recorder_dump_dir": str(tmp_path)})
    exe = static.Executor()
    try:
        with pytest.raises(errors.FatalError, match="check_nan_inf"):
            exe.run(feed={"x": X}, fetch_list=[z])
        dumps = list(tmp_path.glob("paddle_tpu_flight_*.json"))
        assert dumps
        with open(dumps[0]) as f:
            snap = json.load(f)
        assert snap["reason"].startswith("check_nan_inf:")
    finally:
        set_flags({"check_nan_inf": False, "check_nan_inf_action": "raise",
                   "flight_recorder_dump_dir": ""})


def test_nan_action_invalid_value_is_loud(_static_env):
    from paddle_tpu import errors

    z, X = _nan_program()
    set_flags({"check_nan_inf": True, "check_nan_inf_action": "explode"})
    exe = static.Executor()
    try:
        with pytest.raises(errors.InvalidArgumentError,
                           match="raise|warn|dump"):
            exe.run(feed={"x": X}, fetch_list=[z])
    finally:
        set_flags({"check_nan_inf": False, "check_nan_inf_action": "raise"})


def test_nan_action_warn_in_compiled_train_step():
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu.framework import jit as fjit

    m = nn.Linear(4, 2)
    o = opt.SGD(learning_rate=0.1, parameters=m.parameters())

    def loss_fn(mm, x):
        out = mm(x)
        return (ops.log(out.sum() - out.sum() - 1.0)).mean()  # log(-1)

    paddle.set_flags({"check_nan_inf": True,
                      "check_nan_inf_action": "warn"})
    try:
        step = fjit.train_step(m, o, loss_fn)
        with pytest.warns(RuntimeWarning, match="check_nan_inf"):
            metrics = step(np.ones((4, 4), np.float32))
        assert np.isnan(float(np.asarray(metrics["loss"])))
        assert monitor.counter("debug/nan_events").value >= 1
    finally:
        paddle.set_flags({"check_nan_inf": False,
                          "check_nan_inf_action": "raise"})


# -- PS dead-peer barrier release --------------------------------------------


def test_ps_dead_peer_releases_barrier(tmp_path):
    from paddle_tpu.distributed.ps.client import PSClient
    from paddle_tpu.distributed.ps.server import (
        TableServer, _recv_msg, _send_msg)

    set_flags({"flight_recorder_dump_dir": str(tmp_path)})
    srv = TableServer(barrier_timeout=60.0).start()
    result = {}
    try:
        c1 = PSClient(srv.endpoint)
        host, port = srv.endpoint.rsplit(":", 1)
        # the soon-to-die peer becomes a FENCE PARTICIPANT first (only
        # fence participants release fences when they die): raw socket so
        # we can feed it garbage afterwards
        s = socket.create_connection((host, int(port)), timeout=10)
        t0 = threading.Thread(
            target=lambda: c1.barrier("warmup", 2, timeout=30.0),
            daemon=True)
        t0.start()
        _send_msg(s, ("barrier", "warmup", 2))
        assert _recv_msg(s)[0] == "ok"
        t0.join(10)

        def waiter():
            try:
                c1.barrier("fence", 2, timeout=30.0)
                result["err"] = None
            except Exception as e:
                result["err"] = e

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        time.sleep(0.3)  # let the fence park

        s.sendall(b"X" * 16)  # garbage: the participant's conn thread dies
        s.close()

        t.join(15)
        assert not t.is_alive(), "waiter stranded despite dead peer"
        err = result["err"]
        assert isinstance(err, RuntimeError)
        msg = str(err)
        assert "fence" in msg and "connection died" in msg
        assert "127.0.0.1" in msg  # the dead peer is NAMED
        c1.close()
    finally:
        srv.stop()
        set_flags({"flight_recorder_dump_dir": ""})


def test_ps_non_participant_abnormal_death_aborts_nothing():
    """A protocol-valid client that never joined a fence (stats probe)
    dying ABNORMALLY must not abort a live training sync."""
    from paddle_tpu.distributed.ps.client import PSClient
    from paddle_tpu.distributed.ps.server import (
        TableServer, _recv_msg, _send_msg)

    srv = TableServer(barrier_timeout=60.0).start()
    try:
        c1 = PSClient(srv.endpoint)
        result = {}

        def waiter():
            try:
                c1.barrier("fence4", 2, timeout=30.0)
                result["err"] = None
            except Exception as e:
                result["err"] = e

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        time.sleep(0.2)

        host, port = srv.endpoint.rsplit(":", 1)
        s = socket.create_connection((host, int(port)), timeout=10)
        _send_msg(s, ("stats",))
        _recv_msg(s)          # protocol peer, but never barriered
        s.sendall(b"X" * 16)  # dies abnormally
        s.close()
        time.sleep(0.3)
        assert t.is_alive(), "probe death aborted a live fence"

        c2 = PSClient(srv.endpoint)
        c2.barrier("fence4", 2, timeout=30.0)
        t.join(10)
        assert result["err"] is None
        c1.close()
        c2.close()
    finally:
        srv.stop()


def test_ps_killed_fence_participant_eof_releases_barrier():
    """A SIGKILLed worker produces a CLEAN EOF, not a decode error: if
    that worker had joined a fence before, its disconnect must release
    the waiters too (the common crash mode, not just wire garbage)."""
    from paddle_tpu.distributed.ps.client import PSClient
    from paddle_tpu.distributed.ps.server import TableServer

    srv = TableServer(barrier_timeout=60.0).start()
    try:
        c1 = PSClient(srv.endpoint)
        c2 = PSClient(srv.endpoint)
        # both parties complete one fence: c2 is now a fence participant
        t0 = threading.Thread(
            target=lambda: c1.barrier("warmup", 2, timeout=30.0),
            daemon=True)
        t0.start()
        c2.barrier("warmup", 2, timeout=30.0)
        t0.join(10)

        result = {}

        def waiter():
            try:
                c1.barrier("fence3", 2, timeout=30.0)
                result["err"] = None
            except Exception as e:
                result["err"] = e

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        time.sleep(0.3)
        c2._sock.close()  # SIGKILL equivalent: clean EOF on the server

        t.join(15)
        assert not t.is_alive(), "waiter stranded after participant EOF"
        err = result["err"]
        assert isinstance(err, RuntimeError)
        assert "fence3" in str(err) and "disconnected" in str(err)
        c1.close()
    finally:
        srv.stop()


def test_ps_garbage_from_stranger_aborts_nothing():
    """A connection that never spoke the protocol (port scanner) dying
    must NOT abort a live fence."""
    from paddle_tpu.distributed.ps.client import PSClient
    from paddle_tpu.distributed.ps.server import TableServer

    srv = TableServer(barrier_timeout=60.0).start()
    try:
        c1 = PSClient(srv.endpoint)
        result = {}

        def waiter():
            try:
                # second party arrives below → fence completes normally
                c1.barrier("fence2", 2, timeout=30.0)
                result["err"] = None
            except Exception as e:
                result["err"] = e

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        time.sleep(0.2)

        host, port = srv.endpoint.rsplit(":", 1)
        s = socket.create_connection((host, int(port)), timeout=10)
        s.sendall(b"NOT-THE-PROTOCOL")  # stranger dies without one valid msg
        s.close()
        time.sleep(0.3)
        assert t.is_alive(), "stranger's garbage aborted a live fence"

        c2 = PSClient(srv.endpoint)
        c2.barrier("fence2", 2, timeout=30.0)
        t.join(10)
        assert result["err"] is None
        c1.close()
        c2.close()
    finally:
        srv.stop()


# -- launcher fault-diagnosis wiring -----------------------------------------


def test_launch_procs_injects_diagnosis_flags(monkeypatch):
    import subprocess

    from paddle_tpu.distributed import launch

    captured = []

    class _FakeProc:
        def __init__(self, argv, env=None):
            captured.append(env)

    monkeypatch.setattr(subprocess, "Popen",
                        lambda argv, env=None: _FakeProc(argv, env))
    launch.launch_procs(["train.py"], nproc=2, debug_port=8080,
                        watchdog_timeout=120.0)
    assert len(captured) == 2
    for rank, env in enumerate(captured):
        # every rank gets the BASE port; install_from_flags adds +rank
        assert env["FLAGS_debug_port"] == "8080"
        assert env["FLAGS_watchdog_timeout_s"] == "120.0"
        assert env["PADDLE_TRAINER_ID"] == str(rank)
    # defaults leave the environment untouched
    captured.clear()
    launch.launch_procs(["train.py"], nproc=1)
    assert "FLAGS_debug_port" not in captured[0]
    assert "FLAGS_watchdog_timeout_s" not in captured[0]


# -- prometheus HELP + collision hardening ------------------------------------


def test_prometheus_help_lines_escaped():
    monitor.counter("helpme/c", help="line1\nline2 with \\ backslash").inc()
    text = monitor.prometheus_text()
    assert "# HELP helpme_c line1\\nline2 with \\\\ backslash" in text
    # the help text never splits into a bogus sample line
    for line in text.splitlines():
        if not line.startswith("#"):
            assert "line2" not in line


def test_prometheus_no_help_line_without_help():
    monitor.counter("nohelp/c").inc()
    text = monitor.prometheus_text()
    assert "# HELP nohelp_c" not in text
    assert "nohelp_c 1" in text


def test_prometheus_name_collision_is_an_error():
    monitor.counter("col/a").inc()
    monitor.counter("col:a").inc()  # both sanitize to col_a
    with pytest.raises(ValueError, match="collision.*col_a"):
        monitor.prometheus_text()


def test_prometheus_registry_vs_profiler_collision():
    monitor.counter("exec/x").inc()
    profiler.bump_counter("exec::x")  # sanitizes to exec__x... not a clash
    monitor.prometheus_text()  # distinct names: fine
    profiler.bump_counter("exec/x ")  # "exec/x " → exec_x_ ; still fine
    monitor.prometheus_text()
    profiler.bump_counter("exec:x")  # exec_x == registry exec/x → clash
    with pytest.raises(ValueError, match="collision"):
        monitor.prometheus_text()


def test_prometheus_identical_raw_name_in_both_sources_is_an_error():
    """The SAME raw name in the registry and the profiler counters would
    emit two '# TYPE' blocks for one family — just as fatal to a scraper
    as a sanitization clash, and caught the same way."""
    monitor.counter("dup/name").inc()
    profiler.bump_counter("dup/name")
    with pytest.raises(ValueError, match="collision"):
        monitor.prometheus_text()

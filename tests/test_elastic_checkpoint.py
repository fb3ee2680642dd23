"""Preemption-tolerant elastic training (ROADMAP item 5).

Covers distributed/checkpoint.py (async crash-consistent snapshots,
manifest + checksums, reshard-on-resume across mesh sizes),
distributed/chaos.py (FLAGS_fault_injection), and the elastic layer
(heartbeat grace, straggler eviction, world renegotiation,
elastic_run's world-change handling). The multi-process 2→1→2 e2e
lives in test_dist_multiprocess.py.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu import parallel
from paddle_tpu.distributed import chaos
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.distributed.elastic import (
    ElasticContext,
    EvictedError,
    HeartbeatMonitor,
    StragglerTracker,
    WorldChangedError,
    check_world,
    elastic_run,
    evicted_ranks,
    install_straggler_eviction,
    renegotiate_world,
)
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.framework import jit as fjit
from paddle_tpu.parallel.sharding import spec_from_wire, spec_to_wire


@pytest.fixture
def flagged():
    """set_flags with automatic restore."""
    saved = {}

    def _set(**kw):
        for k in kw:
            saved.setdefault(k, get_flags(k)[k])
        set_flags(kw)

    yield _set
    if saved:
        set_flags(saved)
    chaos.reset()


class MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(16, 32)
        self.fc2 = nn.Linear(32, 4)

    def forward(self, x):
        return self.fc2(F.relu(self.fc1(x)))


def _loss_fn(m, x, y):
    return F.cross_entropy(m(x), y).mean()


def _data(n_steps, batch=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n_steps, batch, 16).astype("float32")
    Y = rng.randint(0, 4, (n_steps, batch)).astype("int64")
    return X, Y


def _plain_step(seed=7):
    paddle.seed(seed)
    m = MLP()
    o = opt.Adam(learning_rate=0.01, parameters=m.parameters())
    return fjit.train_step(m, o, _loss_fn)


def _sharded_step(dp, seed=7, zero1=True):
    paddle.seed(seed)
    m = MLP()
    o = opt.Adam(learning_rate=0.01, parameters=m.parameters())
    mesh = parallel.create_mesh(dp=dp)
    return parallel.sharded_train_step(m, o, _loss_fn, mesh, zero1=zero1)


# -- spec wire format -------------------------------------------------------


def test_spec_wire_roundtrip():
    for spec in (P(), P("dp"), P(None, "tp"), P(("dp", "tp"), None),
                 P("dp", None, "tp")):
        wire = spec_to_wire(spec)
        import json

        json.dumps(wire)  # must be JSON-serializable
        assert tuple(spec_from_wire(wire)) == tuple(spec)
    assert tuple(spec_from_wire(None)) == ()
    assert tuple(spec_from_wire([])) == ()


# -- chaos injection --------------------------------------------------------


def test_chaos_parse():
    d = chaos.parse("kill:point=step,step=3,rank=1;"
                    "delay:point=step,step=2,ms=250;"
                    "raise:point=mid_save,n=2")
    assert [x["action"] for x in d] == ["kill", "delay", "raise"]
    assert d[0] == {"action": "kill", "point": "step", "step": 3, "rank": 1}
    assert d[1]["ms"] == 250.0
    assert d[2]["n"] == 2
    assert chaos.parse("") == []

    from paddle_tpu.errors import InvalidArgumentError

    with pytest.raises(InvalidArgumentError):
        chaos.parse("explode:point=step")
    with pytest.raises(InvalidArgumentError):
        chaos.parse("kill:step=3")  # no point
    with pytest.raises(InvalidArgumentError):
        chaos.parse("kill:point=step,step=abc")


def test_chaos_delay_and_raise(flagged):
    flagged(fault_injection="delay:point=step,step=1,ms=80")
    chaos.reset()
    t0 = time.perf_counter()
    chaos.inject("step", step=0)
    assert time.perf_counter() - t0 < 0.05  # no match, no sleep
    chaos.inject("step", step=1)
    assert time.perf_counter() - t0 >= 0.08
    chaos.inject("step", step=1)  # fires at most once per process
    assert time.perf_counter() - t0 < 0.2

    flagged(fault_injection="raise:point=mid_save,n=2")
    chaos.reset()
    chaos.inject("mid_save")  # 1st occurrence: no-op
    with pytest.raises(chaos.ChaosInjected):
        chaos.inject("mid_save")  # 2nd: fires

    flagged(fault_injection="")
    chaos.reset()
    chaos.inject("step", step=1)  # disabled: pure no-op


def test_chaos_rank_filter(flagged):
    flagged(fault_injection="raise:point=step,step=0,rank=3")
    chaos.reset()
    chaos.inject("step", step=0, rank=1)  # not our directive
    with pytest.raises(chaos.ChaosInjected):
        chaos.inject("step", step=0, rank=3)


# -- checkpoint: save/load/rotation/corruption ------------------------------


def test_checkpoint_roundtrip_plain_step(tmp_path):
    X, Y = _data(4)
    step = _plain_step()
    ref_losses = [float(np.asarray(step(X[s], Y[s])["loss"]))
                  for s in range(4)]

    step2 = _plain_step()
    for s in range(2):
        step2(X[s], Y[s])
    path = str(tmp_path / "step_1")
    assert step2.save_checkpoint(path, step=1, async_=False) is None
    manifest = ckpt.validate(path)
    assert manifest["step"] == 1
    assert manifest["files"]  # checksummed files listed
    # entries carry global shape/dtype/spec metadata for every leaf
    entry_names = list(manifest["entries"])
    assert any("fc1.weight" in n for n in entry_names)
    for e in manifest["entries"].values():
        assert "shape" in e and "dtype" in e and "spec" in e

    # fresh process: new objects, different init — restore overwrites
    step3 = _plain_step(seed=123)
    got = step3.load_checkpoint(path)
    assert got["step"] == 1
    resumed = [float(np.asarray(step3(X[s], Y[s])["loss"]))
               for s in range(2, 4)]
    np.testing.assert_allclose(resumed, ref_losses[2:], rtol=1e-6)


def test_checkpoint_async_durability_and_rotation(tmp_path, flagged):
    flagged(checkpoint_async=True)
    X, Y = _data(4)
    step = _plain_step()
    pendings = []
    for s in range(4):
        step(X[s], Y[s])
        p = step.save_checkpoint(str(tmp_path / f"step_{s}"), step=s,
                                 keep=2)
        pendings.append(p)
    assert all(p is not None for p in pendings)  # async handles
    ckpt.wait_pending()
    kept = sorted(d for d in os.listdir(tmp_path))
    assert kept == ["step_2", "step_3"]  # rotation kept the newest 2
    path, manifest = ckpt.latest_checkpoint(str(tmp_path))
    assert path.endswith("step_3") and manifest["step"] == 3
    ckpt.validate(path)


def test_latest_skips_corrupt_and_manifestless(tmp_path):
    X, Y = _data(3)
    step = _plain_step()
    for s in range(3):
        step(X[s], Y[s])
        step.save_checkpoint(str(tmp_path / f"step_{s}"), step=s,
                             async_=False)
    # newest: flip bytes in its shard file -> checksum fails
    shard = tmp_path / "step_2" / "shard_r0.pdshard"
    data = bytearray(shard.read_bytes())
    data[len(data) // 2] ^= 0xFF
    shard.write_bytes(bytes(data))
    # second-newest: manifest-less (torn publish simulation)
    (tmp_path / "step_1" / ckpt.MANIFEST).unlink()

    path, manifest = ckpt.latest_checkpoint(str(tmp_path))
    assert path.endswith("step_0") and manifest["step"] == 0
    with pytest.raises(ckpt.CheckpointCorruptError):
        ckpt.validate(str(tmp_path / "step_2"))
    with pytest.raises(ckpt.CheckpointCorruptError):
        ckpt.load(str(tmp_path / "step_1"))
    # the corrupt snapshot loads from nothing — and a truncated file is
    # flagged too
    (tmp_path / "step_2" / "shard_r0.pdshard").write_bytes(b"")
    with pytest.raises(ckpt.CheckpointCorruptError):
        ckpt.load(str(tmp_path / "step_2"))


def test_sweep_tmp_removes_torn_saves(tmp_path):
    torn = tmp_path / "step_5.tmp"
    torn.mkdir()
    (torn / "shard_r0.pdshard").write_bytes(b"partial")
    keepme = tmp_path / "step_4"
    keepme.mkdir()
    removed = ckpt.sweep_tmp(str(tmp_path))
    assert removed == [str(torn)]
    assert not torn.exists() and keepme.exists()
    assert ckpt.sweep_tmp(str(tmp_path / "missing")) == []


def test_mid_save_crash_leaves_previous_intact(tmp_path, flagged):
    """A save failing between data files and manifest publication must
    leave a manifest-less .tmp — never a half-published snapshot — and
    resume must land on the previous intact one."""
    X, Y = _data(2)
    step = _plain_step()
    step(X[0], Y[0])
    step.save_checkpoint(str(tmp_path / "step_0"), step=0, async_=False)

    flagged(fault_injection="raise:point=mid_save,n=1")
    chaos.reset()
    step(X[1], Y[1])
    with pytest.raises(chaos.ChaosInjected):
        step.save_checkpoint(str(tmp_path / "step_1"), step=1,
                             async_=False)
    assert (tmp_path / "step_1.tmp").is_dir()
    assert not (tmp_path / "step_1").exists()

    path, manifest = ckpt.latest_checkpoint(str(tmp_path))
    assert path.endswith("step_0") and manifest["step"] == 0
    ckpt.sweep_tmp(str(tmp_path))
    assert not (tmp_path / "step_1.tmp").exists()


def _start_elastic_trainer(env, ckpt_dir, devices):
    """tests/fixtures/dist_elastic.py as a one-process world of
    ``devices`` virtual devices, six steps, a checkpoint after each."""
    from paddle_tpu.distributed.launch import _build_env, _free_port

    base = dict(env, ELASTIC_CKPT_DIR=ckpt_dir, ELASTIC_TOTAL_STEPS="6",
                XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(__file__), "fixtures",
                                      "dist_elastic.py")],
        env=_build_env(0, 1, f"127.0.0.1:{_free_port()}", base),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _trainer_result(proc):
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == 0, err[-3000:]
    return json.loads([ln for ln in out.splitlines()
                       if ln.startswith("{")][-1])


def test_kill9_inside_a_save_resumes_resharded_on_the_same_curve(tmp_path,
                                                                 child_env):
    """A REAL kill -9 between a snapshot's data files and its manifest:
    the torn ``.tmp`` never loads and is swept, the relaunch at twice
    the devices resumes from the newest intact snapshot with the ZeRO-1
    state re-sliced, and every recomputed step matches the uninterrupted
    run's loss."""
    ref_proc = _start_elastic_trainer(child_env(), str(tmp_path / "ref"), 4)
    chaos_dir = str(tmp_path / "chaos")
    victim = _start_elastic_trainer(
        child_env(FLAGS_fault_injection="kill:point=mid_save,n=3"),
        chaos_dir, 2)
    try:
        _, err = victim.communicate(timeout=120)
        assert victim.returncode == -9, err[-2000:]
        ref = _trainer_result(ref_proc)
    finally:
        for p in (ref_proc, victim):
            if p.poll() is None:
                p.kill()
    assert sorted(map(int, ref["losses"])) == list(range(6))
    assert ref["zero1_dp_sharded"]

    assert [d for d in os.listdir(chaos_dir) if d.endswith(".tmp")]
    path, manifest = ckpt.latest_checkpoint(chaos_dir)
    assert path is not None and manifest["step"] < 5
    assert manifest["mesh_shape"]["dp"] == 2

    out = _trainer_result(_start_elastic_trainer(child_env(), chaos_dir, 4))
    assert out["resumed_from"] == manifest["step"]
    assert out["reshards"] >= 1 and out["zero1_dp_sharded"]
    assert out["steps"] == list(range(manifest["step"] + 1, 6))
    assert not [d for d in os.listdir(chaos_dir) if d.endswith(".tmp")]
    for s, v in out["losses"].items():
        np.testing.assert_allclose(
            v, ref["losses"][s], rtol=5e-4, atol=1e-6,
            err_msg=f"step {s} diverged after kill -9 + reshard")


def test_async_save_error_surfaces_on_wait(tmp_path, flagged):
    flagged(fault_injection="raise:point=mid_save,n=1")
    chaos.reset()
    X, Y = _data(1)
    step = _plain_step()
    step(X[0], Y[0])
    p = step.save_checkpoint(str(tmp_path / "step_0"), step=0, async_=True)
    with pytest.raises(chaos.ChaosInjected):
        p.wait()
    # the failure is NOT dropped by a later submit: wait_pending still
    # reports it (raise_errors=False returns instead of raising), and a
    # second drain comes back clean
    step.save_checkpoint(str(tmp_path / "step_1"), step=1, async_=True)
    err = ckpt.wait_pending(raise_errors=False)
    assert isinstance(err, chaos.ChaosInjected)
    assert ckpt.wait_pending(raise_errors=False) is None
    assert ckpt.latest_checkpoint(str(tmp_path))[1]["step"] == 1


def test_async_save_error_reraises_at_drain(tmp_path, flagged):
    """An errored save must survive later submits and re-raise at the
    next raise_errors drain — a dropped snapshot never fails silently."""
    flagged(fault_injection="raise:point=mid_save,n=1")
    chaos.reset()
    X, Y = _data(1)
    step = _plain_step()
    step(X[0], Y[0])
    step.save_checkpoint(str(tmp_path / "step_0"), step=0, async_=True)
    step.save_checkpoint(str(tmp_path / "step_1"), step=1, async_=True)
    step.save_checkpoint(str(tmp_path / "step_2"), step=2, async_=True)
    with pytest.raises(chaos.ChaosInjected):
        ckpt.wait_pending()
    # the two later saves published fine and the queue is now clean
    assert ckpt.wait_pending() is None
    assert ckpt.latest_checkpoint(str(tmp_path))[1]["step"] == 2


# -- reshard on resume ------------------------------------------------------


def test_reshard_across_mesh_sizes(tmp_path):
    """A dp=4 ZeRO-1 checkpoint restores onto a dp=2 mesh (and back to
    the eager objects) with a loss-curve-identical continuation — the
    resume-at-new-world-size contract."""
    X, Y = _data(6)

    ref = _sharded_step(dp=4)
    ref_losses = [float(np.asarray(ref(X[s], Y[s])["loss"]))
                  for s in range(6)]

    big = _sharded_step(dp=4)
    for s in range(3):
        big(X[s], Y[s])
    path = str(tmp_path / "step_2")
    big.save_checkpoint(path, step=2, async_=False)
    manifest = ckpt.validate(path)
    assert manifest["mesh_shape"]["dp"] == 4
    # ZeRO-1: at least one optimizer-accumulator entry is recorded as
    # dp-sharded in the manifest (mesh-independent wire spec)
    accum_specs = [e["spec"] for n, e in manifest["entries"].items()
                   if "accums" in n]
    assert accum_specs and any("dp" in (s or []) for s in accum_specs)

    small = _sharded_step(dp=2, seed=99)  # different init, smaller world
    got = small.load_checkpoint(path)
    assert got["step"] == 2 and got["mesh_shape"]["dp"] == 4
    # the restored accumulators really live dp=2-sharded on device now
    accums = small.state["opt"]["accums"]
    name = sorted(accums)[0]
    sharded_dims = [
        p for p in accums[name][0].sharding.spec if p is not None]
    assert "dp" in sharded_dims
    resumed = [float(np.asarray(small(X[s], Y[s])["loss"]))
               for s in range(3, 6)]
    np.testing.assert_allclose(resumed, ref_losses[3:], rtol=1e-5,
                               atol=1e-6)

    # and the reassembled host globals match the big world's state
    flat, _ = ckpt.load(path)
    small.sync()
    w = next(v for k, v in flat.items() if "fc1.weight" in k)
    assert w.shape == (16, 32)


def test_restore_rejects_mismatched_state(tmp_path):
    X, Y = _data(1)
    step = _plain_step()
    step(X[0], Y[0])
    step.save_checkpoint(str(tmp_path / "step_0"), step=0, async_=False)

    class Tiny(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 2)

        def forward(self, x):
            return self.fc(x)

    paddle.seed(1)
    m = Tiny()
    o = opt.Adam(learning_rate=0.01, parameters=m.parameters())
    other = fjit.train_step(m, o, _loss_fn)
    with pytest.raises(ckpt.CheckpointError, match="does not match"):
        other.load_checkpoint(str(tmp_path / "step_0"))


# -- straggler eviction -----------------------------------------------------


def test_straggler_tracker_consecutive_threshold(flagged):
    flagged(eviction_threshold=3)
    t = StragglerTracker()
    for _ in range(2):
        t.observe([1], present=[0, 1, 2])
    assert t.evictable() == []           # streak 2 < threshold 3
    t.observe([], present=[0, 1, 2])     # clean tick resets
    assert t.streak(1) == 0
    for _ in range(3):
        t.observe([1], present=[0, 1, 2])
    assert t.evictable() == [1]
    # a rank missing from the report keeps its streak
    t.observe([2], present=[0, 2])
    assert t.streak(1) == 3 and t.streak(2) == 1
    t.reset(1)
    assert t.evictable() == []


def test_verdict_listener_feeds_tracker():
    from paddle_tpu.monitor import cluster

    t = StragglerTracker(threshold=2)
    handle = install_straggler_eviction(t)
    try:
        payload = {"stragglers": [{"rank": 1, "step_ms": 50.0}],
                   "ranks": [{"rank": 0}, {"rank": 1}]}
        for cb in list(cluster._VERDICT_LISTENERS):
            cb(payload)
            cb(payload)
        assert t.evictable() == [1]
        # the real endpoint path dispatches too (world=1: no stragglers,
        # present resets nothing it shouldn't)
        cluster.clusterz_payload(timeout_s=0.1)
        assert t.streak(1) == 2  # rank 1 absent from a 1-rank payload
    finally:
        cluster.remove_verdict_listener(handle)


def test_check_world_eviction_and_markers(tmp_path, flagged):
    flagged(eviction_threshold=2)
    job = str(tmp_path)
    m0 = HeartbeatMonitor(job, rank=0, world_size=3, interval=0.1,
                          timeout=30.0, grace=0.0)
    m1 = HeartbeatMonitor(job, rank=1, world_size=3, interval=0.1,
                          timeout=30.0, grace=30.0)
    m0.beat()
    m1.beat()
    m2 = HeartbeatMonitor(job, rank=2, world_size=3, interval=0.1,
                          timeout=30.0, grace=30.0)
    m2.beat()
    assert check_world(m0) == [0, 1, 2]  # everyone healthy

    tracker = StragglerTracker()
    tracker.observe([1], present=[0, 1, 2])
    assert check_world(m0, tracker) == [0, 1, 2]  # one verdict: noise
    tracker.observe([1], present=[0, 1, 2])
    with pytest.raises(WorldChangedError) as ei:
        check_world(m0, tracker)
    assert ei.value.survivors == [0, 2]
    assert ei.value.evicted == [1]
    assert evicted_ranks(job) == [1]  # decision persisted for everyone
    # the evicted rank's own check sees the marker and leaves
    with pytest.raises(EvictedError):
        check_world(m1, None)
    # survivors keep going with the shrunk membership: no further change
    assert check_world(m0, tracker, members=[0, 2]) == [0, 2]


def test_renegotiate_world_agreement(tmp_path):
    job = str(tmp_path)
    mons = {r: HeartbeatMonitor(job, rank=r, world_size=3, interval=0.1,
                                timeout=0.5, grace=0.0)
            for r in (0, 1)}
    for m in mons.values():
        m.beat()
    # rank 2 never joined; grace 0 => dead immediately
    results, errors = {}, {}

    def negotiate(r):
        try:
            results[r] = renegotiate_world(mons[r], generation=1,
                                           timeout=10.0)
        except Exception as e:  # pragma: no cover - surfaced below
            errors[r] = e

    threads = [threading.Thread(target=negotiate, args=(r,)) for r in mons]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=15)
    assert not errors, errors
    assert results[0].survivors == [0, 1] == results[1].survivors
    assert results[0].rank == 0 and results[1].rank == 1
    assert results[0].world_size == 2
    # an evicted rank renegotiating learns it must leave
    from paddle_tpu.distributed.elastic import mark_evicted

    mark_evicted(job, 1)
    with pytest.raises(EvictedError):
        renegotiate_world(mons[1], generation=2, timeout=2.0)


# -- elastic_run ------------------------------------------------------------


def test_elastic_run_world_change_does_not_burn_restarts():
    calls = []

    def train(ctx):
        calls.append(ctx.members if ctx.world is None
                     else ctx.world.survivors)
        if len(calls) == 1:
            raise WorldChangedError([0, 2], dead=[1])
        assert isinstance(ctx, ElasticContext)
        assert ctx.world is not None and ctx.world.survivors == [0, 2]
        assert ctx.world_changes == 1 and ctx.restarts == 0
        return "resized"

    # max_restarts=0: a crash would be fatal — the resize must not count
    assert elastic_run(train, max_restarts=0) == "resized"
    assert len(calls) == 2


def test_elastic_run_eviction_propagates():
    def train():
        raise EvictedError(3)

    with pytest.raises(EvictedError):
        elastic_run(train, max_restarts=5)


def test_elastic_run_world_change_budget():
    from paddle_tpu.errors import FatalError

    def train():
        raise WorldChangedError([0])

    with pytest.raises(FatalError, match="thrashing"):
        elastic_run(train, max_restarts=0, max_world_changes=2)


def test_elastic_run_legacy_signature_unchanged():
    """Zero-arg train fns (the historical API) still work."""
    calls = []

    def train():
        calls.append(1)
        if len(calls) < 2:
            raise RuntimeError("preempted")
        return "ok"

    assert elastic_run(train, max_restarts=2) == "ok"
    assert len(calls) == 2

"""What held a stalled call, from the record alone (profiler off).

Each hot loop leaves one flight event when a pass stands still
(`generation_stall`, `train_stall`): the program, the innermost phase,
its usual time, the time lost over it and whose time it was. The five
provocations here read `device`, `python`, `interpreter`, `runtime` and
(eight honest admissions) `lost_ms` 0; the training rule holds both of
its conditions; the evidence reader tolerates a platform that lacks
every source and reads nothing between its once-a-second refreshes.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.generation import GenerationEngine
from paddle_tpu.models import GPTForCausalLM, gpt_tiny_config
from paddle_tpu.monitor import flight_recorder
from paddle_tpu.serving import ContinuousBatcher

CACHE = 32
BUCKETS = (4, 8)
EVIDENCE = ("evidence_ms", "thread_cpu_ms", "process_cpu_ms", "gc_ms",
            "gc_max_ms", "gc_n")
HELD = ("t_ns", "held_phase", "held_ms", "usual_ms", "lost_ms", "held_by")
PROGRAM = ("program", "program_runs", "program_idle_s")
PROC = ("run_delay_ms", "steal_ms", "iowait_ms", "pressure_cpu_ms",
        "pressure_memory_ms", "pressure_io_ms")


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    cfg = gpt_tiny_config()
    cfg.attention_window = CACHE
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _engine(model, slots=2):
    return GenerationEngine(model, slots=slots, cache_len=CACHE,
                            prefill_buckets=BUCKETS, seed=7).warmup()


def _serve(eng, n=2, budget=3, on_token=None, start_first=True):
    """n requests through a scheduler; the `generation_stall` records."""
    flight_recorder.reset_recorder()
    sched = ContinuousBatcher(eng, queue_capacity=16)
    if start_first:
        sched.start()
    try:
        reqs = [sched.submit(list(range(3, 6 + i % 3)), max_new_tokens=budget,
                             temperature=0.0, on_token=on_token)
                for i in range(n)]
        sched.start()
        for r in reqs:
            r.wait(timeout=60)
    finally:
        sched.stop(drain=False)
    return [e for e in flight_recorder.events()
            if e["kind"] == "generation_stall"]


class _Slow:
    """Stands in for a device value whose program is still running: the
    conversion to the host is what waits."""

    def __init__(self, real, seconds):
        self.real, self.seconds = real, seconds

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.seconds)
        return np.asarray(self.real)

    def __int__(self):
        time.sleep(self.seconds)
        return int(self.real)


def _slow_results(eng, monkeypatch, label, seconds, times=1):
    """The next ``times`` results of ``label``'s program take ``seconds``
    to reach the host. A step enqueued behind one takes the tokens where
    they are: only the fetch waits."""
    real, fired = eng._dispatch, []

    def dispatch(lbl, jitted, make_args):
        out = real(lbl, jitted, lambda: tuple(
            a.real if isinstance(a, _Slow) else a for a in make_args()))
        if lbl == label and len(fired) < times:
            fired.append(1)
            return out[0], _Slow(out[1], seconds)
        return out

    monkeypatch.setattr(eng, "_dispatch", dispatch)


def _slow_launch(store, seconds):
    """The next call of the store's newest executable blocks ``seconds``
    before it enqueues: a load, an allocation, a full queue."""
    entry = list(store.entries().values())[-1]
    real, fired = entry.aot, []

    def runner(*args):
        if not fired:
            fired.append(1)
            time.sleep(seconds)
        return real(*args)

    entry.aot = runner


def _once(fn):
    fired = []

    def on_token(tok):
        if not fired:
            fired.append(1)
            fn()

    return on_token


def _burn(seconds):
    """``seconds`` of this thread's own CPU time, in Python."""
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


def _wait_for_a_spinning_thread(seconds):
    """Blocked, while another thread of the process burns ``seconds`` of
    CPU in Python: someone else holds the interpreter."""
    other = threading.Thread(target=_burn, args=(seconds,))
    other.start()
    other.join(timeout=60)
    assert not other.is_alive()


def test_a_slow_fetch_is_the_devices_time(model, monkeypatch):
    eng = _engine(model)
    _slow_results(eng, monkeypatch, "decode", 1.5)
    (ev,) = _serve(eng)
    assert ev["held_phase"] == "generation::decode_fetch"
    assert ev["held_by"] == "device"
    assert ev["program"] == "decode" and ev["program_runs"] >= 1
    assert ev["held_ms"] >= 1500 and ev["usual_ms"] < 100
    assert ev["lost_ms"] == pytest.approx(ev["held_ms"] - ev["usual_ms"],
                                          abs=0.01)
    assert ev["thread_cpu_ms"] < 0.5 * ev["held_ms"]
    for key in HELD + PROGRAM + EVIDENCE:
        assert key in ev, key
    # what the record had before this PR, and the split still sums
    assert abs(sum(ev["phases_ms"].values()) - ev["iteration_ms"]) < 0.05
    assert ev["live_slots"] >= 1 and "queue_depth" in ev
    assert set(ev["nested_ms"]) >= {"generation::args", "runtime::lookup",
                                    "runtime::launch"}
    assert sum(ev["nested_ms"].values()) <= ev["phases_ms"][
        "generation::decode"] + ev["phases_ms"].get("generation::prefill", 0)


@pytest.mark.parametrize("held_by, fn", [
    ("python", lambda: _burn(1.5)),
    ("interpreter", lambda: _wait_for_a_spinning_thread(1.5)),
    ("blocked", lambda: time.sleep(1.5)),
])
def test_a_slow_deliver_is_the_hosts_time_by_whose_cpu_it_was(
        model, held_by, fn):
    (ev,) = _serve(_engine(model), on_token=_once(fn))
    assert ev["held_phase"] in ("serving::deliver", "serving::install")
    assert ev["held_by"] == held_by
    assert "program" not in ev  # a host phase belongs to no program
    assert ev["lost_ms"] > 1000 and ev["usual_ms"] < 100


def test_a_launch_that_blocks_is_the_runtimes_time(model):
    eng = _engine(model)
    _slow_launch(eng._stores["decode"], 1.5)
    (ev,) = _serve(eng)
    assert ev["held_phase"] == "runtime::launch"
    assert ev["held_by"] == "runtime"
    assert ev["program"] == "decode"
    assert ev["nested_ms"]["runtime::launch"] >= 1500
    assert ev["lost_ms"] > 1000


def test_eight_honest_admissions_pass_the_second_and_lose_nothing(
        model, monkeypatch):
    eng = _engine(model, slots=8)
    _slow_results(eng, monkeypatch, "prefill", 0.2, times=8)
    (ev,) = _serve(eng, n=8, start_first=False)
    assert ev["iteration_ms"] >= 1600
    assert ev["held_phase"] == "generation::prefill_fetch"
    assert ev["program"] in ("prefill/4", "prefill/8")
    assert 200 <= ev["held_ms"] < 1000
    assert ev["lost_ms"] == 0
    # each prompt's bucket had run once, in warm-up, when its turn came
    assert ev["program_runs"] >= 1 and ev["program_idle_s"] > 0


def test_the_record_is_on_the_spans_clock(model, monkeypatch):
    eng = _engine(model)
    _slow_results(eng, monkeypatch, "decode", 1.2)
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    try:
        (ev,) = _serve(eng)
        spans = [(e["ts"] * 1e3, e["dur"] * 1e3) for e in
                 profiler.host_events()
                 if e["name"] == "generation::decode_fetch"]
    finally:
        profiler.stop_profiler()
        profiler.reset_profiler()
    start, dur = max(spans, key=lambda s: s[1])
    assert abs(ev["t_ns"] - start) < 2e3  # the span's start, to the us
    assert abs(ev["held_ms"] - dur / 1e6) < 0.01


def test_a_platform_that_lacks_every_source_still_gets_its_record(
        model, monkeypatch):
    def missing(path):
        raise FileNotFoundError(path)

    def no_rusage():
        raise OSError("no RUSAGE_THREAD here")

    monkeypatch.setattr(flight_recorder, "_first_line_fields", missing)
    monkeypatch.setattr(flight_recorder.Evidence, "_rusage",
                        staticmethod(no_rusage))
    eng = _engine(model)

    def lost():
        raise RuntimeError("device lost")

    monkeypatch.setattr(eng, "device_memory_stats", lost)
    _slow_results(eng, monkeypatch, "decode", 1.2)
    (ev,) = _serve(eng)
    assert ev["held_by"] == "device"
    for key in PROC + ("nvcsw", "nivcsw", "bytes_in_use",
                       "bytes_in_use_before"):
        assert key not in ev, key
    for key in HELD + EVIDENCE:
        assert key in ev, key


def test_evidence_reads_nothing_between_its_refreshes(model, monkeypatch):
    reads = []
    real = flight_recorder._first_line_fields

    def counted(path):
        reads.append(path)
        return real(path)

    monkeypatch.setattr(flight_recorder, "_first_line_fields", counted)
    asked = []
    ev = flight_recorder.Evidence(lambda: asked.append(1) or {})
    t0 = time.perf_counter_ns()
    ev.refresh(t0)
    once = len(reads)
    assert once >= 1 and len(asked) == 1
    for i in range(1000):  # a second of passes: nothing is read
        ev.refresh(t0 + i * 999_000)
    assert len(reads) == once and len(asked) == 1
    ev.refresh(t0 + 1_000_000_000)
    assert len(reads) == 2 * once and len(asked) == 2
    # the same through the loop: a run of many iterations reads the
    # files and asks the device once at its start and once a second
    eng = _engine(model)
    calls = []
    monkeypatch.setattr(eng, "device_memory_stats",
                        lambda: calls.append(1) or {})
    del reads[:]
    t0 = time.perf_counter()
    assert _serve(eng, n=6, budget=8) == []
    refreshes = int(time.perf_counter() - t0) + 1
    assert 1 <= len(calls) <= refreshes
    assert len(reads) <= refreshes * once


# -- the training loop ------------------------------------------------------


def _train_step():
    from paddle_tpu import nn, optimizer
    from paddle_tpu.framework import jit as fjit

    net = nn.Linear(4, 2)
    opt = optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
    step = fjit.train_step(net, opt, lambda m, x, y: ((m(x) - y) ** 2).mean())
    x = np.ones((3, 4), np.float32)
    y = np.zeros((3, 2), np.float32)
    step(x, y)  # the compile: an interval with nothing before it
    flight_recorder.reset_recorder()
    return step, x, y


def _groups(step, x, y, waits, every=5):
    """Groups of ``every`` calls, each closed by the caller's fetch,
    which takes ``waits[i]`` seconds (the benchmark's
    ``block_until_ready``: the caller's time, not the step's)."""
    for wait in waits:
        for _ in range(every):
            out = step(x, y)
        float(out["loss"])
        time.sleep(wait)
    step(x, y)  # the call that closes the last interval


def _stalls():
    return [e for e in flight_recorder.events() if e["kind"] == "train_stall"]


def test_a_regular_long_fetch_leaves_no_train_stall():
    # bert-base's shape: every tenth interval carries the fetch of ten
    # steps. Over the second or not, it stands beside others like it.
    step, x, y = _train_step()
    _groups(step, x, y, [0.3, 0.3, 1.1, 1.05], every=10)
    assert _stalls() == []


def test_a_group_that_stands_clear_of_the_others_leaves_one():
    # resnet50's shape: groups of five at 0.3 s, and one that took 1.4 s
    step, x, y = _train_step()
    _groups(step, x, y, [0.3, 0.3, 1.4, 0.3])
    (ev,) = _stalls()
    assert ev["held_phase"] == "outside" and ev["held_by"] == "blocked"
    assert ev["program"] == "train_step" and ev["program_runs"] == 15 + 1
    assert ev["interval_ms"] >= 1400 and 290 <= ev["usual_ms"] < 500
    assert ev["lost_ms"] == pytest.approx(ev["held_ms"] - ev["usual_ms"],
                                          abs=0.01)
    assert abs(sum(ev["phases_ms"].values()) - ev["interval_ms"]) < 0.05
    assert set(ev["phases_ms"]) == {"outside", "train::shard_batch",
                                    "train::step_dispatch", "other"}
    assert set(ev["nested_ms"]) == {"runtime::lookup", "runtime::launch"}
    for key in HELD + PROGRAM + EVIDENCE:
        assert key in ev, key


def test_a_dispatch_that_blocks_reads_runtime_launch():
    step, x, y = _train_step()
    _groups(step, x, y, [0.3, 0.3])
    _slow_launch(step._exec, 1.4)
    step(x, y)
    (ev,) = _stalls()
    assert ev["held_phase"] == "runtime::launch"
    assert ev["held_by"] == "runtime"
    assert ev["nested_ms"]["runtime::launch"] >= 1400
    assert ev["lost_ms"] > 1000 and ev["usual_ms"] < 100


def test_the_sharded_steps_share_the_watch():
    from paddle_tpu import nn, optimizer, parallel
    import jax

    net = nn.Linear(4, 2)
    opt = optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
    mesh = parallel.create_mesh(parallel.MeshConfig(
        dp=2, devices=jax.devices()[:2]))
    step = parallel.sharded_train_step(
        net, opt, lambda m, x, y: ((m(x) - y) ** 2).mean(), mesh)
    x = np.ones((4, 4), np.float32)
    y = np.zeros((4, 2), np.float32)
    step(x, y)
    flight_recorder.reset_recorder()
    _groups(step, x, y, [0.3, 0.3, 1.4], every=2)
    (ev,) = _stalls()
    assert ev["held_phase"] == "outside"
    assert set(ev["phases_ms"]) == {"outside", "train::shard_batch",
                                    "train::step_dispatch", "other"}

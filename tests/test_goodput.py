"""Training goodput ledger: exclusive-phase accounting, sidecar
restart continuity, lost-work attribution, and metric/line/trace
surfaces.

Acceptance pins (ISSUE 18): phases exclusive and conserving (idle is
the residual), overlap deduction inside step frames, background gating
for off-thread notes, GOODPUT.json CRC roundtrip + corrupt-file fresh
start, note_resume pricing recomputation as lost_work (not compute),
aborted-step badput with a step_aborted flight event, the
``# TYPE io_input_wait_ms_total counter`` migration with the legacy
gauge alias, parser goldens for the [monitor:train] and
[monitor:goodput] lines (incl. the _fmt_util scientific branch), and
the goodput SLO gating.
"""
import json
import os
import re
import subprocess
import sys
import threading

import pytest

from paddle_tpu import monitor
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.monitor import flight_recorder as fr
from paddle_tpu.monitor import goodput as gp
from paddle_tpu.monitor import registry as _reg
from paddle_tpu.monitor import slo as slo_mod
from paddle_tpu.monitor.training_monitor import _fmt_util


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


@pytest.fixture
def clock():
    return FakeClock()


@pytest.fixture
def ledger(clock):
    led = gp.GoodputLedger(dir=None, clock=clock)
    yield led


# -- exclusive phases + conservation ----------------------------------------

def test_phase_accounting_exact(ledger, clock):
    ledger.step_begin()
    clock.advance(0.7)
    ledger.step_commit(global_step=0)
    with ledger.span("compile"):
        clock.advance(0.3)
    with ledger.span("checkpoint"):
        clock.advance(0.2)
    clock.advance(0.6)  # unattributed -> idle residual
    s = ledger.snapshot()
    assert s["phases"]["compute"] == pytest.approx(0.7)
    assert s["phases"]["compile"] == pytest.approx(0.3)
    assert s["phases"]["checkpoint"] == pytest.approx(0.2)
    assert s["phases"]["idle"] == pytest.approx(0.6)
    assert s["wall_s"] == pytest.approx(1.8)
    assert sum(s["phases"].values()) == pytest.approx(s["wall_s"])
    assert s["conservation_error"] == 0.0
    assert s["goodput"] == pytest.approx(0.7 / 1.8)
    assert s["steps"] == 1 and s["max_committed_step"] == 0


def test_frame_overlap_deducted_from_compute(ledger, clock):
    # a compile inside the step frame must not double-count: the frame's
    # compute share shrinks by the noted sub-phase
    ledger.step_begin()
    clock.advance(0.2)
    with ledger.span("compile"):
        clock.advance(0.5)
    clock.advance(0.3)
    ledger.step_commit(global_step=0)
    s = ledger.snapshot()
    assert s["phases"]["compile"] == pytest.approx(0.5)
    assert s["phases"]["compute"] == pytest.approx(0.5)  # 1.0 - 0.5
    assert s["conservation_error"] == 0.0


def test_offthread_note_is_background(ledger, clock):
    # an async checkpoint writer runs overlapped with compute: its
    # seconds cost no wall time, so they land in background_s and stay
    # out of the conservation sum
    ledger.step_begin()
    clock.advance(0.1)
    t = threading.Thread(
        target=lambda: ledger.note_phase("checkpoint", 0.4))
    t.start()
    t.join()
    clock.advance(0.1)
    ledger.step_commit(global_step=0)
    s = ledger.snapshot()
    assert s["phases"]["checkpoint"] == 0.0
    assert s["background_s"] == {"checkpoint": pytest.approx(0.4)}
    assert s["phases"]["compute"] == pytest.approx(0.2)
    assert s["conservation_error"] == 0.0


def test_note_phase_rejects_unknown_phase(ledger):
    with pytest.raises(ValueError, match="unknown goodput phase"):
        ledger.note_phase("coffee_break", 1.0)


def test_abort_is_badput_not_compute(ledger, clock):
    ledger.step_begin()
    clock.advance(0.25)
    ledger.step_abort()
    s = ledger.snapshot()
    assert s["phases"]["aborted"] == pytest.approx(0.25)
    assert s["phases"]["compute"] == 0.0
    assert s["steps"] == 0  # aborted steps never count as committed


# -- sidecar persistence + restart continuity -------------------------------

def _run_first_life(tmp_path, clock):
    led = gp.GoodputLedger(dir=tmp_path, clock=clock)
    for step in range(5):
        led.step_begin()
        clock.advance(2.0)
        led.step_commit(global_step=step)
    led.publish()
    return led


def test_sidecar_roundtrip_and_lost_work(tmp_path, clock):
    d = str(tmp_path / "goodput")
    _run_first_life(d, clock)
    doc = json.load(open(os.path.join(d, gp.SIDECAR)))
    assert doc["body"]["max_committed_step"] == 4
    assert doc["body"]["mean_step_s"] == pytest.approx(2.0)

    # second life: resumes from a manifest at step 1 -> steps 2..4 were
    # committed after it and must be recomputed as lost_work
    led2 = gp.GoodputLedger(dir=d, clock=clock)
    assert led2.sidecar_loaded
    assert led2.max_committed_step == 4
    led2.note_resume(1)
    assert led2.recompute_until == 4
    assert led2.lost_work_priced_s == pytest.approx(3 * 2.0)
    # recommit inside the window -> lost_work; past it -> compute
    led2.step_begin()
    clock.advance(2.0)
    led2.step_commit(global_step=2)
    led2.step_begin()
    clock.advance(2.0)
    led2.step_commit(global_step=5)
    s = led2.snapshot()
    assert s["phases"]["lost_work"] == pytest.approx(2.0)
    assert s["phases"]["compute"] == pytest.approx(2.0)
    assert s["lost_steps"] == 1 and s["resumes"] == 1
    # lifetime continuity: previous life's wall + phases carried over
    assert s["lifetime"]["wall_s"] > s["wall_s"]
    assert s["lifetime"]["steps"] == 7
    assert s["lifetime"]["phases"]["compute"] == pytest.approx(12.0)
    ev = [e for e in fr.get_recorder().snapshot()["events"]
          if e.get("kind") == "goodput_resume"]
    assert ev and ev[-1]["steps_to_recompute"] == 3


def _run_goodput_trainer(root, env):
    """tests/fixtures/goodput_trainer.py as a process: twelve steps of
    5 ms, a synchronous checkpoint every third, the sidecar published at
    every commit."""
    os.makedirs(os.path.join(root, "ckpt"), exist_ok=True)
    return subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "fixtures",
                                      "goodput_trainer.py")],
        env=dict(env, GOODPUT_CKPT_DIR=os.path.join(root, "ckpt"),
                 FLAGS_goodput_dir=os.path.join(root, "goodput"),
                 FLAGS_goodput_publish_interval_s="0",
                 GOODPUT_TOTAL_STEPS="12", GOODPUT_STEP_MS="5",
                 GOODPUT_SAVE_EVERY="3"),
        capture_output=True, text=True, timeout=120)


def test_kill9_inside_a_save_continues_the_lifetime_ledger(tmp_path,
                                                           child_env):
    """A REAL kill -9 inside the second checkpoint save, then a
    relaunch: the sidecar of the first life is loaded, the lifetime wall
    and steps go on from it, and the steps committed after the restored
    manifest are charged to lost_work, not to compute."""
    root = str(tmp_path)
    killed = _run_goodput_trainer(root, child_env(
        FLAGS_fault_injection="kill:point=mid_save,n=2"))
    assert killed.returncode == -9, killed.stderr[-2000:]
    with open(os.path.join(root, "goodput", gp.SIDECAR)) as f:
        before = json.load(f)["body"]

    resumed = _run_goodput_trainer(root, child_env())
    assert resumed.returncode == 0, resumed.stderr[-3000:]
    out = json.loads([ln for ln in resumed.stdout.splitlines()
                      if ln.startswith("{")][-1])
    assert out["resumed_from"] >= 0 and out["sidecar_loaded"]
    assert out["resumes"] == 1
    life = out["lifetime"]
    assert life["wall_s"] > out["wall_s"]
    assert life["wall_s"] >= before["wall_s"]
    assert life["steps"] > before["steps"]
    recomputed = out["max_committed_step"] - out["resumed_from"]
    assert 1 <= out["lost_steps"] <= recomputed
    assert out["phases"]["lost_work"] > 0
    assert out["lost_work_priced_s"] > 0
    # the real feeds reached the ledger, and the phases do not overrun
    # the wall they partition
    for phase in ("compute", "input_wait", "checkpoint"):
        assert out["phases"][phase] > 0, phase
    assert out["conservation_error"] <= 0.02
    assert any(ln.startswith("[monitor:goodput] wall_s=")
               for ln in out["monitor_lines"])


def test_unknown_global_step_never_guesses_lost_work(tmp_path, clock):
    d = str(tmp_path / "goodput")
    _run_first_life(d, clock)
    led2 = gp.GoodputLedger(dir=d, clock=clock)
    led2.note_resume(1)
    led2.step_begin()
    clock.advance(1.0)
    led2.step_commit()  # no global step -> compute, window untouched
    s = led2.snapshot()
    assert s["phases"]["compute"] == pytest.approx(1.0)
    assert s["lost_steps"] == 0
    assert s["max_committed_step"] == 4  # not clobbered by a guess


def test_corrupt_sidecar_starts_fresh(tmp_path, clock):
    d = str(tmp_path / "goodput")
    os.makedirs(d)
    with open(os.path.join(d, gp.SIDECAR), "w") as f:
        f.write('{"crc32": 1, "body": {"wall_s": 1e9}}')
    led = gp.GoodputLedger(dir=d, clock=clock)
    assert not led.sidecar_loaded
    s = led.snapshot()
    assert s["lifetime"]["wall_s"] == pytest.approx(s["wall_s"])
    ev = [e for e in fr.get_recorder().snapshot()["events"]
          if e.get("kind") == "goodput_sidecar_corrupt"]
    assert ev and "crc" in ev[-1]["error"]


def test_publish_is_atomic_no_tmp_left(tmp_path, clock, ledger):
    d = str(tmp_path / "goodput")
    led = gp.GoodputLedger(dir=d, clock=clock)
    led.publish()
    assert os.path.isfile(os.path.join(d, gp.SIDECAR))
    assert not os.path.exists(os.path.join(d, gp.SIDECAR + ".tmp"))


# -- metric + line + trace surfaces -----------------------------------------

def test_flush_metrics_labeled_counters(ledger, clock):
    ledger.step_begin()
    clock.advance(1.0)
    ledger.step_commit(global_step=0)
    with ledger.span("checkpoint"):
        clock.advance(0.5)
    ledger.flush_metrics()
    text = monitor.prometheus_text()
    assert "# TYPE goodput_seconds_total counter" in text
    assert 'goodput_seconds_total{phase="compute"} 1' in text
    assert 'goodput_seconds_total{phase="checkpoint"} 0.5' in text
    assert "# TYPE goodput_wall_seconds_total counter" in text
    assert "# TYPE goodput_badput_seconds_total counter" in text


def test_flush_watermark_keeps_counters_monotone(ledger, clock):
    clock.advance(1.0)  # all idle
    ledger.flush_metrics()
    fam = _reg.counter("goodput/seconds_total")
    idle0 = fam.labels(phase="idle").value
    assert idle0 == pytest.approx(1.0)
    # attribute that second retroactively: snapshot idle shrinks, but
    # the flushed counter must NOT decrease (clamped at high water)
    ledger.note_phase("compile", 0.8)
    ledger.flush_metrics()
    assert fam.labels(phase="idle").value == pytest.approx(idle0)
    assert fam.labels(phase="compile").value == pytest.approx(0.8)


def test_goodput_line_golden(ledger, clock):
    ledger.step_begin()
    clock.advance(0.5)
    ledger.step_commit(global_step=0)
    lines = []
    line = ledger.emit_line(log_fn=lines.append)
    assert lines == [line]
    m = re.fullmatch(
        r"\[monitor:goodput\] wall_s=(?P<wall>[\d.]+) "
        r"goodput=(?P<gp>[\d.eE+-]+) "
        r"compute_s=([\d.]+) input_wait_s=([\d.]+) compile_s=([\d.]+) "
        r"checkpoint_s=([\d.]+) restore_s=([\d.]+) "
        r"renegotiate_s=([\d.]+) lost_work_s=([\d.]+) "
        r"aborted_s=([\d.]+) idle_s=([\d.]+) "
        r"steps=(?P<steps>\d+) lost_steps=\d+ resumes=\d+", line)
    assert m, line
    assert float(m.group("wall")) == pytest.approx(0.5)
    assert float(m.group("gp")) == pytest.approx(1.0)
    assert int(m.group("steps")) == 1


def test_fmt_util_scientific_branch():
    # a CPU smoke's 4e-5 goodput/MFU must stay distinguishable from zero
    assert _fmt_util(4e-5) == "4.00e-05"
    assert _fmt_util(0.0) == "0.0000"
    assert _fmt_util(0.25) == "0.2500"


def test_chrome_events_track(ledger, clock):
    ledger.step_begin()
    clock.advance(0.5)
    ledger.step_commit(global_step=0)
    events = ledger.chrome_events()
    meta = [e for e in events if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"] == "goodput phases"
    xs = [e for e in events if e["ph"] == "X"]
    assert xs[0]["name"] == "goodput::compute"
    assert xs[0]["dur"] == pytest.approx(0.5e6)  # µs
    assert xs[0]["tid"] == meta[0]["tid"]


def test_goodputz_payload_disabled_shape():
    assert gp.active_ledger() is None
    payload = gp.goodputz_payload()
    assert payload["enabled"] is False and "FLAGS_goodput_dir" in payload["hint"]
    # module-level span is a shared no-op when off
    with gp.span("compile"):
        pass


# -- input-wait counter migration (satellite 1) -----------------------------

def test_input_wait_counter_migration_type_lines():
    monitor.record_input_wait_ms(12.5)
    monitor.record_input_wait_ms(7.5)
    assert _reg.counter("io/input_wait_ms_total").value == pytest.approx(20.0)
    # legacy gauge alias still present for existing scrapers
    assert _reg.gauge("io/input_wait_ms").value == pytest.approx(20.0)
    text = monitor.prometheus_text()
    assert "# TYPE io_input_wait_ms_total counter" in text
    assert "# TYPE io_input_wait_ms gauge" in text


def test_input_wait_feeds_ledger_phase(clock):
    led = gp.start_ledger(clock=clock)
    try:
        monitor.record_input_wait_ms(250.0)
        assert led.snapshot()["phases"]["input_wait"] == pytest.approx(0.25)
    finally:
        gp.reset_ledger()


# -- TrainingMonitor integration (satellites 2 + 3) -------------------------

def test_monitor_abort_records_badput_and_event(clock):
    led = gp.start_ledger(clock=clock)
    try:
        mon = monitor.TrainingMonitor("train", interval=0)
        with pytest.raises(RuntimeError):
            with mon.step(examples=4):
                raise RuntimeError("boom")
        ev = [e for e in fr.get_recorder().snapshot()["events"]
              if e.get("kind") == "step_aborted"]
        assert ev and ev[-1]["monitor"] == "train" and ev[-1]["step"] == 1
        assert _reg.counter("monitor/train/aborted_step_ms").value >= 0
        assert led.snapshot()["phases"]["aborted"] >= 0.0
        mon.close()
    finally:
        gp.reset_ledger()


def test_monitor_emits_goodput_line_alongside_window_line():
    led = gp.start_ledger()
    try:
        lines = []
        mon = monitor.TrainingMonitor("train", interval=2,
                                      log_fn=lines.append)
        for s in range(2):
            with mon.step(examples=4, global_step=s):
                pass
        mon.close()
        train = [l for l in lines if l.startswith("[monitor:train]")]
        good = [l for l in lines if l.startswith("[monitor:goodput]")]
        assert train and good
        # window-line golden: every field parseable, util fields via
        # _fmt_util (fixed-point or scientific, never a bare 0)
        m = re.fullmatch(
            r"\[monitor:train\] step=\d+ step_ms=[\d.]+ "
            r"examples_per_sec=[\d.]+ input_wait_ratio=[\d.]+ "
            r"plan_cache_hit_rate=[\d.]+ jit_cache_hit_rate=[\d.]+ "
            r"compiles=\d+ hbm_peak_bytes=\d+ "
            r"mfu=(?:[\d.]+|[\d.]+e[+-]\d+) "
            r"hbm_bw_util=(?:[\d.]+|[\d.]+e[+-]\d+) "
            r"roofline=\S+", train[0])
        assert m, train[0]
        assert led.snapshot()["steps"] == 2
    finally:
        gp.reset_ledger()


# -- SLO gating (tentpole surface) ------------------------------------------

def test_goodput_slo_gating():
    prev = get_flags("goodput_slo_target")["goodput_slo_target"]
    try:
        set_flags({"goodput_slo_target": 0.0})
        assert gp.install_goodput_slo() is None
        s = gp.install_goodput_slo(target=0.9, window_s=60.0)
        assert s is not None and s.name == "goodput"
        assert s.selector == "goodput/badput_seconds_total"
        assert s.total_selector == "goodput/wall_seconds_total"
        assert s.mode == "error"
    finally:
        set_flags({"goodput_slo_target": prev})
        slo_mod.reset_engine()

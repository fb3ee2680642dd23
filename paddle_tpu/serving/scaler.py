"""Metrics-driven autoscaling: grow/shrink the backend fleet.

The router balances whatever fleet exists; this module decides how big
that fleet should BE. An :class:`AutoScaler` periodically gathers

- **router-side aggregates** — per-backend queue depth / in-flight from
  the router's probed :class:`~paddle_tpu.serving.router.BackendState`
  table (the same ``/loadz`` signals dispatch uses), and
- **host snapshots** — ``monitor/cluster.py``'s ``local_snapshot()``
  (MFU, HBM watermark, step rate), recorded as evidence with every
  decision so a post-mortem can see what the fleet looked like when the
  scaler acted,

and runs one decision per tick against a pluggable **launcher**:

- *scale up* when mean queue depth per healthy backend sustains at or
  above ``up_queue_depth`` for ``window`` consecutive evaluations
  (hysteresis — one spiky tick must not flap the fleet), bounded by
  ``max_backends``;
- *scale down* when the fleet sustains idle (queue depth at or below
  ``down_queue_depth`` with zero in-flight) for a full window, bounded
  by ``min_backends`` — the victim is the least-loaded backend the
  scaler itself launched, which is first removed from rotation (no new
  traffic) and then terminated through the launcher (SIGTERM -> the
  backend's graceful drain);
- after ANY action, ``cooldown_s`` suppresses further decisions so a
  booting backend's warmup cannot be misread as sustained pressure.

Each is a constructor argument of :class:`AutoScaler`; the defaults are
the ``DEFAULT_*`` constants below (no deployment sets another).

Decisions, hysteresis, and cooldowns are pure functions of the signal
stream and an injectable clock (``AutoScaler(clock=...)``) — unit tests
drive :meth:`AutoScaler.decide` tick by tick with synthetic
:class:`FleetSignals` and a fake launcher, no processes involved. The
provided :class:`SubprocessLauncher` boots real
``python -m paddle_tpu.serving.backend`` processes with port-file
discovery (ready means warmed: the port file is written after warmup).
"""
from __future__ import annotations

import os
import signal as _signal
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field

from ..errors import InvalidArgumentError, UnavailableError
from ..flags import flag
from ..monitor import cluster as _cluster
from ..monitor import counter, gauge
from ..monitor import flight_recorder as _flight

__all__ = ["AutoScaler", "FleetSignals", "SubprocessLauncher",
           "LaunchedBackend", "launch_process"]

# AutoScaler's defaults
DEFAULT_INTERVAL_S = 5.0        # seconds between evaluations
DEFAULT_MIN_BACKENDS = 1        # never drain below
DEFAULT_MAX_BACKENDS = 4        # never launch above
DEFAULT_UP_QUEUE_DEPTH = 4.0    # mean depth per backend that is pressure
DEFAULT_DOWN_QUEUE_DEPTH = 0.25  # mean depth per backend that is idleness
DEFAULT_WINDOW = 3              # consecutive evaluations before acting
DEFAULT_COOLDOWN_S = 30.0       # no decision for this long after an action


@dataclass
class FleetSignals:
    """One evaluation tick's view of the fleet (inputs to ``decide``).

    ``kinds`` splits the pressure aggregates per backend kind
    (``predict`` / ``generate`` / ``prefill`` / ``decode``): fleet-wide
    means average a saturated decode tier against idle prefill
    backends, which is exactly how a starving tier hides — a
    kind-scoped scaler reads its own tier's split instead. When the
    scaler is constructed with ``kind=...``, the TOP-LEVEL aggregates
    are already that tier's (and ``kind`` names it); ``kinds`` always
    carries the full per-kind view for evidence/debugging."""

    time: float
    backends_total: int
    backends_healthy: int
    mean_queue_depth: float
    max_queue_depth: int
    total_inflight: int
    host: dict = field(default_factory=dict)  # cluster.local_snapshot()
    kind: str | None = None
    kinds: dict = field(default_factory=dict)
    # confirmed SLO burn rate (monitor.slo.current_burn(): max over
    # objectives of min(fast, slow) window burn) — queue depth says the
    # fleet is BUSY, burn says users are already losing error budget
    slo_burn: float = 0.0


def _kind_split(states) -> dict:
    """Per-kind pressure aggregates over in-rotation backends (a
    kind-unknown backend is booting/unprobed: its own bucket, so it
    cannot dilute a confirmed tier's mean)."""
    split: dict = {}
    for b in states:
        if not b.in_rotation:
            continue
        k = b.kind or "unknown"
        row = split.setdefault(
            k, {"healthy": 0, "queue_depths": [], "inflight": 0})
        row["healthy"] += 1
        row["queue_depths"].append(b.queue_depth)
        row["inflight"] += b.inflight
    out = {}
    for k, row in split.items():
        depths = row.pop("queue_depths")
        row["mean_queue_depth"] = (sum(depths) / len(depths)
                                   if depths else 0.0)
        row["max_queue_depth"] = max(depths) if depths else 0
        out[k] = row
    return out


@dataclass
class LaunchedBackend:
    """A backend process the scaler owns (and may terminate)."""

    url: str
    proc: object = None
    workdir: str = ""
    log_path: str = ""


def launch_process(module, args, host="127.0.0.1", python=None,
                   env=None, cpus=None, startup_timeout_s=120.0):
    """Boot ``python -m <module> <args> --port-file <f>`` and wait for
    the port announcement — the one process-discovery recipe every
    fleet process (backend OR router) uses: PYTHONPATH propagation so
    the child imports THIS paddle_tpu even uninstalled, stdout/stderr
    into a per-process log, optional ``taskset -c`` core pinning, and a
    startup deadline that distinguishes "died during boot" (with the
    log path) from "never became ready". The announced port is written
    by the child only once it is READY (the entrypoints write it after
    warmup/start), so the returned URL is immediately servable."""
    workdir = tempfile.mkdtemp(prefix="ptpu_proc_")
    port_file = os.path.join(workdir, "port")
    log_path = os.path.join(workdir, "proc.log")
    cmd = [python or sys.executable, "-m", module,
           *[str(a) for a in args], "--port-file", port_file]
    if cpus is not None:
        import shutil

        if shutil.which("taskset"):
            cmd = ["taskset", "-c", str(cpus)] + cmd
    child_env = dict(os.environ)
    import paddle_tpu

    pkg_root = os.path.dirname(os.path.dirname(
        os.path.abspath(paddle_tpu.__file__)))
    child_env["PYTHONPATH"] = os.pathsep.join(
        [pkg_root] + ([child_env["PYTHONPATH"]]
                      if child_env.get("PYTHONPATH") else []))
    if env:
        child_env.update(env)
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(cmd, stdout=log,
                                stderr=subprocess.STDOUT, env=child_env)
    deadline = time.monotonic() + float(startup_timeout_s)
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise UnavailableError(
                f"{module} process died during startup "
                f"(rc={proc.returncode}); log: {log_path}")
        if os.path.exists(port_file):
            with open(port_file) as f:
                port = int(f.read().strip())
            return LaunchedBackend(url=f"http://{host}:{port}",
                                   proc=proc, workdir=workdir,
                                   log_path=log_path)
        time.sleep(0.05)
    proc.kill()
    raise UnavailableError(
        f"{module} did not become ready within {startup_timeout_s}s; "
        f"log: {log_path}")


class SubprocessLauncher:
    """Launch/terminate real backend processes on this host.

    ``launch()`` blocks until the backend announces its port (which the
    entrypoint does only after warmup, so a returned URL is READY) and
    returns a :class:`LaunchedBackend`; ``terminate()`` SIGTERMs it
    (graceful drain) and escalates to SIGKILL past the timeout.

    Children inherit this process's environment, and a TPU chip belongs
    to one process at a time: several backends on one TPU host, or a
    parent that has touched jax on it, are unsupported (the child fails
    or hangs). Pass ``env={"JAX_PLATFORMS": "cpu"}`` for the CPU
    emulation; one chip per backend is ROADMAP R5.
    """

    def __init__(self, model_dir, host="127.0.0.1", replicas=None,
                 buckets=None, queue_capacity=None, batch_timeout_ms=None,
                 mesh_dp=0, python=None, env=None,
                 startup_timeout_s=120.0, cpu_sets=None,
                 kind="predict", extra_args=()):
        self.model_dir = model_dir
        self.host = host
        self.replicas = replicas
        self.buckets = buckets
        self.queue_capacity = queue_capacity
        self.batch_timeout_ms = batch_timeout_ms
        self.mesh_dp = mesh_dp
        # generation kinds boot from a save_gpt_model dir (--gpt-dir);
        # extra_args passes kind-specific knobs straight through
        # (--slots, --draft-dir, ... — a tier-bound scaler's launcher
        # bakes its tier's configuration here)
        self.kind = str(kind)
        self.extra_args = [str(a) for a in extra_args]
        self.python = python or sys.executable
        self.env = dict(env) if env else {}
        self.startup_timeout_s = float(startup_timeout_s)
        # optional taskset core pinning, cycled per launch ("0-5",
        # "6-11", ...): on a single box, XLA:CPU spreads one backend's
        # intra-op threads across EVERY core, so co-hosted backends
        # fight for the same silicon — disjoint core sets make each
        # process behave like its own host. Multi-host fleets don't
        # need it.
        self.cpu_sets = list(cpu_sets) if cpu_sets else []
        self._launches = 0

    def _args(self):
        if self.kind != "predict":
            args = ["--kind", self.kind,
                    "--gpt-dir", str(self.model_dir),
                    "--host", self.host, "--port", "0"]
            if self.queue_capacity is not None:
                args += ["--queue-capacity", str(self.queue_capacity)]
            return args + self.extra_args
        args = ["--model-dir", str(self.model_dir),
                "--host", self.host, "--port", "0"]
        if self.replicas is not None:
            args += ["--replicas", str(self.replicas)]
        if self.buckets is not None:
            b = self.buckets
            args += ["--buckets",
                     b if isinstance(b, str)
                     else ",".join(str(int(v)) for v in b)]
        if self.queue_capacity is not None:
            args += ["--queue-capacity", str(self.queue_capacity)]
        if self.batch_timeout_ms is not None:
            args += ["--batch-timeout-ms", str(self.batch_timeout_ms)]
        if self.mesh_dp:
            args += ["--mesh-dp", str(self.mesh_dp)]
        return args + self.extra_args

    def launch(self) -> LaunchedBackend:
        cpus = (self.cpu_sets[self._launches % len(self.cpu_sets)]
                if self.cpu_sets else None)
        handle = launch_process(
            "paddle_tpu.serving.backend", self._args(), host=self.host,
            python=self.python, env=self.env, cpus=cpus,
            startup_timeout_s=self.startup_timeout_s)
        self._launches += 1
        _flight.record_event("scaler_backend_launched",
                             url=handle.url, pid=handle.proc.pid)
        return handle

    def terminate(self, handle: LaunchedBackend, drain=True,
                  timeout_s=15.0):
        proc = handle.proc
        if proc is None or proc.poll() is not None:
            return
        proc.send_signal(_signal.SIGTERM if drain else _signal.SIGKILL)
        try:
            proc.wait(timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(5.0)
        _flight.record_event("scaler_backend_terminated",
                             url=handle.url, drain=drain,
                             rc=proc.returncode)


class AutoScaler:
    """Scale decisions over router signals, acting through a launcher.

    ``router`` needs ``backend_states()`` / ``add_backend`` /
    ``remove_backend`` (duck-typed; tests pass a stub). ``launcher``
    needs ``launch() -> LaunchedBackend`` and ``terminate(handle,
    drain=)``. All thresholds default to the module's ``DEFAULT_*``
    constants; ``clock`` is injectable for deterministic
    hysteresis/cooldown tests.
    """

    def __init__(self, router, launcher, min_backends=None,
                 max_backends=None, up_queue_depth=None,
                 down_queue_depth=None, window=None, cooldown_s=None,
                 interval_s=None, kind=None, clock=time.monotonic):
        self.router = router
        self.launcher = launcher
        # tier scoping: a kind-bound scaler sees ONLY its tier's
        # pressure and owns only its tier's backends — one scaler per
        # kind sizes a disaggregated fleet's tiers independently (the
        # launcher must boot backends of the matching --kind)
        self.kind = kind
        self.min_backends = int(
            min_backends if min_backends is not None else DEFAULT_MIN_BACKENDS)
        self.max_backends = int(
            max_backends if max_backends is not None else DEFAULT_MAX_BACKENDS)
        if not 0 < self.min_backends <= self.max_backends:
            raise InvalidArgumentError(
                f"scaler bounds must satisfy 0 < min <= max, got "
                f"min={self.min_backends} max={self.max_backends}")
        self.up_queue_depth = float(
            up_queue_depth if up_queue_depth is not None
            else DEFAULT_UP_QUEUE_DEPTH)
        self.down_queue_depth = float(
            down_queue_depth if down_queue_depth is not None
            else DEFAULT_DOWN_QUEUE_DEPTH)
        self.window = int(window if window is not None else DEFAULT_WINDOW)
        if self.window <= 0:
            raise InvalidArgumentError(
                f"scaler hysteresis window must be positive, got "
                f"{self.window}")
        self.cooldown_s = float(
            cooldown_s if cooldown_s is not None else DEFAULT_COOLDOWN_S)
        self.interval_s = float(
            interval_s if interval_s is not None else DEFAULT_INTERVAL_S)
        # burn at/above this (both SLO windows confirming) is up-pressure
        # on its own: latency SLOs can burn while queues stay shallow
        # (e.g. a wedged-but-answering backend)
        self.burn_alert = float(flag("slo_burn_alert"))
        self.clock = clock
        self.owned: dict[str, LaunchedBackend] = {}
        self._up_streak = 0
        self._down_streak = 0
        self._last_action_t = None
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = None
        self._m_ups = counter("serving/scaler_scale_ups_total")
        self._m_downs = counter("serving/scaler_scale_downs_total")
        self._m_reaped = counter("serving/scaler_backends_reaped_total")
        self._m_owned = gauge("serving/scaler_backends_owned")
        from . import _register_live

        _register_live(self)

    # -- signal gathering ----------------------------------------------------

    def signals(self) -> FleetSignals:
        """One tick's fleet view: router backend table aggregates plus
        this host's cluster snapshot (decision evidence). A kind-bound
        scaler's top-level aggregates are its TIER's only (a saturated
        decode tier must never be masked by idle prefill backends);
        the full per-kind split rides along either way."""
        all_states = self.router.backend_states()
        states = all_states
        if self.kind is not None:
            # a just-launched owned backend may not have a probed kind
            # yet — it still belongs to this tier's totals
            states = [b for b in all_states
                      if b.kind == self.kind or (
                          b.kind is None
                          and b.url in self.owned)]
        healthy = [b for b in states if b.in_rotation]
        depths = [b.queue_depth for b in healthy]
        # the scaler runs in-process with the router, so the router-side
        # SLO engine's confirmed burn is a local read, not an RPC
        from ..monitor import slo as _slo

        return FleetSignals(
            time=self.clock(),
            backends_total=len(states),
            backends_healthy=len(healthy),
            mean_queue_depth=(sum(depths) / len(depths)
                              if depths else 0.0),
            max_queue_depth=max(depths) if depths else 0,
            total_inflight=sum(b.inflight for b in healthy),
            host=_cluster.local_snapshot(),
            kind=self.kind,
            kinds=_kind_split(all_states),
            slo_burn=_slo.current_burn(),
        )

    # -- decision ------------------------------------------------------------

    def in_cooldown(self, now=None) -> bool:
        if self._last_action_t is None:
            return False
        now = self.clock() if now is None else now
        return (now - self._last_action_t) < self.cooldown_s

    def decide(self, sig: FleetSignals) -> str | None:
        """Evaluate one tick: returns ``"up"``, ``"down"``, or ``None``.

        Hysteresis: an action fires only after ``window`` CONSECUTIVE
        same-direction ticks; a neutral tick resets both streaks. During
        cooldown streaks do not accumulate at all — pressure during a
        backend's boot must not pre-charge the next decision.
        """
        if self.in_cooldown(sig.time):
            self._up_streak = self._down_streak = 0
            return None
        # zero healthy backends IS up-pressure regardless of queue math:
        # the fleet is dark and the router is answering 503s; a
        # confirmed SLO burn past the alert threshold likewise — error
        # budget is being spent NOW even if queues look shallow
        up = (sig.backends_healthy == 0
              or sig.mean_queue_depth >= self.up_queue_depth
              or sig.slo_burn >= self.burn_alert)
        down = (not up
                and sig.mean_queue_depth <= self.down_queue_depth
                and sig.total_inflight == 0)
        self._up_streak = self._up_streak + 1 if up else 0
        self._down_streak = self._down_streak + 1 if down else 0
        if (self._up_streak >= self.window
                and sig.backends_total < self.max_backends):
            return "up"
        if (self._down_streak >= self.window
                and sig.backends_healthy > self.min_backends
                and self.owned):
            return "down"
        return None

    # -- actions -------------------------------------------------------------

    def _note_action(self, now):
        self._last_action_t = now
        self._up_streak = self._down_streak = 0
        self._m_owned.set(len(self.owned))

    def scale_up(self, sig: FleetSignals):
        handle = self.launcher.launch()
        self.owned[handle.url.rstrip("/")] = handle
        self.router.add_backend(handle.url)
        self._m_ups.inc()
        self._note_action(self.clock())
        _flight.record_event(
            "scaler_scale_up", url=handle.url,
            backends=sig.backends_total + 1,
            mean_queue_depth=round(sig.mean_queue_depth, 3),
            host_mfu=sig.host.get("mfu"),
            host_hbm_peak=sig.host.get("hbm_peak_bytes"))
        return handle

    def scale_down(self, sig: FleetSignals):
        """Drain the least-loaded OWNED backend: out of rotation first
        (no new traffic), then a graceful terminate (SIGTERM -> the
        backend drains queued work before its listener closes)."""
        victims = [b for b in self.router.backend_states()
                   if b.url in self.owned]
        if not victims:
            return None
        victim = min(victims, key=lambda b: (b.score(), b.url))
        self.router.remove_backend(victim.url)
        handle = self.owned.pop(victim.url)
        self._m_downs.inc()
        self._note_action(self.clock())
        _flight.record_event(
            "scaler_scale_down", url=victim.url,
            backends=sig.backends_total - 1,
            mean_queue_depth=round(sig.mean_queue_depth, 3),
            host_mfu=sig.host.get("mfu"),
            host_hbm_peak=sig.host.get("hbm_peak_bytes"))
        self.launcher.terminate(handle, drain=True)
        return handle

    def reap_dead(self) -> list:
        """Forget owned backends whose PROCESS died (crash, OOM-kill):
        drop them from the router and from ``owned``. Without this, a
        dead-but-registered backend holds a ``backends_total`` slot
        forever and blocks its own replacement at ``max_backends`` —
        the fleet would run degraded with no path back to capacity."""
        reaped = []
        for url, handle in list(self.owned.items()):
            proc = handle.proc
            if proc is None or proc.poll() is None:
                continue
            self.owned.pop(url, None)
            try:
                self.router.remove_backend(url)
            except Exception:
                pass
            self._m_reaped.inc()
            self._m_owned.set(len(self.owned))
            _flight.record_event("scaler_backend_reaped", url=url,
                                 rc=proc.returncode)
            reaped.append(url)
        return reaped

    def step(self) -> str | None:
        """One evaluate-decide-act tick (the loop body; also the unit
        tests' entry). Returns the action taken, if any."""
        self.reap_dead()
        sig = self.signals()
        action = self.decide(sig)
        if action == "up":
            self.scale_up(sig)
        elif action == "down":
            self.scale_down(sig)
        return action

    # -- lifecycle -----------------------------------------------------------

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self):
        if self.alive:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="ptpu-serving-scaler", daemon=True)
        self._thread.start()
        _flight.record_event("scaler_start",
                             interval_s=self.interval_s,
                             min=self.min_backends,
                             max=self.max_backends)
        return self

    def _run(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.step()
            except Exception:  # the scaler must never kill the fleet
                pass

    def stop(self, drain=True, timeout=10.0):
        """Stop the loop and terminate every backend the scaler owns
        (``drain=False`` SIGKILLs them — the test-teardown path must
        not leave orphan processes)."""
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=self.interval_s + 1.0)
        self._thread = None
        for url, handle in list(self.owned.items()):
            try:
                self.router.remove_backend(url)
            except Exception:
                pass
            try:
                self.launcher.terminate(handle, drain=drain,
                                        timeout_s=timeout)
            except Exception:
                pass
            self.owned.pop(url, None)
        self._m_owned.set(0)
        _flight.record_event("scaler_stop", drain=drain)

    def view(self) -> dict:
        return {
            "alive": self.alive,
            "owned": sorted(self.owned),
            "min_backends": self.min_backends,
            "max_backends": self.max_backends,
            "up_streak": self._up_streak,
            "down_streak": self._down_streak,
            "in_cooldown": self.in_cooldown(),
            "scale_ups": self._m_ups.value,
            "scale_downs": self._m_downs.value,
        }

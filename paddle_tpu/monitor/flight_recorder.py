"""Flight recorder: post-mortem + live fault diagnosis for unhealthy runs.

PR 2 made healthy runs legible; an *unhealthy* run — a hung collective, a
desynced fleet worker, a stalled PS RPC, a NaN blow-up — still died dark.
This module is the black box the whole stack reports into (the role
PyTorch's c10d flight recorder plays for NCCL, and the reference's
VLOG-on-crash breadcrumbs played for the fluid runtime):

- :class:`FlightRecorder` — a lock-cheap fixed-capacity ring buffer of
  structured events: executor run begin/end (program id + plan/jit cache
  disposition), ``program_verify`` verdicts (the IR verifier's pass/fail
  per program version, with the offending op/var on failure — so a
  rejected program is in the black box even when the raising process
  dies), every collective call with a **per-group monotonic sequence
  number** and a shape/dtype/reduce-op **fingerprint**, PS RPC
  send/recv, DataLoader epoch/worker lifecycle, flag changes, XLA compile
  events. Dumped to JSON on unhandled exception, on ``SIGUSR1``, and on
  watchdog trip.
- :class:`HangWatchdog` — a daemon thread behind
  ``FLAGS_watchdog_timeout_s`` that fires when no executor step /
  collective / PS reply completes within the deadline, dumping the
  recorder plus every Python thread's stack.
- **Collective desync detection** — on watchdog trip or barrier timeout,
  ranks exchange their per-group (seq, fingerprint) tails over the
  side channel every multi-process fleet run already has (the
  jax.distributed coordination-service KV store that backed the gloo
  rendezvous) and :func:`first_divergence` names the first mismatched
  call per rank — a mismatched ``all_reduce`` stops being a silent
  deadlock and becomes "group dp diverges at seq 41: rank0 issued
  all_reduce|(1024,)|float32|sum, rank1 issued all_gather|...".

- **Stall records** — each hot loop (the serving scheduler's iteration,
  a train step's call-to-call interval) keeps an always-on split of its
  thread's time and leaves one event when a pass stands still
  (``generation_stall``, ``train_stall``): the program, the innermost
  phase, the time over its usual, and whose time it was
  (:class:`PhaseRing`, :class:`Evidence`, :func:`held_by`,
  :class:`StepWatch`).

Recording rides hot paths always-on (``FLAGS_flight_recorder``), so the
per-event cost budget is one flag read, one dict build, and one short
lock hold.
"""
from __future__ import annotations

import collections
import gc
import json
import os
import signal
import statistics
import sys
import tempfile
import threading
import time
import traceback

from ..flags import flag
from ..profiler import add_span as _add_span
from . import tracing as _tracing

__all__ = [
    "FlightRecorder", "HangWatchdog",
    "get_recorder", "record_event", "record_collective", "events",
    "reset_recorder", "dump_now", "default_dump_path",
    "notify_progress", "last_progress_age_s",
    "first_divergence", "exchange_and_diagnose",
    "install", "install_from_flags",
    "start_watchdog", "stop_watchdog", "watchdog",
    "thread_stacks",
    "STALL_NS", "STALL_FACTOR", "PhaseRing", "Evidence", "StepWatch",
    "allocator_stats", "held_among", "record_stall",
]

# per-group collective tail length kept for desync diagnosis — long
# enough to reach back past a divergence that happened many calls before
# anyone hung, bounded so a week-long run holds kilobytes, not gigabytes
_TAIL_LEN = 256

_t0_monotonic = time.monotonic()


def _safe_rank() -> int:
    """Process rank WITHOUT touching the XLA backend (the recorder must
    work inside crash handlers, where initializing jax is off the table)."""
    try:
        return int(os.getenv("PADDLE_TRAINER_ID", os.getenv("RANK", "0")))
    except ValueError:
        return 0


def _safe_world() -> int:
    try:
        return int(os.getenv("PADDLE_TRAINERS_NUM",
                             os.getenv("WORLD_SIZE", "1")))
    except ValueError:
        return 1


def _safe_flags() -> dict:
    try:
        from ..flags import globals_view

        return {k: v for k, v in globals_view().items()}
    except Exception:
        return {}


def thread_stacks() -> dict:
    """Every Python thread's current stack (faulthandler-style, but
    structured): ``{"<name>-<tid>": [frame lines...]}``."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for tid, frame in sys._current_frames().items():
        key = f"{names.get(tid, 'unknown')}-{tid}"
        out[key] = [line.rstrip("\n")
                    for line in traceback.format_stack(frame)]
    return out


class FlightRecorder:
    """Fixed-capacity ring buffer of structured runtime events.

    One lock, held only for the deque append / seq bump — recording is a
    hot-path citizen, reading (snapshot/dump) pays the copies. Events are
    plain dicts with ``i`` (global index — monotonic, so ``dropped`` in a
    snapshot says exactly how much history the ring evicted), ``t``
    (epoch seconds) and ``kind``.
    """

    def __init__(self, capacity=None):
        if capacity is None:
            try:
                capacity = int(flag("flight_recorder_capacity"))
            except Exception:
                capacity = 4096
        self._capacity = max(1, int(capacity))
        self._buf = collections.deque(maxlen=self._capacity)
        self._lock = threading.Lock()
        self._seq = 0
        self._coll_seq = {}   # group -> next per-group collective seq
        self._tails = {}      # group -> deque[(seq, fingerprint)]

    @property
    def enabled(self) -> bool:
        try:
            return bool(flag("flight_recorder"))
        except Exception:
            return True

    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def total_recorded(self) -> int:
        """Monotonic count of events ever recorded (ring eviction does
        not decrement it — matches the dump's ``events_recorded``)."""
        with self._lock:
            return self._seq

    # -- recording -----------------------------------------------------------

    def record(self, kind, **fields):
        """Append one structured event; no-op (None) when disabled.

        Events recorded inside an active trace cite its ``trace_id`` —
        a flight-recorder post-mortem (NaN dump, watchdog trip) can
        name the exact request/step whose trace to pull from
        ``/tracez``, and a trace can be grepped out of a dump."""
        if not self.enabled:
            return None
        ev = {"i": 0, "t": time.time(), "kind": kind}
        ctx = _tracing.current_context()
        if ctx is not None:
            ev["trace_id"] = ctx.trace_id
        ev.update(fields)
        with self._lock:
            ev["i"] = self._seq
            self._seq += 1
            self._buf.append(ev)
        return ev

    def record_collective(self, primitive, group, shape=None, dtype=None,
                          reduce_op=None, traced=False, nbytes=0,
                          sequenced=True):
        """Record one collective call: assigns the group's next monotonic
        sequence number and a ``primitive|shape|dtype|reduce_op``
        fingerprint, and appends both to the group's desync tail.
        Returns the seq (None when disabled).

        Trace-time calls (``traced=True``) and rank-local utilities
        (``sequenced=False`` — e.g. ``wait``, which any single rank may
        legally call alone) land in the event ring but do NOT consume a
        seq or touch the tails: one trace stands for N executions,
        retraces are rank-asymmetric (one rank's jit-cache miss is
        another's hit), and a lone rank timing a step must not read as
        desync. The cross-rank comparison is over *issued* logically-
        collective eager calls only.
        """
        if not self.enabled:
            return None
        shape_s = tuple(int(d) for d in shape) if shape is not None else ()
        fp = f"{primitive}|{shape_s}|{dtype or ''}|{reduce_op or ''}"
        if traced or not sequenced:
            self.record("collective", primitive=primitive, group=group,
                        seq=None, fingerprint=fp, traced=bool(traced),
                        nbytes=int(nbytes))
            return None
        with self._lock:
            seq = self._coll_seq.get(group, 0)
            self._coll_seq[group] = seq + 1
            tail = self._tails.get(group)
            if tail is None:
                tail = self._tails[group] = collections.deque(
                    maxlen=_TAIL_LEN)
            tail.append((seq, fp))
        self.record("collective", primitive=primitive, group=group,
                    seq=seq, fingerprint=fp, traced=False,
                    nbytes=int(nbytes))
        return seq

    # -- reading -------------------------------------------------------------

    def events(self) -> list:
        with self._lock:
            return list(self._buf)

    def collective_tails(self) -> dict:
        """Per-group desync tails: ``{group: [(seq, fingerprint), ...]}``."""
        with self._lock:
            return {g: list(t) for g, t in self._tails.items()}

    def reset(self):
        with self._lock:
            self._buf.clear()
            self._seq = 0
            self._coll_seq.clear()
            self._tails.clear()

    def snapshot(self, reason="snapshot", desync=None) -> dict:
        """The full dump payload as plain data (what every dump trigger
        and the /flightrecorder endpoint serve)."""
        evs = self.events()
        with self._lock:
            total = self._seq
        snap = {
            "reason": reason,
            "time": time.time(),
            "pid": os.getpid(),
            "rank": _safe_rank(),
            "world": _safe_world(),
            "uptime_s": round(time.monotonic() - _t0_monotonic, 3),
            "capacity": self._capacity,
            "events_recorded": total,
            "dropped": max(0, total - len(evs)),
            "events": evs,
            "collective_tails": self.collective_tails(),
            "threads": thread_stacks(),
            "flags": _safe_flags(),
        }
        if desync is not None:
            snap["desync"] = desync
        return snap

    def dump(self, path=None, reason="dump", desync=None) -> str:
        """Write the snapshot as JSON (atomically: tmp + rename, so a
        crash mid-dump never leaves a half-written file that a post-
        mortem tool chokes on). Returns the path."""
        snap = self.snapshot(reason=reason, desync=desync)
        if path is None:
            path = default_dump_path(reason)
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(snap, f, indent=1, default=str)
        os.replace(tmp, path)
        sys.stderr.write(
            f"[flight_recorder] rank {snap['rank']}: dumped "
            f"{len(snap['events'])} events -> {path} (reason: {reason})\n")
        return path


_RECORDER = FlightRecorder()


def get_recorder() -> FlightRecorder:
    return _RECORDER


def record_event(kind, **fields):
    return _RECORDER.record(kind, **fields)


def record_collective(primitive, group, **kwargs):
    return _RECORDER.record_collective(primitive, group, **kwargs)


def events() -> list:
    return _RECORDER.events()


def reset_recorder():
    _RECORDER.reset()


def default_dump_path(reason="dump") -> str:
    """``<FLAGS_flight_recorder_dump_dir or tempdir>/paddle_tpu_flight_
    rank<r>_pid<pid>_<reason-slug>.json`` — rank+pid keyed so every
    process of a fleet world dumps without clobbering peers on a shared
    filesystem, and reason-slug keyed so distinct triggers never
    clobber each other (a barrier-failure dump carrying the desync
    report must survive the excepthook dump the re-raised error writes
    moments later). Same-reason re-dumps (a watchdog re-tripping)
    overwrite in place: latest evidence wins, disk use stays bounded."""
    try:
        d = flag("flight_recorder_dump_dir")
    except Exception:
        d = ""
    d = d or tempfile.gettempdir()
    slug = "".join(c if c.isalnum() or c in "-_" else "_"
                   for c in str(reason))[:48] or "dump"
    return os.path.join(
        d, f"paddle_tpu_flight_rank{_safe_rank()}_pid{os.getpid()}"
           f"_{slug}.json")


def dump_now(reason="request", path=None, desync=None) -> str:
    """Dump the global recorder immediately (the SIGUSR1 handler's body,
    also the programmatic trigger)."""
    return _RECORDER.dump(path=path, reason=reason, desync=desync)


def nan_event_action(where, detail):
    """Shared ``FLAGS_check_nan_inf_action`` policy for every NaN/Inf
    detection site (the executor's post-run scan, the checkify train
    step): validates the flag value, bumps ``debug/nan_events``, records
    the ``nan_inf`` flight event, and performs the non-raising half.

    Returns None when ``action=warn`` consumed the event (the caller
    continues), else the action — the caller must then raise its
    domain-specific error (for ``"dump"`` the snapshot has already been
    written)."""
    from ..flags import flag as _flag

    action = _flag("check_nan_inf_action")
    if action not in ("raise", "warn", "dump"):
        from ..errors import InvalidArgumentError

        raise InvalidArgumentError(
            f"FLAGS_check_nan_inf_action must be raise|warn|dump, "
            f"got {action!r}")
    from . import registry as _registry

    _registry.counter("debug/nan_events").inc()
    record_event("nan_inf", where=str(where), action=action,
                 detail=str(detail)[:300])
    if action == "warn":
        import warnings

        warnings.warn(
            f"check_nan_inf: {detail} (action=warn: continuing; "
            f"debug/nan_events counter bumped)",
            RuntimeWarning, stacklevel=3)
        return None
    if action == "dump":
        dump_now(reason=f"check_nan_inf:{where}")
    return action


# -- stall records: what held a hot loop ------------------------------------
#
# Both hot loops (the serving scheduler's iteration, a train step's
# call-to-call interval) keep an always-on split of their thread's time
# and leave ONE flight event when a pass stands still: ``generation_stall``
# / ``train_stall``. The record names the longest single call or host
# phase of the pass down to its innermost phase (``held_phase``), what
# that phase usually takes, and whose time it was (``held_by``), decided
# from the evidence below by :func:`held_by`. No flag, no thread: the
# loops themselves feed it, one append a call.

# A pass longer than this leaves a record. A constant, not a flag: a
# decode step is tens of milliseconds, a train step a hundred.
STALL_NS = 1_000_000_000
# A held phase has LOST time only where it also took over this many
# times its usual: an iteration of 32 honest admissions passes the
# second and loses nothing.
STALL_FACTOR = 4
# the evidence baseline is read again from the loop at most this often
_REFRESH_NS = 1_000_000_000
_RING = 32

# phases in which the thread waits in native code with the interpreter
# released (or, for a train step's ``outside``, in the caller's code,
# which may): other threads' CPU there is the runtime's own (a load, a
# compile, the CPU backend's compute), not someone holding the interpreter
_RUNTIME_PHASES = ("runtime::launch", "runtime::compile")
_FETCH_SUFFIX = "_fetch"
_OUTSIDE = "outside"


class PhaseRing:
    """What one program's calls (or one loop's host phases) took lately:
    the last instances, each ``(start_ns, phase, ns, nested)`` on
    ``perf_counter_ns``, ``nested`` the innermost phases inside it as
    ``[(name, start_ns, ns)]`` or None. ``runs`` is the owner's count of
    the program's calls. Nothing is reduced until a record is written:
    the hot path pays one append a call."""

    __slots__ = ("name", "runs", "ring")

    def __init__(self, name, length=2 * _RING):
        self.name, self.runs = name, 0
        self.ring = collections.deque(maxlen=length)

    def note(self, phase, t0_ns, ns, nested=None):
        self.ring.append((t0_ns, phase, ns, nested))

    def usual_ns(self, phase, inner, but=None):
        """Median of what ``inner`` (an innermost phase of ``phase``)
        took over the instances kept, ``but`` left out; None where there
        is no other instance."""
        took = [ns for entry in self.ring
                if entry is not but and entry[1] == phase
                for ns, _, name in _innermost(entry) if name == inner]
        return statistics.median(took) if took else None

    def before(self, entry):
        """``(runs before this call, ns since the call before it began)``
        of an instance kept; the second None for a first call."""
        base = entry[1].removesuffix(_FETCH_SUFFIX)
        starts = [e[0] for e in self.ring if e[1] == base]
        later = sum(1 for t in starts if t > entry[0])
        earlier = [t for t in starts if t < entry[0]]
        return (max(self.runs - later - 1, 0),
                entry[0] - max(earlier) if earlier else None)


def _innermost(entry):
    """``(ns, start_ns, name)`` of an instance's innermost phases: the
    nested ones, and what is left of the phase itself."""
    t0, phase, ns, nested = entry
    for name, start, took in nested or ():
        ns -= took
        yield took, start, name
    yield ns, t0, phase


def held_among(entries):
    """Of ``(entry, owner)`` pairs, the instances of one pass: its
    longest single innermost phase as ``(ns, start_ns, name, owner,
    entry)`` (None with no entry), and the nested phases' sums by
    name."""
    held, nested_ns = None, {}
    for entry, owner in entries:
        for ns, start, name in _innermost(entry):
            if name != entry[1]:
                nested_ns[name] = nested_ns.get(name, 0) + ns
            if held is None or ns > held[0]:
                held = (ns, start, name, owner, entry)
    return held, nested_ns


def allocator_stats(device) -> dict:
    """The device allocator's state where the backend reports it (the
    CPU backend reports nothing)."""
    stats = device.memory_stats() or {}
    return {k: int(stats[k]) for k in (
        "bytes_in_use", "bytes_reserved", "largest_free_block_bytes",
        "num_allocs") if k in stats}


# collector pauses, process-wide: ``[total_ns, collections]`` and the
# last few pauses as (end_ns, ns); one gc.callbacks entry, installed by
# the first Evidence
_gc_totals = [0, 0]
_gc_pauses = collections.deque(maxlen=64)
_gc_start = [0]


def _on_gc(phase, info):
    now = time.perf_counter_ns()
    if phase == "start":
        _gc_start[0] = now
    elif _gc_start[0]:
        took = now - _gc_start[0]
        _gc_totals[0] += took
        _gc_totals[1] += 1
        _gc_pauses.append((now, took))


def _first_line_fields(path):
    with open(path) as f:
        return f.readline().split()


def _pressure_us(path):
    # "some avg10=0.00 avg60=0.00 avg300=0.00 total=12345" (microseconds)
    return int(_first_line_fields(path)[-1].partition("=")[2])


class Evidence:
    """Cheap process counters at a baseline and now: whose time a stall
    was. The loop that owns it calls :meth:`refresh` once a pass, which
    reads the baseline again at most once a second (so a stall's evidence
    spans the stall and at most about a second before it: ``evidence_ms``
    says how long); :meth:`since` reads the counters again and returns
    the deltas, when a record is written. Both on the loop's own thread:
    three of the sources are the calling thread's. Between the two
    nothing is read. A source the platform lacks (no ``/proc/pressure``
    in a container, no allocator on the CPU backend, a device that does
    not answer) is left out, never an error.

    ``allocator``: zero-argument callable returning the device
    allocator's fields (:func:`allocator_stats`), or None."""

    def __init__(self, allocator=None):
        self._allocator = allocator
        self._t_ns = 0
        self._base = {}
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)

    def refresh(self, now_ns):
        if now_ns - self._t_ns >= _REFRESH_NS:
            self._base, self._t_ns = self._read(), now_ns

    def _read(self):
        out = {"thread_cpu": time.thread_time_ns() / 1e6,
               "process_cpu": time.process_time_ns() / 1e6,
               "gc": _gc_totals[0] / 1e6, "gc_n": _gc_totals[1]}
        for read in (self._schedstat, self._proc_stat, self._pressure,
                     self._rusage, self._alloc):
            try:
                out.update(read())
            except Exception:  # noqa: BLE001 — a source the platform lacks
                continue
        return out

    @staticmethod
    def _schedstat():
        # "<on-cpu ns> <runnable and not on a cpu, ns> <timeslices>"
        return {"run_delay": int(_first_line_fields(
            "/proc/thread-self/schedstat")[1]) / 1e6}

    @staticmethod
    def _pressure():
        out = {}
        for what in ("cpu", "memory", "io"):
            out[f"pressure_{what}"] = _pressure_us(
                f"/proc/pressure/{what}") / 1e3
        return out

    def _alloc(self):
        return {} if self._allocator is None else {
            "alloc": self._allocator()}

    @staticmethod
    def _proc_stat():
        # "cpu user nice system idle iowait irq softirq steal ...", in
        # clock ticks summed over the CPUs
        f = _first_line_fields("/proc/stat")
        tick_ms = 1e3 / os.sysconf("SC_CLK_TCK")
        return {"iowait": int(f[5]) * tick_ms, "steal": int(f[8]) * tick_ms}

    @staticmethod
    def _rusage():
        import resource

        ru = resource.getrusage(resource.RUSAGE_THREAD)
        return {"nvcsw": ru.ru_nvcsw, "nivcsw": ru.ru_nivcsw}

    def since(self, now_ns) -> dict:
        """The record's evidence fields: each counter's growth since the
        baseline (``*_ms``, the two switch counts, ``gc_n``), the longest
        collector pause in that time, and the allocator's fields now and
        (``*_before``) at the baseline."""
        base, now = self._base, self._read()
        out = {"evidence_ms": round((now_ns - self._t_ns) / 1e6, 3)}
        for key, value in now.items():
            if key == "alloc" or key not in base:
                continue
            name = key if key in ("nvcsw", "nivcsw", "gc_n") else key + "_ms"
            out[name] = round(value - base[key], 3)
        out["gc_max_ms"] = round(max(
            (ns for end, ns in list(_gc_pauses) if end >= self._t_ns),
            default=0) / 1e6, 3)
        out.update(now.get("alloc", {}))
        out.update({k + "_before": v
                    for k, v in base.get("alloc", {}).items()})
        return out


def held_by(phase, held_ms, ev) -> str:
    """Whose time a held phase was, from the evidence of its pass. In
    this order, "most" meaning half of ``held_ms`` or more:

    - ``python``: this thread's own CPU time is most of it: its own
      work, a tracer's, a collection it ran (``gc_ms`` says);
    - ``interpreter``: the process's OTHER threads' CPU time is, and the
      phase runs Python: someone else held the interpreter. Not asked of
      ``runtime::launch`` / ``::compile`` or a ``*_fetch`` (there the
      thread sits in the runtime with the interpreter released, and the
      CPU of the runtime's own threads is part of the call), nor of a
      train step's ``outside`` (the caller's code, which may do the same);
    - ``host``: the machine did not run us: this thread was runnable and
      not on a CPU (``run_delay_ms``), the hypervisor took the CPUs
      (``steal_ms``, a sum over the CPUs, so divided by their number),
      or tasks stalled on memory or I/O (the ``some`` totals; the CPU's
      is recorded and not ruled on: ``run_delay_ms`` is this thread's
      own share of it). Where the platform hides all of these (a
      sandboxed kernel), no stall reads ``host``;
    - otherwise by the phase: ``runtime`` for ``runtime::launch`` /
      ``::compile`` (the executable's call blocked: a load, an
      allocation, a full queue; a compile), ``device`` for a ``*_fetch``
      (the program was still running), ``blocked`` for a host phase (a
      lock, I/O, a stopped process; for ``outside``, whatever the
      caller waited on: in a loop that fetches its loss, the device)."""
    most = 0.5 * held_ms
    fetch = phase.endswith(_FETCH_SUFFIX)
    mine = ev.get("thread_cpu_ms", 0.0)
    if mine >= most:
        return "python"
    if not (fetch or phase in _RUNTIME_PHASES or phase == _OUTSIDE) \
            and ev.get("process_cpu_ms", 0.0) - mine >= most:
        return "interpreter"
    if max(ev.get("run_delay_ms", 0.0),
           ev.get("steal_ms", 0.0) / (os.cpu_count() or 1),
           ev.get("pressure_memory_ms", 0.0),
           ev.get("pressure_io_ms", 0.0)) >= most:
        return "host"
    if phase in _RUNTIME_PHASES:
        return "runtime"
    return "device" if fetch else "blocked"


def record_stall(kind, held, usual_ns, program, runs, idle_ns, evidence,
                 **fields):
    """Leave one stall record: the caller's own fields, then what held
    the pass. ``held`` is ``(ns, start_ns, innermost phase)`` of its
    longest single call or host phase, ``usual_ns`` what that phase
    usually takes (None: nothing to compare with), ``program`` /
    ``runs`` / ``idle_ns`` the program it belongs to, its runs before
    this one and the time since the last began (None for a host phase
    of no program). ``lost_ms`` is ``held_ms - usual_ms`` where the
    phase took over a second and over :data:`STALL_FACTOR` times its
    usual, else 0: the time to sum over records."""
    ns, start_ns, phase = held
    lost = (ns - usual_ns if usual_ns is not None and ns > STALL_NS
            and ns > STALL_FACTOR * usual_ns else 0)
    fields.update(
        t_ns=int(start_ns), held_phase=phase, held_ms=round(ns / 1e6, 3),
        usual_ms=None if usual_ns is None else round(usual_ns / 1e6, 3),
        lost_ms=round(lost / 1e6, 3))
    if program is not None:
        fields.update(
            program=program, program_runs=runs,
            program_idle_s=None if idle_ns is None
            else round(idle_ns / 1e9, 3))
    try:
        ev = evidence.since(time.perf_counter_ns())
    except Exception:  # noqa: BLE001 — the loop must survive its record
        ev = {}
    fields.update(ev)
    fields["held_by"] = held_by(phase, ns / 1e6, ev)
    return record_event(kind, **fields)


class StepWatch:
    """A train step's always-on split of its calls, and its
    ``train_stall`` record. The three ``__call__``s (``TrainStepFn``,
    ``ShardedTrainStep``, ``LocalSGDTrainStep``) bracket themselves with
    :meth:`enter` / :meth:`phase` / :meth:`leave`; ``nested`` is the
    list the compiled-callable runtime times its lookup and launch into.
    A pass is one call-to-call interval, return to return: ``outside``
    (the caller's own time since the last return: its fetch, its data,
    its checkpoint), then the call's phases.

    An interval leaves a record when it passes :data:`STALL_NS` AND
    stands :data:`STALL_FACTOR` times clear of the intervals before it:
    of the longest of the last 32 under a second, and of the median of
    the last 8 over a second that left none. So a loop that fetches its
    loss every tenth step (nine enqueues of milliseconds, then most of
    a second) leaves nothing, however long that tenth interval is, once
    one like it has been seen; the first of them, with only short
    intervals before it, leaves one record if it passes the second. The
    first call (a compile) closes no interval, and an interval with
    none kept before it leaves no record. ``usual_ms`` of the held phase follows the same rule over the
    kept intervals' own values of that phase."""

    def __init__(self, program, device=None):
        self.program = program
        self.runs = 0
        self.nested = None
        self._evidence = Evidence(
            None if device is None else lambda: allocator_stats(device))
        self._t_leave = self._t_enter = self._t_before = 0
        self._phases = None
        self._short = collections.deque(maxlen=_RING)
        self._long = collections.deque(maxlen=8)

    def enter(self):
        """The call begins: ``outside`` ends. Returns the instant."""
        now = time.perf_counter_ns()
        self._t_before, self._t_enter = self._t_enter, now
        self.nested = []
        self._phases = []
        if self._t_leave:
            self._phases.append((self._t_leave, _OUTSIDE,
                                 now - self._t_leave, None))
        return now

    def phase(self, name, t0_ns, nested=None):
        """Close the phase ``name`` begun at ``t0_ns``: a span while the
        profiler is on (one clock read serves both), an entry of the
        split always. Returns the instant."""
        now = time.perf_counter_ns()
        _add_span(name, t0_ns, now)
        self._phases.append((t0_ns, name, now - t0_ns, nested))
        return now

    def leave(self):
        """The call returns: the interval closes, and is judged if it
        passed the second."""
        now = time.perf_counter_ns()
        start, self._t_leave = self._t_leave, now
        self.runs += 1
        if start:  # the first call's compile is no interval
            pass_ = (now - start, self._phases)
            if pass_[0] > STALL_NS:
                self._judge(pass_)
            else:
                self._short.append(pass_)
        self._evidence.refresh(now)

    def _usual(self, took):
        """The rule's "usual" of a quantity ``took(pass)`` over the kept
        intervals; None with nothing kept."""
        usual = max((took(p) for p in self._short), default=0)
        if self._long:
            usual = max(usual, statistics.median(
                took(p) for p in self._long))
        return usual or None

    def _judge(self, pass_):
        interval, phases = pass_
        usual = self._usual(lambda p: p[0])
        if usual is None:
            return  # nothing before it to stand clear of
        if interval <= STALL_FACTOR * usual:
            self._long.append(pass_)
            return
        (ns, t_ns, name, _, _), nested_ns = held_among(
            (entry, None) for entry in phases)
        parts = {}
        for entry in phases:
            parts[entry[1]] = parts.get(entry[1], 0) + entry[2]
        parts["other"] = interval - sum(parts.values())
        record_stall(
            "train_stall", (ns, t_ns, name),
            self._usual(lambda p: sum(
                took for entry in p[1]
                for took, _, inner in _innermost(entry) if inner == name)),
            self.program, self.runs - 1,
            self._t_enter - self._t_before if self._t_before else None,
            self._evidence,
            interval_ms=round(interval / 1e6, 3),
            phases_ms={k: round(v / 1e6, 3) for k, v in parts.items()},
            nested_ms={k: round(v / 1e6, 3) for k, v in nested_ns.items()})


# -- progress clock / hang watchdog ------------------------------------------

# [monotonic time of last completed unit of work, what it was]; written
# by the executor (run end), collectives (eager completion), and the PS
# client (reply received) — two plain stores + one clock read, cheap
# enough to ride every completion unconditionally
_last_progress = [time.monotonic(), "startup"]


def notify_progress(what="step"):
    """Feed the watchdog: some unit of forward progress just completed."""
    _last_progress[0] = time.monotonic()
    _last_progress[1] = what


def last_progress_age_s() -> float:
    return time.monotonic() - _last_progress[0]


def last_progress_what() -> str:
    return _last_progress[1]


class HangWatchdog:
    """Daemon thread that trips when the progress clock goes stale.

    On trip: records a ``watchdog_trip`` event, runs the desync exchange
    (if a multi-process side channel exists), and dumps the recorder —
    thread stacks included, so the dump shows *where* every thread is
    parked, not just that nothing moved. The progress clock is re-armed
    after a trip, so a still-hung process re-dumps once per timeout
    period instead of once per poll.
    """

    def __init__(self, timeout_s, recorder=None, poll_interval=None,
                 desync=True, on_trip=None):
        self.timeout_s = float(timeout_s)
        if self.timeout_s <= 0:
            raise ValueError("watchdog timeout must be > 0 (0 disables the "
                             "watchdog — don't construct one)")
        self._recorder = recorder or _RECORDER
        self._poll = (float(poll_interval) if poll_interval
                      else max(0.05, min(self.timeout_s / 4.0, 5.0)))
        self._desync = desync
        self._on_trip = on_trip
        self._stop = threading.Event()
        self._thread = None
        self.trips = 0
        self.last_dump = None

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self):
        if self.alive:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="ptpu-hang-watchdog", daemon=True)
        self._thread.start()
        self._recorder.record("watchdog_start", timeout_s=self.timeout_s)
        return self

    def stop(self):
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=self._poll * 4 + 1.0)
        self._thread = None

    def _run(self):
        while not self._stop.wait(self._poll):
            age = last_progress_age_s()
            if age < self.timeout_s:
                continue
            try:
                self._trip(age)
            except Exception as e:  # the watchdog must never kill the run
                sys.stderr.write(f"[flight_recorder] watchdog trip handler "
                                 f"failed: {type(e).__name__}: {e}\n")
            notify_progress("watchdog_rearm")

    def _trip(self, age):
        self.trips += 1
        self._recorder.record(
            "watchdog_trip", age_s=round(age, 3),
            timeout_s=self.timeout_s, trips=self.trips,
            last_progress=last_progress_what())
        desync = None
        if self._desync:
            try:
                # STABLE tag: trip counts are rank-local (a transient
                # first-compile trip on one rank would desynchronize
                # per-trip tags forever, stranding later exchanges on
                # mismatched keys). Every rank always publishes/reads
                # "watchdog"; set() overwrites, so a get returns the
                # peer's latest published tail — possibly from an
                # earlier trip, which for a hung peer is exactly the
                # freshest evidence that exists.
                desync = exchange_and_diagnose(
                    tag="watchdog", recorder=self._recorder)
            except Exception as e:
                desync = {"error": f"{type(e).__name__}: {e}"}
        # stable path (reason varies by age digits): a re-tripping
        # watchdog overwrites its own dump — latest evidence, bounded disk
        self.last_dump = self._recorder.dump(
            path=default_dump_path("watchdog_timeout"),
            reason=f"watchdog_timeout({age:.1f}s > {self.timeout_s:g}s, "
                   f"last progress: {last_progress_what()})",
            desync=desync)
        if self._on_trip is not None:
            self._on_trip(self)


_watchdog = [None]


def watchdog() -> HangWatchdog | None:
    return _watchdog[0]


def start_watchdog(timeout_s=None) -> HangWatchdog | None:
    """Start the global watchdog (idempotent). ``timeout_s`` defaults to
    ``FLAGS_watchdog_timeout_s``; <=0 leaves it off and returns None."""
    if timeout_s is None:
        timeout_s = flag("watchdog_timeout_s")
    if not timeout_s or float(timeout_s) <= 0:
        return None
    wd = _watchdog[0]
    if wd is not None and wd.alive:
        return wd
    notify_progress("watchdog_armed")
    wd = HangWatchdog(float(timeout_s))
    wd.start()
    _watchdog[0] = wd
    return wd


def stop_watchdog():
    wd = _watchdog[0]
    if wd is not None:
        wd.stop()
    _watchdog[0] = None


# -- collective desync detection ---------------------------------------------


def first_divergence(tails_by_rank) -> list:
    """Name the first diverging collective call per group.

    ``tails_by_rank``: ``{rank: {group: [(seq, fingerprint), ...]}}`` —
    each rank's per-group tail as exchanged over the side channel.
    Returns one dict per diverging group::

        {"group": "dp", "seq": 41,
         "fingerprints": {"0": "all_reduce|(1024,)|float32|sum",
                          "1": "all_gather|(1024,)|float32|"},
         "summary": "group 'dp' diverges at seq 41: ..."}

    Comparison happens inside the seq window every rank can still see
    (tails are bounded rings) — a seq evicted on one rank is not
    evidence. A missing fingerprint inside the window (``None``) means
    that rank never issued the call: the skipped-collective case. When
    the common window is fingerprint-identical but ranks stopped at
    different seqs, the first seq past the shortest rank is reported as
    a call-count mismatch (the classic "one rank left the loop early").
    """
    ranks = sorted(tails_by_rank)
    groups = sorted({g for tails in tails_by_rank.values() for g in tails})
    out = []
    for g in groups:
        per = {r: {int(s): f for s, f in tails_by_rank[r].get(g, [])}
               for r in ranks}
        starts = [min(m) for m in per.values() if m]
        ends = [max(m) for m in per.values() if m]
        lo = max(starts) if starts else 0
        hi = max(ends) if ends else -1
        shortest = min(ends) if len(ends) == len(ranks) else -1
        div = None
        for s in range(lo, hi + 1):
            fps = {r: per[r].get(s) for r in ranks}
            if len(set(fps.values())) > 1:
                div = {"group": g, "seq": s,
                       "fingerprints": {str(r): fps[r] for r in ranks}}
                if 0 <= shortest < s:
                    div["note"] = ("call-count mismatch: some ranks "
                                   "stopped issuing collectives earlier")
                break
        if div is not None:
            parts = ", ".join(
                f"rank{r}={div['fingerprints'][str(r)] or 'MISSING'}"
                for r in ranks)
            div["summary"] = (
                f"group {g!r} diverges at seq {div['seq']}: {parts}")
            out.append(div)
    return out


class _JaxKVChannel:
    """The jax.distributed coordination-service KV store — the rendezvous
    side channel every multi-process fleet run already holds open (it is
    what replaced the reference's gloo/gen_nccl_id rendezvous), reused
    here as the desync exchange wire. Values are strings; gets block
    until a peer publishes or the timeout lapses."""

    def __init__(self, client):
        self._client = client

    def set(self, key, value):
        # a retried exchange (same barrier token failing twice) must
        # overwrite rather than die before any tails are collected
        self._client.key_value_set(key, value, allow_overwrite=True)

    def get(self, key, timeout_s):
        return self._client.blocking_key_value_get(
            key, int(max(timeout_s, 0.001) * 1000))


def _default_channel():
    # jax exposes is_initialized() but no public handle on the
    # coordination-service client; this is the one private import
    from jax._src import distributed as _dist

    client = _dist.global_state.client
    return _JaxKVChannel(client) if client is not None else None


def exchange_and_diagnose(tag="trip", timeout_s=15.0, channel=None,
                          rank=None, world=None, recorder=None):
    """Exchange collective tails across ranks and diagnose the first
    divergence (c10d-flight-recorder style).

    Publishes this rank's per-group (seq, fingerprint) tail under
    ``ptpu/flight/<tag>/<rank>`` and collects every peer's, then runs
    :func:`first_divergence`. Returns the report dict, or None when
    there is nothing to exchange (single-process world, or no side
    channel — the eager path must stay harmless). Peers that never
    publish within ``timeout_s`` (crashed before their own trip) are
    listed in ``missing_ranks`` rather than failing the diagnosis —
    a dead peer is itself evidence.

    Every rank that trips calls this with the same ``tag`` (the stable
    ``"watchdog"`` tag, a barrier token), so the keyspace lines up
    without extra coordination; publishes overwrite, so a reused tag
    reads each peer's latest published tail.
    """
    recorder = recorder or _RECORDER
    if rank is None:
        rank = _safe_rank()
    if world is None:
        world = _safe_world()
    if world <= 1:
        return None
    channel = channel or _default_channel()
    if channel is None:
        return None
    tails = recorder.collective_tails()
    payload = json.dumps(
        {g: [[s, f] for s, f in t] for g, t in tails.items()})
    try:
        channel.set(f"ptpu/flight/{tag}/{rank}", payload)
    except Exception as e:
        # best-effort: peers may still have published THEIR tails — a
        # one-sided diagnosis beats none
        recorder.record("desync_publish_failed", tag=str(tag),
                        error=f"{type(e).__name__}: {e}"[:200])
    by_rank = {}
    # ONE shared deadline across all peers: a hung fleet must not pay
    # timeout_s per missing rank (world * timeout_s could hold the
    # watchdog's dump hostage for minutes)
    deadline = time.monotonic() + float(timeout_s)

    def _try_get(r, budget_s):
        try:
            raw = channel.get(f"ptpu/flight/{tag}/{r}",
                              max(budget_s, 0.001))
            if isinstance(raw, bytes):
                raw = raw.decode("utf-8")
            by_rank[r] = {g: [(int(s), f) for s, f in t]
                          for g, t in json.loads(raw).items()}
            return True
        except Exception:
            return False

    # two passes: a quick short-slice sweep first, so one dead LOW rank
    # cannot starve reads of higher ranks whose tails are already
    # published (the dead rank is exactly when cross-rank evidence
    # matters most); whatever deadline remains is then split across the
    # stragglers
    stragglers = [r for r in range(world)
                  if not _try_get(r, min(0.25,
                                         deadline - time.monotonic()))]
    for i, r in enumerate(stragglers):
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            break
        _try_get(r, remaining / (len(stragglers) - i))
    missing = sorted(set(range(world)) - set(by_rank))
    divergences = first_divergence(by_rank)
    report = {
        "tag": str(tag),
        "rank": rank,
        "world": world,
        "missing_ranks": missing,
        "divergences": divergences,
        "tails_by_rank": {str(r): {g: [[s, f] for s, f in t]
                                   for g, t in tails.items()}
                          for r, tails in by_rank.items()},
    }
    recorder.record("desync_report", tag=str(tag),
                    divergences=len(divergences),
                    missing_ranks=missing)
    for d in divergences:
        sys.stderr.write(f"[flight_recorder] rank {rank}: DESYNC "
                         f"{d['summary']}\n")
    return report


# -- crash / signal installation ---------------------------------------------

_installed = {"excepthook": False, "signal": False}


def install(excepthook=True, sig=True):
    """Install the dump triggers that need process-global hooks:

    - unhandled exception: chain onto ``sys.excepthook`` — the dump is
      written *before* the traceback prints, so a crash leaves evidence
      even if stderr is lost;
    - ``SIGUSR1``: faulthandler-style on-demand dump of a live process
      (``kill -USR1 <pid>``) — main-thread only (signal module rule).

    Idempotent; both hooks preserve and call whatever was installed
    before them.
    """
    if excepthook and not _installed["excepthook"]:
        prev_hook = sys.excepthook

        def _dump_excepthook(etype, value, tb):
            try:
                _RECORDER.record("unhandled_exception",
                                 type=etype.__name__,
                                 message=str(value)[:500])
                _RECORDER.dump(reason=f"unhandled_exception:{etype.__name__}")
            except Exception:
                pass
            prev_hook(etype, value, tb)

        sys.excepthook = _dump_excepthook
        _installed["excepthook"] = True

    if (sig and not _installed["signal"] and hasattr(signal, "SIGUSR1")
            and threading.current_thread() is threading.main_thread()):
        prev_handler = signal.getsignal(signal.SIGUSR1)

        def _on_sigusr1(signum, frame):
            try:
                dump_now(reason="SIGUSR1")
            except Exception:
                pass
            if callable(prev_handler):
                prev_handler(signum, frame)

        try:
            signal.signal(signal.SIGUSR1, _on_sigusr1)
            _installed["signal"] = True
        except (ValueError, OSError):
            pass
    return _installed


def install_from_flags():
    """One-call wiring of everything the FLAGS ask for — crash/SIGUSR1
    dumps always, the hang watchdog when ``FLAGS_watchdog_timeout_s``>0,
    and the debug server when ``FLAGS_debug_port``>0 (bound at
    port+rank so a multi-process host serves every rank). Called by
    ``init_parallel_env``; safe to call repeatedly."""
    install()
    wd = start_watchdog()
    server = None
    try:
        port = int(flag("debug_port"))
    except Exception:
        port = 0
    if port > 0:
        from .debug_server import start_debug_server

        try:
            server = start_debug_server(port + _safe_rank())
        except OSError as e:
            sys.stderr.write(
                f"[flight_recorder] debug server bind failed on port "
                f"{port + _safe_rank()}: {e}\n")
            _RECORDER.record("debug_server_bind_failed",
                             port=port + _safe_rank(), error=str(e))
    # cluster metrics publisher (rank-0 /clusterz aggregation feed):
    # multi-process worlds only — a lone process IS its own cluster view
    try:
        interval = float(flag("cluster_metrics_interval_s"))
    except Exception:
        interval = 0.0
    if interval > 0 and _safe_world() > 1:
        from . import cluster as _cluster

        try:
            _cluster.start_publisher(interval)
        except Exception as e:
            _RECORDER.record("cluster_publisher_failed",
                             error=f"{type(e).__name__}: {e}"[:200])
    return wd, server

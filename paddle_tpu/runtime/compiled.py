"""The ONE compiled-callable runtime every dispatch site shares.

Before this module, AOT compile, CostRecord capture, LRU caching,
donation-retry discipline, and compile accounting were triplicated
across ``static/executor.py`` (jit-cache entries), ``framework/jit.py``
(``TrainStepFn._exec``), and ``generation/engine.py`` (``_compiled``) —
with per-site drift (executor LRU 128 vs TrainStepFn LRU 16, separate
unexpected-compile counters). TVM's lesson (PAPERS.md, arXiv
1802.04799) is that compilation policy belongs at one choke point;
this is it:

- **Cache key** — any hashable signature the caller derives from its
  avals; the store folds it into a short stable ``cache_key`` string
  (``<label>#<hex>``) that names the SAME identity everywhere: the
  CostRecord ledger, flight-recorder compile/demote events, and trace
  ``annotate()`` dispositions. A /tracez reader, a debug dump, and
  ``/costz`` all cite one id.
- **LRU bound** — ``FLAGS_compiled_cache_capacity`` governs every
  store (one knob, not N hardcoded constants); an eviction bumps
  ``<label>::cache_evict`` so silent recompile churn from an
  undersized cache is visible in the counters.
- **AOT lower+compile** — the same single XLA compile ``jax.jit``'s
  first call would pay, done once per entry under a double-checked
  per-entry lock (N serving workers racing one cold signature pay ONE
  compile) and captured into the cost model so MFU comes from what XLA
  actually built. A site that knows its programs before it runs any
  starts them with :meth:`CompiledStore.precompile`: traced on the
  caller's thread, compiled (or loaded from the persistent cache) on
  a worker thread each, so the programs' compiles overlap each other
  and the caller's next trace.
- **Demote-to-jit** — the AOT executable is stricter than ``jax.jit``
  (aval/layout drift raises ``TypeError``/``ValueError`` where jit
  silently recompiles): such a dispatch demotes the entry to the jit
  path and retries — but NEVER after donation consumed input buffers,
  and the stale CostRecord is dropped so the MFU ledger can't credit
  pre-drift numbers against jit's recompile. Nothing else is caught: a
  lowering or compile error (Mosaic's included) and a device run-time
  error surface with their own message.
"""
from __future__ import annotations

import contextlib
import hashlib
import threading
from concurrent.futures import Future

import jax

from ..flags import flag
from ..profiler import bump_counter, timed_span

__all__ = ["CompiledEntry", "CompiledStore", "CompileWatch",
           "any_deleted", "cache_capacity"]


def cache_capacity() -> int:
    """The shared executable-cache bound (``FLAGS_compiled_cache_capacity``),
    read at insert time so ``set_flags`` applies to live stores."""
    return max(1, int(flag("compiled_cache_capacity")))


def any_deleted(arrays) -> bool:
    """Whether any array's buffer has been consumed (donation): decides
    if a failed AOT dispatch may be retried on the jit fallback path."""
    for a in arrays:
        try:
            if a.is_deleted():
                return True
        except Exception:
            continue
    return False


class _NullCapture:
    """Stand-in when the tuning stack is unavailable: records nothing
    (entries then never schedule-refresh — plain caching)."""

    log: dict = {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _sched_capture():
    """Capture which kernel schedules a trace resolves
    (tuning/schedule.py capture_resolutions) — the per-entry record
    behind precise invalidation: a tuned swap-in rebuilds ONLY the
    signatures that actually baked the changed schedule in, never the
    whole fleet of compiled programs. Exception-safe: a broken tuning
    stack degrades to no capture, never a crash."""
    try:
        from ..tuning.schedule import capture_resolutions

        return capture_resolutions()
    except Exception:
        return _NullCapture()


def _schedules_stale(entry) -> bool:
    """Would any schedule this entry's trace resolved resolve
    DIFFERENTLY now? (Quiet — no tuner counters, no search enqueue.)"""
    rec = entry.resolved_schedules
    if not rec:
        return False  # resolved nothing (or not traced yet): immune
    try:
        from ..tuning.schedule import resolutions_stale

        return resolutions_stale(rec)
    except Exception:
        return False


class CompiledEntry:
    """One compiled program: the ``jax.jit`` callable plus its AOT slot.

    ``meta`` carries whatever the call site attached at build time
    (e.g. the executor's donate/hold name tuples). ``lock`` serializes
    the one-time AOT compile; ``attempted`` is the double-check."""

    __slots__ = ("sig", "cache_key", "jitted", "meta", "aot", "record",
                 "attempted", "lock", "resolved_schedules", "refresh_gen")

    def __init__(self, sig, cache_key, jitted, meta, refresh_gen=0):
        self.sig = sig
        self.cache_key = cache_key
        self.jitted = jitted
        self.meta = meta
        self.aot = None
        self.record = None
        self.attempted = False
        self.lock = threading.Lock()
        # which kernel schedules the trace resolved (captured at first
        # lower/dispatch): the precise-invalidation record — None until
        # traced, {} if the program resolves no tuned kernel
        self.resolved_schedules = None
        # bumps each time this signature is rebuilt for a schedule
        # swap, so the refreshed compile gets a NEW cost identity
        self.refresh_gen = refresh_gen


class CompiledStore:
    """LRU cache of :class:`CompiledEntry` + the dispatch discipline.

    ``label`` prefixes counters and cache keys; ``cost_label`` is the
    CostRecord label (``cost_model.latest_record(cost_label)``).
    ``hit_counter``/``miss_counter`` are optional profiler counter names
    bumped on lookup (the executor keeps its historical
    ``executor::jit_cache_hit/miss`` names through these; generation
    routes its ``generation::compile`` count through ``miss_counter``).
    ``capacity`` overrides the flag-governed bound (tests only).
    """

    def __init__(self, label, *, cost_label=None, capacity=None,
                 hit_counter=None, miss_counter=None):
        self.label = label
        self.cost_label = cost_label or label
        self._capacity = capacity
        self._hit_counter = hit_counter
        self._miss_counter = miss_counter
        self._entries: dict = {}
        self._lock = threading.Lock()

    # -- cache -------------------------------------------------------------

    @property
    def capacity(self) -> int:
        return (self._capacity if self._capacity is not None
                else cache_capacity())

    @capacity.setter
    def capacity(self, value):
        self._capacity = None if value is None else int(value)

    def __len__(self):
        return len(self._entries)

    def entries(self) -> dict:
        """Snapshot of sig -> CompiledEntry (insertion = LRU order)."""
        with self._lock:
            return dict(self._entries)

    def mapping(self) -> "EntriesView":
        """A LIVE mutable view over the cache (``clear``/``del`` force
        recompiles on the next lookup) — the legacy ``Executor._cache``
        surface."""
        return EntriesView(self)

    def drop(self, sig):
        """Invalidate one signature (next lookup recompiles)."""
        with self._lock:
            return self._entries.pop(sig, None)

    def clear(self):
        with self._lock:
            self._entries.clear()

    def _key_of(self, sig, refresh_gen=0) -> str:
        ident = sig if refresh_gen == 0 else (sig, refresh_gen)
        h = hashlib.sha1(repr(ident).encode()).hexdigest()[:10]
        return f"{self.label}#{h}"

    def get_or_build(self, sig, build, nested=None):
        """Look up (or build) the entry for ``sig``.

        ``build()`` -> ``(jitted_callable, meta)`` runs under the store
        lock on a miss (entry creation must be atomic so two threads
        racing a cold signature share ONE entry — the per-entry lock
        then serializes the actual XLA compile). Returns
        ``(entry, "hit" | "miss")``.

        Kernel-autotuner coupling: each entry records which schedules
        its trace resolved; when any of them would resolve differently
        NOW (a tuned swap-in, a ``FLAGS_kernel_autotune`` flip), the
        entry is invalidated here — counted as
        ``<label>::schedule_refresh`` — so the swap is a clean
        recompile, never a stale trace. Signatures that resolve no
        tuned kernel are immune (no fleet-wide recompile waves).

        The lookup is the ``runtime::lookup`` span, nested inside
        whatever span the dispatch site holds; its two clock reads also
        go to ``nested``, the caller's always-on list of the innermost
        phases of its call (:class:`profiler.timed_span`).
        """
        with timed_span("runtime::lookup", nested), self._lock:
            entry = self._entries.get(sig)
            refresh_gen = 0
            if entry is not None and _schedules_stale(entry):
                self._entries.pop(sig)
                refresh_gen = entry.refresh_gen + 1
                bump_counter(f"{self.label}::schedule_refresh")
                _flight().record_event(
                    "runtime_schedule_refresh", label=self.label,
                    cache_key=entry.cache_key)
                entry = None
            if entry is not None:
                self._entries[sig] = self._entries.pop(sig)  # refresh LRU
                if self._hit_counter:
                    bump_counter(self._hit_counter)
                return entry, "hit"
            if self._miss_counter:
                bump_counter(self._miss_counter)
            jitted, meta = build()
            entry = CompiledEntry(sig, self._key_of(sig, refresh_gen),
                                  jitted, meta, refresh_gen=refresh_gen)
            self._entries[sig] = entry
            cap = self.capacity
            while len(self._entries) > cap:
                evicted = self._entries.pop(next(iter(self._entries)))
                # an eviction means the NEXT dispatch of that signature
                # recompiles: silent churn from an undersized cache must
                # show in the counters (FLAGS_compiled_cache_capacity is
                # the knob)
                bump_counter(f"{self.label}::cache_evict")
                _flight().record_event(
                    "runtime_cache_evict", label=self.label,
                    cache_key=evicted.cache_key, capacity=cap)
        return entry, "miss"

    # -- dispatch ----------------------------------------------------------

    def _aot_compile(self, entry, args, capture_meta):
        """One-time AOT lower+compile (the same work jax.jit's first
        call would do) so the compiled module's own cost_analysis /
        memory_analysis land in the cost-model registry — utilization
        from what XLA actually built, not an estimate. Double-checked
        under the per-entry lock: a second worker on the same cold
        signature, or the first dispatch of a precompiled
        one, waits for the executable instead of recompiling, and that
        wait is this thread's compile time in the goodput ledger. A
        lowering or compile error propagates from here with its
        message; the entry stays unattempted."""
        from ..monitor import goodput as _goodput

        with _goodput.span("compile"):
            entry.lock.acquire()
        try:
            if not entry.attempted:
                self._compile(entry, self._lower(entry, args), capture_meta,
                              _goodput.span("compile"))
        finally:
            entry.lock.release()

    def _lower(self, entry, args):
        """Trace and lower (entry's lock held). Tracing swaps live
        module state and is not reentrant: the dispatching thread's
        work."""
        from ..monitor import goodput as _goodput

        # trace + XLA compile are badput in the goodput ledger's
        # taxonomy: a span here covers the trace, :meth:`_compile`'s
        # caller brings the one for the compile, and the ledger deducts
        # both from the enclosing step frame's compute. The
        # named_scope prefixes every op stamp the traced function
        # emits (executor._exec_one's opprof stamps) with this
        # store's label, so a device-trace row reads
        # executor/matmul#0/3/... and attribution can tell which
        # runtime (executor, serving replica, ...) issued the op.
        with _goodput.span("compile"), _sched_capture() as cap, \
                jax.named_scope(self.label):
            lowered = entry.jitted.lower(*args)
        # the trace just ran: record the schedules it baked in
        entry.resolved_schedules = dict(cap.log or {})
        return lowered

    def _compile(self, entry, lowered, capture_meta, span):
        """XLA compile (or the persistent cache's load) under ``span``,
        then the cost capture (entry's lock held); any thread's work."""
        from ..monitor import cost_model as _cost

        with span:
            entry.aot = lowered.compile()
        entry.record = _cost.capture(
            self.cost_label, lowered=lowered, compiled=entry.aot,
            key=entry.cache_key, cache_key=entry.cache_key,
            **(capture_meta or {}))
        _flight().record_event(
            "runtime_compile", label=self.label,
            cache_key=entry.cache_key,
            flops=entry.record.flops if entry.record else 0.0)
        entry.attempted = True

    def precompile(self, sig, build, args, capture_meta=None):
        """Start ``sig``'s one-time compile ahead of its first dispatch
        and return a future that is done when the executable is there.

        Lookup (a miss is counted as any other), trace and lowering
        happen here, on the caller's thread: tracing swaps live module
        state and is not reentrant. The compile or cache load and the
        cost capture run on a worker thread of their own, which holds
        the entry's lock until it is done, so the first :meth:`dispatch`
        waits for the executable and compiles nothing. A compile that
        fails leaves the entry unattempted: the future is still done,
        and that dispatch compiles again and raises on its own thread.
        ``args`` are only traced, never run or consumed.

        The worker books nothing in the goodput ledger: that ledger
        counts wall time once, and several programs compile at once.
        What the compile costs the caller is its wait for the entry's
        lock at the first dispatch, booked there.

        A thread per program (a site has as many as it has programs):
        on the v5e the runtime loads one executable at a time however
        many threads ask, and a lone worker's loads took twice as long
        as the same loads made from several threads or from the
        caller's own (one chip call's timing; PERF.md, PR 26)."""
        entry, _ = self.get_or_build(sig, build)
        done = Future()
        entry.lock.acquire()
        if entry.attempted:
            entry.lock.release()
            done.set_result(entry)
            return done

        def work():
            try:
                self._compile(entry, lowered, capture_meta,
                              contextlib.nullcontext())
            except Exception:  # noqa: BLE001 — the dispatch raises it
                entry.aot = entry.record = None
            finally:
                entry.lock.release()
                done.set_result(entry)

        try:
            lowered = self._lower(entry, args)
            threading.Thread(target=work, daemon=True,
                             name=f"precompile-{entry.cache_key}").start()
        except BaseException:
            entry.lock.release()
            raise
        return done

    def dispatch(self, entry, *args, donated=(), capture_meta=None,
                 nested=None):
        """Run one compiled call through the shared discipline.

        ``donated`` names the arrays whose buffers the call may consume
        (sequence, or a zero-arg callable evaluated only on failure):
        the demote-to-jit retry is forbidden once any is consumed.
        Annotates the current trace span with the entry's ``cache_key``
        (+ FLOPs when captured) and feeds the executed-work ledger.
        ``nested`` as in :meth:`get_or_build`: the launch, and a first
        dispatch's compile (or its wait for one), are timed into it.
        """
        from ..monitor import cost_model as _cost
        from ..monitor import tracing as _tracing

        if not entry.attempted:
            with timed_span("runtime::compile", nested):
                self._aot_compile(entry, args, capture_meta)
        runner = entry.aot if entry.aot is not None else entry.jitted
        try:
            # the enqueue alone: returns before the device finishes
            with timed_span("runtime::launch", nested):
                out = runner(*args)
        except (TypeError, ValueError):
            # what a Compiled raises for avals / shardings it was not
            # built for; anything else (a device error) is not drift
            consumed = donated() if callable(donated) else donated
            if runner is entry.jitted or any_deleted(consumed):
                raise
            # demote: jax.jit recompiles for the drifted avals; the
            # captured record no longer describes what runs, so drop it
            # (crediting it would silently corrupt the MFU ledger)
            entry.aot = None
            entry.record = None
            bump_counter(f"{self.label}::aot_demote")
            _flight().record_event(
                "runtime_demote", label=self.label,
                cache_key=entry.cache_key)
            out = entry.jitted(*args)
        _cost.note_run(entry.record)
        if entry.record is not None:
            # the cost sheet makes the trace self-contained: a /tracez
            # reader sees what the dispatch COST under the same identity
            # the CostRecord ledger uses
            _tracing.annotate(cache_key=entry.cache_key,
                              flops=entry.record.flops,
                              cost_bytes=entry.record.bytes_accessed)
        else:
            _tracing.annotate(cache_key=entry.cache_key)
        return out


class EntriesView:
    """Live dict-like view over a store's entries. Reads see current
    state; ``clear()``/``del view[sig]``/``pop`` invalidate entries in
    the REAL cache (the next lookup recompiles) — preserving the
    mutation semantics the pre-runtime ``Executor._cache`` dict had."""

    __slots__ = ("_store",)

    def __init__(self, store):
        self._store = store

    def _snap(self):
        return self._store.entries()

    def __len__(self):
        return len(self._store)

    def __iter__(self):
        return iter(self._snap())

    def __contains__(self, sig):
        return sig in self._snap()

    def __getitem__(self, sig):
        entry = self._snap().get(sig)
        if entry is None:
            raise KeyError(sig)
        return entry

    def __delitem__(self, sig):
        if self._store.drop(sig) is None:
            raise KeyError(sig)

    def get(self, sig, default=None):
        return self._snap().get(sig, default)

    def pop(self, sig, *default):
        entry = self._store.drop(sig)
        if entry is None:
            if default:
                return default[0]
            raise KeyError(sig)
        return entry

    def clear(self):
        self._store.clear()

    def keys(self):
        return self._snap().keys()

    def values(self):
        return self._snap().values()

    def items(self):
        return self._snap().items()

    def __repr__(self):
        return f"EntriesView({self._snap()!r})"


def _flight():
    # lazy: the monitor package imports flags early in bootstrap; this
    # module must stay importable before monitor finishes initializing
    from ..monitor import flight_recorder

    return flight_recorder


class CompileWatch:
    """Warmup-snapshot compile accounting (serving pool, generation
    engine, and any future steady-state-bounded dispatch site).

    ``arm()`` after warmup snapshots a compile counter (read through
    ``read``); any later growth is an UNEXPECTED compile — the bounded-
    compile invariant broke — counted loudly into ``metric`` plus a
    flight-recorder event instead of silently re-growing the cache.
    ``note()`` is an atomic read-compare-bump: N workers may observe the
    same miss concurrently and it must count once.
    """

    def __init__(self, read, metric="serving/unexpected_compiles",
                 event="serving_unexpected_compile"):
        from ..monitor import counter

        self._read = read
        self._event = event
        self._baseline = None
        self._seen = 0
        self._metric = counter(metric)
        self._lock = threading.Lock()

    def arm(self):
        self._baseline = self._read()
        self._seen = 0
        return self

    @property
    def armed(self) -> bool:
        return self._baseline is not None

    def extra(self) -> int:
        """Compiles since ``arm()`` — steady state must keep this 0."""
        if self._baseline is None:
            from ..errors import PreconditionNotMetError

            raise PreconditionNotMetError(
                "extra_compiles() before warmup(): nothing to compare")
        return self._read() - self._baseline

    def note(self, **fields):
        """Record any NEW growth since the last note (no-op when flat)."""
        with self._lock:
            extra = self.extra()
            grew = extra - self._seen
            if grew <= 0:
                return
            self._seen = extra
            self._metric.inc(grew)
            _flight().record_event(self._event, total=extra, **fields)

"""Continuous batching: slot-turnover scheduling over a GenerationEngine.

The dynamic batcher (``batcher.py``) assembles a batch, dispatches it,
and TEARS IT DOWN — right for single-call predictors, ruinous for
autoregressive decoding where co-batched sequences finish at different
times: the batch would run at the pace of its longest member while
finished slots burn compute on garbage.

Here the batch never tears down. The compiled decode step always runs
all ``engine.slots`` rows; a sequence that hits EOS or its token budget
VACATES its slot mid-batch, and the next queued request is admitted into
the vacant slot at the very next step (a prefill + one functional
indexed cache write — no recompile, the decode program's shapes are slot
-count-static). Under mixed-length traffic the slots stay full, which is
where the throughput comes from.

Admission reuses the serving queue contracts: bounded queue with
:class:`QueueFullError` backpressure (HTTP 429), deadlines that expire
queued requests WITHOUT dispatch, :class:`ServingClosedError` after
close, and graceful drain. Compile accounting reuses
:class:`replica.CompileWatch` over the ``generation::compile`` counter —
steady state is exactly 1 decode + len(prefill ladder) programs, any
growth bumps ``serving/gen_unexpected_compiles`` + a flight event.

Per-token streaming: pass ``on_token`` to :meth:`submit` and every
sampled token is delivered as it is decoded (the HTTP ``/generate``
endpoint's streaming mode rides this).

The loop runs one decode step ahead of what it has delivered, where the
engine allows it (``engine.steps_ahead``): step n+1 is enqueued from
step n's tokens on the device before step n is fetched and delivered,
so the host's work an iteration costs the device nothing. A request
that ends on EOS at step n has a row in step n+1 already: computed in
vain, never delivered. An admission drains the look-ahead (its first
token is on the host): see :meth:`ContinuousBatcher._loop`.

An admission has one of two units of work. Where the engine can take a
prompt up again from the rows its slot holds
(``engine.begin_admission``), a long prompt goes in a CHUNK an iteration,
the live slots' decode step between two chunks, so a stream's gap is a
step and a chunk where it was a step and the whole prompt; a chunk that
is not the last gives the host nothing and the look-ahead stays; the
last one's token is waited for after the step in flight is delivered.
One prompt is in its chunks at a time, first come first served.
Everywhere else the unit is the whole prompt, as it always was.
"""
from __future__ import annotations

import threading
import time
from collections import deque

from ..errors import InvalidArgumentError
from ..flags import flag
from ..generation.cache import CacheLostError
from ..generation.handoff import PageSlab
from ..monitor import counter, histogram
from ..monitor import flight_recorder as _flight
from ..monitor import tracing as _tracing
from .. import profiler as _profiler
from .batcher import (
    DeadlineExceededError,
    QueueFullError,
    ServingClosedError,
)

__all__ = ["ContinuousBatcher", "GenerationRequest"]

# One loop iteration (pick through deliver, idle wait excluded) longer
# than this leaves a ``generation_stall`` flight event with its split
# and what held it. The one constant both hot loops share (the factor a
# held call must stand over its usual to have lost time is beside it).
_STALL_NS = _flight.STALL_NS


class GenerationRequest:
    """One submitted generation: a token prompt (or a handed-off KV
    slab standing in for one), its budget and sampling override, the
    tokens produced so far, and a completion event."""

    __slots__ = ("prompt", "prompt_len", "max_new_tokens", "temperature",
                 "deadline", "t_submit", "t_first_token", "tokens",
                 "finish_reason", "on_token", "error", "trace",
                 "handoff", "tenant", "_done")

    def __init__(self, prompt, max_new_tokens, temperature, deadline,
                 t_submit, on_token=None, handoff=None, prompt_len=None,
                 tenant=None):
        self.prompt = prompt
        # a disaggregated admission knows the prompt LENGTH (slab
        # metadata) even when the tokens themselves did not ride along
        self.prompt_len = (len(prompt) if prompt_len is None
                           else int(prompt_len))
        # (planes, length, first_token) from generation.handoff — the
        # admission path becomes insert_slot_kv instead of a prefill
        self.handoff = handoff
        # the submitter's trace context (the HTTP handler's server
        # span): queue-wait / slot-admission / decode spans recorded by
        # the decode-loop thread hang under it
        self.trace = _tracing.current_context()
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.deadline = deadline  # absolute monotonic seconds, or None
        self.t_submit = t_submit
        # label dimension on the per-request latency histograms
        self.tenant = "default" if tenant is None else str(tenant)
        self.t_first_token = None
        self.tokens = []
        self.finish_reason = None  # "eos" | "length" | None
        self.on_token = on_token
        self.error = None
        self._done = threading.Event()

    def expired(self, now) -> bool:
        return self.deadline is not None and now > self.deadline

    def done(self, error=None):
        self.error = error
        self._done.set()

    @property
    def finished(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout=None):
        """Block until generation completes; returns the token list or
        raises the stored error."""
        if not self._done.wait(timeout):
            from ..errors import ExecutionTimeoutError

            raise ExecutionTimeoutError(
                f"generation not completed within {timeout}s")
        if self.error is not None:
            raise self.error
        return self.tokens


class _Chunked:
    """The request whose prompt is in its chunks: where it will sit,
    whether other slots were live when it was picked, its admission span
    and the engine's handle."""

    __slots__ = ("req", "slot", "midbatch", "span", "adm")

    def __init__(self, req, slot, midbatch, span, adm):
        self.req, self.slot, self.midbatch = req, slot, midbatch
        self.span, self.adm = span, adm


class _Flight:
    """One decode step between its enqueue and its delivery: the
    requests that sat in the slots when it was enqueued, when that was,
    the engine's handle while the tokens are on the device (``step``),
    and the tokens once fetched: ``rows [S, n]`` with, for a
    speculative round, ``counts [S]`` of how many of a row count."""

    __slots__ = ("seated", "t0_ns", "step", "rows", "counts")

    def __init__(self, seated, t0_ns):
        self.seated, self.t0_ns = seated, t0_ns
        self.step = self.rows = self.counts = None


class ContinuousBatcher:
    """Slot scheduler + decode-loop worker over one GenerationEngine.
    The loop keeps ``engine.steps_ahead`` decode steps enqueued beyond
    the one it fetches (1 for a ring layout without a draft model, else
    0): no flag, the engine's own properties decide."""

    def __init__(self, engine, queue_capacity=None, clock=time.monotonic,
                 kind="generate"):
        self.engine = engine
        # the backend's fleet role ("generate" | "decode" | ...): label
        # dimension on every latency series this scheduler observes
        self.kind = str(kind)
        self.queue_capacity = int(
            queue_capacity if queue_capacity is not None
            else flag("generation_queue_capacity"))
        if self.queue_capacity <= 0:
            raise InvalidArgumentError(
                f"generation queue capacity must be positive, got "
                f"{self.queue_capacity}")
        self._clock = clock
        self._q = deque()
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._closed = False
        self._drain = True
        self._thread = None
        s = engine.slots
        self._slots = [None] * s           # slot -> GenerationRequest
        import numpy as np

        self._last = np.zeros(s, np.int32)
        self._temps = np.zeros(s, np.float32)
        # the loop thread's phase clock (perf_counter_ns, the profiler's):
        # where its current phase and its current iteration began, and
        # the ns each phase took in this iteration
        self._t_ns = self._t_iter_ns = 0
        self._split = {}
        # the loop's own phases as single instances (the engine keeps its
        # calls' by program), and the evidence of whose time a stall was:
        # made by the loop thread, whose counters it reads
        self._host = _flight.PhaseRing("serving", 128)
        self._evidence = None
        # the decode step that is enqueued and not fetched (the loop
        # thread's own), and when the last step's tokens were delivered
        self._flight = None
        self._t_landed_ns = 0
        # the request whose prompt is in its chunks (the loop thread's
        # own; its slot is spoken for though nobody sits in it yet)
        self._chunked = None
        # the engine owns the warmup-snapshot watch (armed by warmup());
        # the loop notes growth through it after every step
        self._watch = engine.watch
        # metrics (get-or-create; shared across scheduler rebuilds)
        self._m_requests = counter("serving/gen_requests_total")
        self._m_responses = counter("serving/gen_responses_total")
        self._m_rejected = counter("serving/gen_rejected_total")
        self._m_expired = counter("serving/gen_expired_total")
        self._m_errors = counter("serving/gen_errors_total")
        self._m_tokens = counter("serving/gen_tokens_total")
        self._m_midbatch = counter("serving/gen_midbatch_admissions_total")
        self._h_token = histogram("serving/gen_token_ms")
        self._h_ttft = histogram("serving/gen_ttft_ms")
        self._h_e2e = histogram("serving/gen_e2e_ms")
        from . import _register_live

        _register_live(self)

    # -- client side ---------------------------------------------------------

    @property
    def closed(self) -> bool:
        return self._closed

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._q)

    @property
    def live_slots(self) -> int:
        return sum(r is not None for r in self._slots)

    def occupancy(self) -> float:
        return self.live_slots / self.engine.slots

    def extra_compiles(self) -> int:
        return self.engine.extra_compiles()

    def submit(self, prompt, max_new_tokens=None, temperature=None,
               deadline_ms=None, on_token=None,
               tenant=None) -> GenerationRequest:
        """Enqueue one generation request. Validation happens at
        ADMISSION TIME here (a malformed prompt must be rejected before
        it can occupy a decode slot); a full queue raises
        :class:`QueueFullError` (HTTP 429)."""
        prompt = [int(t) for t in prompt]
        max_new = (self.engine.default_max_new_tokens
                   if max_new_tokens is None else int(max_new_tokens))
        self.engine.validate(prompt, max_new)
        now = self._clock()
        deadline = (now + float(deadline_ms) / 1e3
                    if deadline_ms is not None and float(deadline_ms) > 0
                    else None)
        req = GenerationRequest(prompt, max_new, temperature, deadline,
                                now, on_token=on_token, tenant=tenant)
        return self._enqueue(req)

    def _enqueue(self, req) -> GenerationRequest:
        """The one admission gate both submit paths share: closed
        check, bounded-queue backpressure (429), enqueue + notify."""
        with self._lock:
            if self._closed:
                raise ServingClosedError(
                    "generation scheduler is shut down; no new requests")
            if len(self._q) >= self.queue_capacity:
                self._m_rejected.inc()
                _flight.record_event(
                    "generation_reject", reason="queue_full",
                    depth=len(self._q), capacity=self.queue_capacity)
                raise QueueFullError(
                    f"generation queue full ({self.queue_capacity} "
                    "requests queued); backpressure — retry with backoff")
            self._q.append(req)
            self._not_empty.notify()
        self._m_requests.inc()
        return req

    def submit_prefilled(self, planes, length, first_token,
                         max_new_tokens=None, temperature=None,
                         deadline_ms=None, on_token=None,
                         prompt=None, tenant=None) -> GenerationRequest:
        """Enqueue a handed-off generation: the prompt was prefilled on
        a PREFILL-tier backend and arrives as a KV slab (window-width
        per-slot planes + true length + the first sampled token).
        Admission becomes a single functional cache insert instead of a
        prefill forward; everything downstream (queue contracts,
        deadlines, streaming, completion) is the normal request path.
        ``prompt`` (the token ids) is required by speculative engines —
        the draft ring must be prefilled at admission."""
        length = int(length)
        if not 1 <= length <= self.engine.cache_len:
            raise InvalidArgumentError(
                f"handoff prompt length {length} outside "
                f"[1, {self.engine.cache_len}]")
        max_new = (self.engine.default_max_new_tokens
                   if max_new_tokens is None else int(max_new_tokens))
        if max_new < 1:
            raise InvalidArgumentError(
                f"max_new_tokens must be >= 1, got {max_new}")
        if length + max_new > self.engine.max_positions:
            raise InvalidArgumentError(
                f"prompt ({length}) + max_new_tokens ({max_new}) "
                f"exceeds max_position_embeddings "
                f"{self.engine.max_positions}")
        if self.engine.speculative and prompt is None:
            raise InvalidArgumentError(
                "a speculative decode tier needs the prompt tokens with "
                "the KV slab (draft ring prefill at admission)")
        now = self._clock()
        deadline = (now + float(deadline_ms) / 1e3
                    if deadline_ms is not None and float(deadline_ms) > 0
                    else None)
        req = GenerationRequest(
            prompt, max_new, temperature, deadline, now,
            on_token=on_token, prompt_len=length,
            handoff=(planes, length, int(first_token)), tenant=tenant)
        return self._enqueue(req)

    def submit_prefilled_pages(self, slab: PageSlab, max_new_tokens=None,
                               temperature=None, deadline_ms=None,
                               on_token=None, tenant=None,
                               prompt=None) -> GenerationRequest:
        """Enqueue a PAGE-GRANULAR handoff (``handoff.PageSlab``): the
        prefill tier shipped only the pages this decode tier's prefix
        index does not already hold; admission maps known pages
        copy-on-write and installs the shipped ones into freshly
        allocated pool pages. Requires ``kv_cache_layout=paged``."""
        if not getattr(self.engine, "paged", False):
            raise InvalidArgumentError(
                "page-granular handoff needs kv_cache_layout=paged on "
                "the decode tier")
        length = int(slab.length)
        if not 1 <= length <= self.engine.cache_len:
            raise InvalidArgumentError(
                f"handoff prompt length {length} outside "
                f"[1, {self.engine.cache_len}]")
        max_new = (self.engine.default_max_new_tokens
                   if max_new_tokens is None else int(max_new_tokens))
        if max_new < 1:
            raise InvalidArgumentError(
                f"max_new_tokens must be >= 1, got {max_new}")
        if length + max_new > self.engine.max_positions:
            raise InvalidArgumentError(
                f"prompt ({length}) + max_new_tokens ({max_new}) "
                f"exceeds max_position_embeddings "
                f"{self.engine.max_positions}")
        now = self._clock()
        deadline = (now + float(deadline_ms) / 1e3
                    if deadline_ms is not None and float(deadline_ms) > 0
                    else None)
        req = GenerationRequest(
            prompt, max_new, temperature, deadline, now,
            on_token=on_token, prompt_len=length, handoff=slab,
            tenant=tenant)
        return self._enqueue(req)

    def generate(self, prompt, max_new_tokens=None, temperature=None,
                 timeout=None) -> list:
        """Synchronous convenience: submit + wait."""
        return self.submit(prompt, max_new_tokens, temperature).wait(timeout)

    # -- decode loop ---------------------------------------------------------

    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return self
        self._thread = threading.Thread(
            target=self._loop, name="ptpu-generation-decode", daemon=True)
        self._thread.start()
        return self

    def _pop_expired_locked(self, now):
        while self._q and self._q[0].expired(now):
            req = self._q.popleft()
            self._m_expired.inc()
            _flight.record_event(
                "generation_deadline_expired",
                queued_ms=round((now - req.t_submit) * 1e3, 3))
            # queue-wait is this request's whole story: record it
            # errored and flag the trace — a deadline miss is never the
            # trace the tail sampler drops
            _tracing.record_interval(
                "serving::queue_wait", req.trace, req.t_submit, now,
                error="deadline exceeded in queue",
                prompt_tokens=req.prompt_len)
            _tracing.flag_trace(req.trace, "deadline")
            req.done(error=DeadlineExceededError(
                f"generation deadline passed after "
                f"{(now - req.t_submit) * 1e3:.1f}ms in queue; "
                "never admitted to a slot"))

    def _finished_reason(self, req):
        if (self.engine.eos_id is not None
                and req.tokens and req.tokens[-1] == self.engine.eos_id):
            return "eos"
        if len(req.tokens) >= req.max_new_tokens:
            return "length"
        return None

    def _deliver(self, req, tok):
        req.tokens.append(int(tok))
        self._m_tokens.inc()
        if req.on_token is not None:
            try:
                req.on_token(int(tok))
            except Exception:  # a slow/broken stream must not stall decode
                req.on_token = None

    def _complete(self, req, reason):
        req.finish_reason = reason
        now = self._clock()
        # one decode span per REQUEST (first token -> finish), not per
        # token: a long generation must not eat the trace's span budget
        _tracing.record_interval(
            "serving::decode", req.trace,
            req.t_first_token if req.t_first_token is not None
            else req.t_submit,
            now, tokens=len(req.tokens), finish_reason=reason)
        # labeled observe: the child propagates into the bare family,
        # so /histz merges keep exact totals while /metricz gains the
        # per-kind/per-tenant series
        self._h_e2e.labels(kind=self.kind, tenant=req.tenant).observe(
            (now - req.t_submit) * 1e3)
        self._m_responses.inc()
        _flight.record_event(
            "generation_complete", reason=reason,
            prompt_tokens=req.prompt_len, tokens=len(req.tokens))
        req.done()

    def _mark(self, name):
        """Close the loop thread's current phase at this instant as span
        ``name``; the next phase begins here. One clock read serves the
        profiler (a span, when it is on) and the iteration's split
        (always: the stall record needs it)."""
        now = time.perf_counter_ns()
        _profiler.add_span(name, self._t_ns, now)
        self._split[name] = self._split.get(name, 0) + now - self._t_ns
        self._host.note(name, self._t_ns, now - self._t_ns)
        self._t_ns = now

    def _pick(self):
        """Expire what waited too long, then take the queue's head if a
        slot is vacant and the engine has room for it. Returns
        ``(request, slot, midbatch, admission span)`` or None."""
        engine = self.engine
        with self._lock:
            now = self._clock()
            self._pop_expired_locked(now)
            if not self._q:
                return None
            free = next((s for s, r in enumerate(self._slots)
                         if r is None), None)
            if free is None:
                return None
            # paged layout: a vacant slot is NOT capacity — the
            # page pool is. Leave the head queued until enough
            # free or evictable pages exist (slots release pages
            # as sequences finish); ring layout always passes.
            head = self._q[0]
            if not engine.has_capacity(
                    head.prompt if head.handoff is None
                    and head.prompt is not None
                    else head.prompt_len):
                return None
            req = self._q.popleft()
        midbatch = self.live_slots > 0
        # queue-wait is knowable only now: record it backwards into
        # the member trace, then time the prefill as a
        # slot-admission span carrying the bucket-padding waste the
        # p99 post-mortem needs (engine._dispatch annotates it with
        # the cache disposition + FLOPs while it is current)
        _tracing.record_interval(
            "serving::queue_wait", req.trace, req.t_submit, self._clock(),
            prompt_tokens=req.prompt_len)
        if req.handoff is not None:
            # a prefill-tier forward already happened elsewhere;
            # admission is one functional cache insert
            asp = _tracing.begin_span(
                "serving::slot_admission", slot=free,
                midbatch=midbatch, handoff=True,
                prompt_tokens=req.prompt_len)
        else:
            bucket = engine.bucket_for(len(req.prompt))
            asp = _tracing.begin_span(
                "serving::slot_admission", slot=free,
                midbatch=midbatch,
                bucket=bucket, prompt_tokens=req.prompt_len,
                padded_tokens=bucket - len(req.prompt),
                fill=round(len(req.prompt) / bucket, 4))
        return req, free, midbatch, asp

    def _admit_ready(self):
        """Fill vacant slots from the queue (the continuous-batching
        move: admission happens between decode steps, never tearing the
        running batch down). Phases: ``serving::pick`` up to the call
        into the engine, the engine's own ``generation::prefill`` and
        ``::prefill_fetch``, then ``serving::install``. With a decode
        step in flight the prefill is enqueued behind it. Where the
        engine takes the prompt by chunks, the request is only seated
        (``_chunked``): the loop runs ONE chunk an iteration
        (:meth:`_admit_chunk`), and nothing else is admitted until the
        prompt is in. Returns whether a first token was waited for (an
        admission that drains the look-ahead)."""
        engine = self.engine
        admitted = False
        while self._chunked is None:
            picked = self._pick()
            self._mark("serving::pick")
            if picked is None:
                return admitted
            req, free, midbatch, asp = picked
            if req.handoff is None:
                adm = engine.begin_admission(free, req.prompt,
                                             req.temperature)
                if adm is not None:
                    self._chunked = _Chunked(req, free, midbatch, asp, adm)
                    break
            admitted = True
            self._admit(req, free, midbatch, asp,
                        lambda: self._admit_whole(req, free))
        return admitted

    def _admit_whole(self, req, free):
        """The engine's call for a prompt that goes in whole, or for one
        prefilled elsewhere: the first token."""
        engine = self.engine
        if isinstance(req.handoff, PageSlab):
            slab = req.handoff
            return engine.admit_prefilled_pages(
                free, slab.pages, slab.length, slab.first_token,
                page_size=slab.page_size, tenant=req.tenant)
        if req.handoff is not None:
            planes, length, first = req.handoff
            return engine.admit_prefilled(free, planes, length, first,
                                          prompt=req.prompt)
        return engine.admit(free, req.prompt, req.temperature,
                            tenant=req.tenant)

    def _admit(self, req, free, midbatch, asp, call):
        """One call into the engine for ``req`` under its admission
        span. Its first token (``None``: a chunk that is not the
        prompt's last) installs the request; an error fails it, and
        every live stream too if the call lost the cache. Returns
        whether the request is still on its way in."""
        try:
            with _tracing.use_span(asp):
                tok = call()
        except Exception as e:  # noqa: BLE001 — the loop must survive
            self._t_ns = time.perf_counter_ns()
            self._give_up_chunked()
            self._m_errors.inc()
            self._fail_admission(req, asp, e)
            if isinstance(e, CacheLostError):
                # the failed prefill took every slot's context
                self._fail_live(e)
            return False
        # the engine's spans cover its call; the next phase begins here
        self._t_ns = time.perf_counter_ns()
        if tok is None:
            return True
        self._install(req, free, tok, midbatch, asp)
        self._mark("serving::install")
        return False

    def _give_up_chunked(self):
        """Leave the prompt that is in its chunks, if one is, where it
        stands: its slot is vacant again as it is. Returns it."""
        c, self._chunked = self._chunked, None
        if c is not None:
            self.engine.abandon_admission(c.adm)
            self.engine.release_slot(c.slot)
        return c

    def _fail_admission(self, req, asp, e):
        """Close ``req``'s admission span on the error and fail the
        request with it, unless somebody has failed it already."""
        asp.set_error(f"{type(e).__name__}: {e}")
        _tracing.record_fanin(asp, [req.trace])
        _tracing.flag_trace(req.trace, "error")
        if not req.finished:
            req.done(error=e)

    def _step_before_chunk(self, depth):
        """No step is in flight (the last admission drained the
        look-ahead) and a chunk is about to go: the live slots' step goes
        first, from the host's tokens, so that this iteration looks ahead
        like any other with a chunk. Behind the chunk the step would wait
        for it, and for the last chunk of the prompt before, whose token
        the host has only just seen: under load one prompt's chunks
        follow another's, and every live stream's gap there would be two
        chunks and a step."""
        seated = self._seated(None)
        if not seated:
            return
        try:
            self._flight = self._launch(seated, None, depth)
        except Exception as e:  # noqa: BLE001 — fail THESE, keep serving
            self._fail_live(e)
        self._t_ns = time.perf_counter_ns()

    def _admit_chunk(self):
        """The next chunk of the prompt that is in its chunks, enqueued
        and left. A request that was failed from outside or whose
        deadline passed since its last chunk is given up. Returns
        whether it was the prompt's last chunk: an admission, which
        drains the look-ahead. Its token is waited for at once where no
        step is in flight, and else after that step is delivered
        (:meth:`_loop`): the step's tokens are ready a chunk before the
        prompt's first is."""
        c = self._chunked
        now = self._clock()
        if c.req.finished or c.req.expired(now):
            self._give_up_chunked()
            e = c.req.error
            if not c.req.finished:
                self._m_expired.inc()
                e = DeadlineExceededError(
                    f"generation deadline passed after "
                    f"{(now - c.req.t_submit) * 1e3:.1f}ms, "
                    f"{c.adm.lo} of {c.req.prompt_len} prompt tokens in")
            self._fail_admission(c.req, c.span, e)
            self._mark("serving::pick")
            return False
        if not self._admit(c.req, c.slot, c.midbatch, c.span,
                           lambda: self.engine.enqueue_chunk(c.adm)):
            return True  # failed, and given up already
        if c.adm.done and self._flight is None:
            self._finish_chunked()
        return c.adm.done

    def _finish_chunked(self):
        """Wait for the first token of the prompt whose last chunk is
        enqueued, and install its request."""
        c = self._chunked
        self._admit(c.req, c.slot, c.midbatch, c.span,
                    lambda: self.engine.fetch_admission(c.adm))
        self._chunked = None

    def _install(self, req, free, tok, midbatch, asp):
        """After the first token is back: close the admission span,
        observe TTFT, deliver the token, and seat the request in its
        slot (or complete it, if one token was all it asked for)."""
        _tracing.record_fanin(asp, [req.trace])
        with self._lock:
            if self._closed and not self._drain:
                # stop(drain=False) landed while this request was in
                # flight between the queue pop and slot install — it
                # was promised a failure, not a quiet completion
                self._m_errors.inc()
                req.done(error=ServingClosedError(
                    "generation scheduler shut down before the "
                    "request reached a decode slot"))
                return
        req.t_first_token = self._clock()
        self._h_ttft.labels(kind=self.kind, tenant=req.tenant).observe(
            (req.t_first_token - req.t_submit) * 1e3)
        if midbatch:
            self._m_midbatch.inc()
        _flight.record_event(
            "generation_admit", slot=free, midbatch=midbatch,
            prompt_tokens=req.prompt_len,
            queued_ms=round(
                (req.t_first_token - req.t_submit) * 1e3, 3))
        self._deliver(req, tok)
        reason = self._finished_reason(req)
        if reason is not None:
            self.engine.release_slot(free)
            self._complete(req, reason)
            return
        self._slots[free] = req
        self._last[free] = tok
        self._temps[free] = (
            self.engine.default_temperature
            if req.temperature is None else float(req.temperature))

    def _fail_live(self, e):
        """Fail every request that holds a slot and vacate the slots:
        a decode step that raised, or any call that lost the cache
        (:class:`CacheLostError`), leaves none of them a context. A
        step in flight is dropped unfetched: nobody is left to take
        its tokens."""
        self._flight = None
        c = self._give_up_chunked()
        if c is not None:
            # half in: its rows went with the cache, or with the step
            self._m_errors.inc()
            self._fail_admission(c.req, c.span, e)
        busy = [s for s, r in enumerate(self._slots) if r is not None]
        for s in busy:
            req, self._slots[s] = self._slots[s], None
            self.engine.release_slot(s)
            self._m_errors.inc()
            _tracing.record_interval(
                "serving::decode", req.trace,
                req.t_first_token if req.t_first_token is not None
                else req.t_submit,
                error=f"{type(e).__name__}: {e}",
                tokens=len(req.tokens))
            _tracing.flag_trace(req.trace, "error")
            req.done(error=e)
        _flight.record_event(
            "generation_step_error", slots=len(busy),
            error=f"{type(e).__name__}: {e}"[:300])

    def _sample_counters(self, ahead):
        """Once an iteration, after admission and before the step: the
        state the step runs with, as samples on the profiler's timeline
        (``serving::steps_ahead``: 1 when this iteration enqueues its
        step before it fetches the one in flight, else 0).
        One boolean when the profiler is off: nothing is counted then."""
        if not _profiler.enabled():
            return
        busy = [s for s, r in enumerate(self._slots) if r is not None]
        _profiler.record_counter("serving::slots_busy", len(busy))
        _profiler.record_counter("serving::steps_ahead", int(ahead))
        _profiler.record_counter("serving::kv_live_tokens", sum(
            self._slots[s].prompt_len + len(self._slots[s].tokens)
            for s in busy))

    def _end_iteration(self):
        """Close one pass of the loop, and leave a ``generation_stall``
        flight event if the pass (idle wait excluded) stood still for
        over a second: its split by phase (the engine added its own),
        and for its longest single call or host phase what held it (the
        program, the innermost phase, its usual time, whose time it
        was: ``flight_recorder.record_stall``)."""
        split = self._split
        began = self._t_iter_ns
        total = self._t_ns - began - split.pop("serving::idle_wait", 0)
        self._t_iter_ns = self._t_ns
        if total > _STALL_NS:
            # what lies between the phases: the engine's host-side
            # preparation around its spans
            split["other"] = total - sum(split.values())
            rings = [self._host] + self.engine.program_rings()
            (ns, start, phase, ring, entry), nested = _flight.held_among(
                (entry, ring) for ring in rings for entry in ring.ring
                if entry[0] >= began)
            runs, idle = ring.before(entry)
            _flight.record_stall(
                "generation_stall", (ns, start, phase),
                ring.usual_ns(entry[1], phase, but=entry),
                None if ring is self._host else ring.name, runs, idle,
                self._evidence, iteration_ms=round(total / 1e6, 3),
                phases_ms={k: round(v / 1e6, 3) for k, v in split.items()},
                nested_ms={k: round(v / 1e6, 3) for k, v in nested.items()},
                live_slots=self.live_slots, queue_depth=len(self._q))
        self._evidence.refresh(self._t_ns)
        split.clear()

    def _seated(self, flight):
        """``{slot: request}`` of the requests that take a token of the
        NEXT step. With a step in flight its token is counted as if it
        were delivered: a request that it brings to ``max_new_tokens``
        is known to end there and is left out. One that it ends by EOS
        is not known, and stays in: its row of the next step is
        computed in vain and dropped (:meth:`_land`)."""
        owed = {} if flight is None else flight.seated
        return {s: r for s, r in enumerate(self._slots)
                if r is not None
                and len(r.tokens) + (owed.get(s) is r) < r.max_new_tokens}

    def _launch(self, seated, after, depth):
        """Enqueue one decode step for ``seated``: from the device
        tokens of the flight ``after``, or from the host's ``_last``.
        At depth 0 the engine runs the step whole (``step`` /
        ``spec_step``) and the flight comes back with its tokens."""
        engine = self.engine
        flight = _Flight(seated, self._t_ns)
        if depth:
            flight.step = engine.enqueue_step(
                self._last if after is None else after.step, self._temps)
        elif engine.speculative:
            # one draft+verify round: every busy slot emits 1..k+1
            # tokens (the scheduler truncates at its own EOS/budget,
            # exactly like the one-token path)
            flight.rows, flight.counts = engine.spec_step(
                self._last, self._temps, busy=list(seated))
        else:
            flight.rows = engine.step(self._last, self._temps)[:, None]
        return flight

    def _land(self, flight):
        """Deliver a fetched step's tokens to the requests that sat in
        its slots when it was enqueued, and complete what finished. A
        slot that has changed hands since (its request ended on EOS one
        step earlier, was failed, or the slot was let again) computed
        its row in vain: the token is dropped, never delivered."""
        engine = self.engine
        now = self._t_ns
        # per-token latency, per STREAM (what a client waits between
        # tokens): from the last delivery, or from this step's enqueue
        # where the loop had come to rest between the two
        dt_ms = (now - max(flight.t0_ns, self._t_landed_ns)) / 1e6
        self._t_landed_ns = now
        if self._watch.armed:
            self._watch.note(slots=len(flight.seated))
        emitted = 0
        for s, req in flight.seated.items():
            if self._slots[s] is not req:
                continue
            if req.finished:  # stop(drain=False) race
                self._slots[s] = None
                engine.release_slot(s)
                continue
            reason = None
            n = 1 if flight.counts is None else int(flight.counts[s])
            for tok in flight.rows[s, :n]:
                self._deliver(req, tok)
                self._last[s] = tok
                emitted += 1
                reason = self._finished_reason(req)
                if reason is not None:
                    break
            if reason is not None:
                self._slots[s] = None
                engine.release_slot(s)
                self._complete(req, reason)
        # a speculative round amortizes its two dispatches over the
        # mean tokens each busy stream emitted
        # kind-labeled only: one step serves slots of mixed tenants
        h_token = self._h_token.labels(kind=self.kind)
        if flight.counts is not None and emitted:
            h_token.observe(dt_ms * len(flight.seated) / emitted)
        else:
            h_token.observe(dt_ms)
        self._mark("serving::deliver")

    def _loop(self):
        # The loop thread's timeline is a PARTITION into sibling phases:
        # serving::pick, generation::prefill, ::prefill_fetch,
        # serving::install, generation::decode, ::decode_fetch,
        # serving::deliver, serving::idle_wait. Never wrap them in an
        # iteration span: the benchmark's idle_gaps gives each device gap
        # to the span that covers most of it, so a wrapper would take
        # every gap. Only generation::args, runtime::lookup and
        # runtime::launch nest, inside the enqueue spans.
        #
        # The loop runs ``engine.steps_ahead`` steps ahead of what it
        # has delivered. At 1 an iteration enqueues step n+1 from step
        # n's tokens on the device and THEN fetches and delivers step n
        # (pick, decode, decode_fetch, deliver), so the device has its
        # next program before the host has seen this one's tokens. An
        # admission's first token is on the host, so it drains the
        # look-ahead: the prefill is enqueued behind the step in
        # flight, which is then fetched and delivered with nothing
        # enqueued (pick, prefill, prefill_fetch, install, pick,
        # decode_fetch, deliver); the next iteration enqueues from the
        # host's tokens and fetches nothing (pick, decode), and the one
        # after looks ahead again. At 0 an iteration is what it always
        # was: the step it enqueues is the step it fetches. A chunk that
        # is not its prompt's last is enqueued and left (pick, prefill,
        # decode, decode_fetch, deliver): on the device the order is
        # step n, chunk, step n+1, the host fetches step n as in a plain
        # iteration, and the look-ahead stays. The last chunk is an
        # admission: nothing is enqueued behind it, but the step in
        # flight is fetched and delivered BEFORE the chunk's token is
        # waited for (pick, prefill, decode_fetch, deliver,
        # prefill_fetch, install): the device is a chunk and a step
        # behind the host by then, and the step's tokens are ready a
        # chunk earlier than the prompt's first. The iteration after it
        # has no step in flight: if it carries a chunk (the next prompt's
        # first), the step is enqueued BEFORE the chunk and the iteration
        # looks ahead at once (pick, decode, prefill, decode,
        # decode_fetch, deliver). At most one step runs between two
        # chunks of a prompt, and none between its last chunk and the
        # fetch of its token (the engine refuses them).
        engine = self.engine
        engine.phase_split = self._split
        # asked of a device that may be the one in trouble: a read that
        # fails leaves the allocator's fields out of the record
        self._evidence = _flight.Evidence(
            lambda: engine.device_memory_stats())
        self._t_ns = self._t_iter_ns = time.perf_counter_ns()
        self._evidence.refresh(self._t_ns)
        while True:
            admitted = self._admit_ready()
            depth = engine.steps_ahead
            if self._chunked is not None and not admitted:
                # (after a whole admission the chunk waits an iteration:
                # the step behind it would wait for both)
                if depth and self._flight is None:
                    self._step_before_chunk(depth)
                admitted = self._chunked is not None and self._admit_chunk()
            flight, self._flight = self._flight, None
            seated = self._seated(flight)
            ahead = bool(depth and flight is not None and not admitted
                         and seated)
            self._sample_counters(ahead)
            if flight is None and not seated:
                with self._lock:
                    # a prompt in its chunks is work: neither left nor
                    # waited on
                    if self._closed and not self._q \
                            and self._chunked is None:
                        break
                    if not self._q and self._chunked is None:
                        self._not_empty.wait(0.05)
                self._mark("serving::idle_wait")
                self._end_iteration()
                continue
            nxt = None
            try:
                if flight is None or ahead:
                    nxt = self._launch(seated, flight, depth)
                if flight is not None:
                    flight.rows = engine.fetch_step(flight.step)[:, None]
            except Exception as e:  # noqa: BLE001 — fail THESE, keep serving
                self._t_ns = time.perf_counter_ns()
                self._fail_live(e)
                self._mark("serving::deliver")
                self._end_iteration()
                continue
            # the engine's spans cover its calls; the next phase begins here
            self._t_ns = time.perf_counter_ns()
            if flight is not None:
                self._land(flight)
            if self._chunked is not None and self._chunked.adm.done:
                # its last chunk went in behind the step just delivered
                self._finish_chunked()
            if nxt is not None and nxt.rows is not None:
                self._land(nxt)
            else:
                self._flight = nxt
            self._end_iteration()
        # drained exit: nothing queued, nothing active

    # -- lifecycle -----------------------------------------------------------

    def close(self, drain=True):
        """Refuse new requests. ``drain=True`` lets the decode loop
        finish everything queued AND active; ``drain=False`` fails
        queued requests immediately (active ones still finish their
        current step and are failed by ``stop``)."""
        with self._lock:
            if self._closed and not self._q:
                return
            self._closed = True
            self._drain = drain
            dropped = []
            if not drain:
                dropped = list(self._q)
                self._q.clear()
            self._not_empty.notify_all()
        for req in dropped:
            self._m_errors.inc()
            req.done(error=ServingClosedError(
                "generation scheduler shut down before admission"))
        _flight.record_event("generation_close", drain=drain,
                             dropped=len(dropped))

    def stop(self, drain=True, timeout=30.0):
        """Close and join the decode loop. With ``drain=False`` active
        sequences are failed instead of run to completion."""
        self.close(drain=drain)
        if not drain:
            self._fail_pending("generation scheduler shut down "
                               "mid-sequence")
        t = self._thread
        if t is not None:
            t.join(timeout)
        if t is None or not t.is_alive():
            # a drain-stop with no live loop (never started, or it died)
            # would otherwise strand queued/slot requests un-completed
            # forever — their waiters must get an error, not a hang
            self._thread = None
            self._fail_pending("generation scheduler stopped with no "
                               "decode loop to drain the request")

    def _fail_pending(self, why):
        with self._lock:
            dropped = list(self._q)
            self._q.clear()
        for s, req in enumerate(self._slots):
            if req is not None:
                self._slots[s] = None
                self.engine.release_slot(s)
                if not req.finished:
                    dropped.append(req)
        c = self._chunked
        if c is not None:
            # the loop gives its slot up when it sees the request done
            dropped.append(c.req)
        for req in dropped:
            if not req.finished:
                self._m_errors.inc()
                req.done(error=ServingClosedError(why))

    @property
    def alive(self) -> int:
        t = self._thread
        return int(t is not None and t.is_alive())

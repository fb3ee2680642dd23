"""Load-aware router tier: one front door over N backend processes.

A single ``InferenceServer``/``GenerationServer`` is one process on one
host; "heavy traffic" needs a fleet. The router spreads ``/predict`` and
``/generate`` traffic across independent backend processes using the
machine-oriented signals they already publish:

- **power-of-two-choices dispatch**: each request samples two in-rotation
  backends and takes the less loaded one (router-side in-flight count
  plus the last-probed ``/loadz`` queue depth). P2C gets most of the
  benefit of full load-awareness while staying O(1) and herd-immune —
  stale load signals cannot stampede every request onto one backend.
- **health/readiness probes**: a daemon prober hits every backend's
  ``/healthz`` + ``/loadz`` each ``FLAGS_serving_router_probe_interval_s``.
  A backend that stops answering, flips draining, or loses readiness is
  **evicted** from rotation; re-admission happens ONLY when a later
  probe sees ``/healthz`` readiness again — a drained backend cannot
  leak back in through a lucky dispatch.
- **retry-on-next-backend** for failures that provably precede dispatch:
  connection failures (refused/reset/EOF before a response line — the
  backend never answered; predict/generate are stateless, so replaying
  on a survivor is the availability contract) and admission rejections
  (503: draining or not ready — refused at the door). Work a backend
  actually ANSWERED is never replayed: any received HTTP status other
  than 503 (429 backpressure, 400 client errors, 504 deadline, 500
  dispatch failures) passes through to the client untouched.
- **fleet introspection**: the router serves its own ``/statz`` — fleet
  p50/p99 merged from the backends' ``/histz`` bucket counts (exact:
  summed buckets ≡ one pooled histogram), per-backend load/weights, and
  eviction/retry/readmission counters — plus ``/healthz``, ``/loadz``,
  ``/metrics``, all reporting into the flight recorder and registered
  with ``serving.shutdown_all``.

Backends enter the fleet via ``add_backend(url)`` (the autoscaler's
launcher calls this after booting a process) and leave via
``remove_backend``/eviction; the router never owns backend processes —
``serving/scaler.py`` does lifecycle.

The router is also runnable as its own process —
``python -m paddle_tpu.serving.router --backend URL [--backend URL ...]``
— which is how a production fleet deploys it: proxying is pure-Python
byte shuffling, so co-hosting the router inside a busy client or backend
process would serialize the whole fleet behind that process's GIL. (The
in-process object form stays the right one for tests and for the
autoscaler, which drives ``add_backend``/``remove_backend`` directly.)
"""
from __future__ import annotations

import json
import random
import socket
import threading
import time
from http.client import (
    BadStatusLine,
    HTTPConnection,
    IncompleteRead,
    LineTooLong,
)
from urllib.parse import urlsplit

from ..errors import InvalidArgumentError, UnavailableError
from ..flags import flag
from ..monitor import counter, gauge, histogram
from ..monitor import flight_recorder as _flight
from ..monitor import histogram_quantile, merge_histogram_snapshots
from ..monitor import tracing as _tracing
from .server import _BaseHandler

__all__ = ["Router", "BackendState", "NoBackendError",
           "BackendUnavailableError", "BackendTimeoutError"]

_POST_KINDS = {"/predict": "predict", "/generate": "generate"}

# a backend dying while its response body is being read: ConnectionError
# covers resets, IncompleteRead a mid-body EOF, socket.timeout a stall,
# OSError the rest of the socket-level failure family
_BACKEND_READ_ERRORS = (ConnectionError, IncompleteRead, socket.timeout,
                        OSError)


def _quantile_row(h) -> dict | None:
    """p50/p99/count for one merged histogram, or None when it holds no
    observations (quantiles of nothing are not 0ms)."""
    p50 = histogram_quantile(h, 0.5)
    if p50 is None:
        return None
    return {"p50_ms": round(p50, 3),
            "p99_ms": round(histogram_quantile(h, 0.99), 3),
            "count": h.count}


class NoBackendError(UnavailableError):
    """No backend admitted the request within the retry budget (503)."""


class BackendUnavailableError(UnavailableError):
    """A backend could not be reached / died before answering. The
    request was never answered, so the router may retry it elsewhere."""

    def __init__(self, reason, detail):
        super().__init__(f"backend unavailable ({reason}): {detail}")
        self.reason = reason


class BackendTimeoutError(UnavailableError):
    """The backend took the request but no response arrived within the
    budget. The work IS dispatched (and may still be running), so the
    router must NOT retry — the client gets 504."""


class BackendState:
    """Router-side view of one backend: rotation membership, the last
    probed ``/loadz`` signals, and per-backend dispatch accounting.
    Mutated only under the router lock."""

    __slots__ = (
        "url", "kind", "in_rotation", "draining", "inflight",
        "queue_depth", "queue_capacity", "load", "mean_fill",
        "slot_occupancy", "compiles", "consecutive_failures",
        "admitted", "completed", "evictions", "last_probe_t",
        "last_error", "metrics", "metrics_t",
    )

    def __init__(self, url):
        self.url = url.rstrip("/")
        self.kind = None           # "predict" | "generate", from /loadz
        self.in_rotation = False   # eligible for dispatch
        self.draining = False
        self.inflight = 0          # router-side outstanding requests
        self.queue_depth = 0
        self.queue_capacity = 0
        self.load = 0.0
        self.mean_fill = None
        self.slot_occupancy = None
        self.compiles = {}
        self.consecutive_failures = 0
        self.admitted = 0
        self.completed = 0
        self.evictions = 0
        self.last_probe_t = 0.0
        self.last_error = None
        # last /metricz?format=snapshot scrape (registry snapshot dict),
        # the /fleetz merge feed; stale-tolerant for one probe period
        self.metrics = {}
        self.metrics_t = 0.0

    def score(self) -> float:
        """P2C comparison key: fresher router-side in-flight count plus
        the last-probed backend queue depth."""
        return self.inflight + self.queue_depth

    def view(self) -> dict:
        return {
            "url": self.url, "kind": self.kind,
            "in_rotation": self.in_rotation, "draining": self.draining,
            "inflight": self.inflight, "queue_depth": self.queue_depth,
            "load": self.load, "mean_fill": self.mean_fill,
            "slot_occupancy": self.slot_occupancy,
            "compiles": self.compiles,
            "admitted": self.admitted, "completed": self.completed,
            "evictions": self.evictions,
            "last_error": self.last_error,
        }


class _RouterHandler(_BaseHandler):
    def do_GET(self):
        path = self.path.split("?", 1)[0].rstrip("/") or "/"
        if self._get_common(path):
            return
        if path == "/fleetz":
            self._reply(200, self._srv.fleetz())
        elif path == "/":
            self._reply(200, {
                "service": "paddle_tpu serving router",
                "routes": ["/predict (POST)", "/generate (POST)",
                           "/healthz", "/statz", "/loadz", "/fleetz",
                           "/histz", "/tracez", "/metrics", "/metricz",
                           "/sloz"]})
        else:
            self._reply(404, {"error": f"unknown path {path!r}"})

    def do_POST(self):
        path = self.path.split("?", 1)[0].rstrip("/")
        body = self._read_body()
        if body is None:
            return
        kind = _POST_KINDS.get(path)
        if kind is None:
            self._reply(404, {"error": f"unknown path {path!r}"})
            return
        # the router is where a fleet trace is BORN (or continued, when
        # the client itself propagates a traceparent): every dispatch
        # attempt becomes a child span, and the chosen backend's whole
        # span tree hangs under the winning attempt
        with self._trace_request("serving::router"):
            _tracing.annotate(kind=kind)
            self._proxy(path, kind, body)

    def _proxy(self, path, kind, body):
        srv = self._srv
        if srv.draining:
            self._reply(503, {"error": "router draining"})
            return
        if kind == "generate" and srv.has_kind("prefill") \
                and srv.has_kind("decode"):
            # disaggregated fleet: /generate becomes prefill -> slab ->
            # decode, orchestrated here (the tiers never talk directly,
            # so each leg keeps the full retry/eviction policy). BOTH
            # tiers must be live — with only a prefill tier up (decode
            # still booting/evicted) requests keep flowing to any
            # unified generate backends instead of 503ing
            self._proxy_disagg(body)
            return
        t0 = time.monotonic()
        try:
            backend, conn, resp = srv.dispatch(kind, path, body)
        except NoBackendError as e:
            self._reply(503, {"error": str(e)})
            return
        except BackendTimeoutError as e:
            self._reply(504, {"error": str(e)})
            return
        _tracing.annotate(backend=backend.url)
        self._relay(srv, backend, conn, resp, t0)

    def _relay(self, srv, backend, conn, resp, t0):
        """Forward one dispatched backend response to the client —
        streamed re-chunking or a buffered read — with the
        died-mid-response handling and the finish bookkeeping."""
        status = resp.status
        try:
            if (resp.getheader("Transfer-Encoding") or "").lower() \
                    == "chunked":
                self._proxy_stream(resp, srv, backend)
            else:
                try:
                    data = resp.read()
                except _BACKEND_READ_ERRORS as e:
                    # the backend answered its status line then died
                    # mid-body: the work WAS dispatched (no retry), but
                    # the client must get a real response, not a
                    # dropped socket
                    status = 502
                    srv.note_backend_died(backend, "died_mid_response")
                    self._reply(502, {
                        "error": "backend connection lost mid-response "
                                 f"({type(e).__name__})"})
                else:
                    self._reply_raw(status, data,
                                    resp.getheader("Content-Type"))
        finally:
            srv.finish(backend, t0, status, conn=conn, resp=resp)

    def _proxy_disagg(self, body):
        """Two-leg /generate: POST the request to a prefill backend
        (bounded forward on the handoff budget), then hand its KV slab
        to a decode backend whose response — streamed or not — relays
        to the client exactly like a unified /generate.

        Leg semantics: the prefill leg is stateless and keeps the full
        retry policy; a non-200 prefill answer (400 bad prompt, 429
        backpressure) passes through untouched. The slab then rides the
        normal dispatch to the decode tier, where the usual "answered
        means no replay" contract takes over."""
        from ..generation.handoff import HANDOFF_CONTENT_TYPE

        srv = self._srv
        t0 = time.monotonic()
        try:
            b1, conn1, resp1 = srv.dispatch(
                "prefill", "/prefill", body,
                read_timeout=srv.handoff_timeout_s)
        except NoBackendError as e:
            self._reply(503, {"error": str(e)})
            return
        except BackendTimeoutError as e:
            self._reply(504, {"error": f"prefill handoff: {e}"})
            return
        _tracing.annotate(prefill_backend=b1.url)
        status1 = resp1.status
        slab = None
        ctype1 = resp1.getheader("Content-Type")
        try:
            try:
                slab = resp1.read()
            except _BACKEND_READ_ERRORS as e:
                status1 = 502
                srv.note_backend_died(b1, "died_mid_response")
                self._reply(502, {
                    "error": "prefill backend connection lost "
                             f"mid-slab ({type(e).__name__})"})
                return
        finally:
            srv.finish(b1, t0, status1, conn=conn1, resp=resp1)
        if status1 != 200:
            self._reply_raw(status1, slab, ctype1)
            return
        t1 = time.monotonic()
        try:
            b2, conn2, resp2 = srv.dispatch(
                "decode", "/generate_kv", slab,
                content_type=HANDOFF_CONTENT_TYPE)
        except NoBackendError as e:
            self._reply(503, {"error": str(e)})
            return
        except BackendTimeoutError as e:
            self._reply(504, {"error": str(e)})
            return
        _tracing.annotate(backend=b2.url, handoff=True)
        self._relay(srv, b2, conn2, resp2, t1)

    def _proxy_stream(self, resp, srv, backend):
        """Re-chunk a streaming backend response to the client as the
        bytes arrive (one ``read1`` per backend chunk — per-token
        streaming survives the hop)."""
        # the chunked path bypasses _reply/_reply_raw, so the trace
        # must learn its status here
        _tracing.note_status(resp.status)
        self.send_response(resp.status)
        self.send_header("Content-Type",
                         resp.getheader("Content-Type")
                         or "application/x-ndjson; charset=utf-8")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def chunk_out(data):
            self.wfile.write(f"{len(data):x}\r\n".encode()
                             + data + b"\r\n")

        try:
            while True:
                try:
                    chunk = resp.read1(65536)
                except _BACKEND_READ_ERRORS as e:
                    # backend died mid-stream: the status line is long
                    # gone, so terminate the chunked stream PROPERLY
                    # with an error line — a bare connection drop would
                    # leave the client hanging on a dechunk. The trace
                    # is exactly the one the incident post-mortem needs:
                    # mark it errored so the tail sampler keeps it.
                    _tracing.note_status(502)
                    srv.note_backend_died(backend, "died_mid_stream")
                    chunk_out(json.dumps({
                        "error": "backend connection lost mid-stream "
                                 f"({type(e).__name__})"
                    }).encode() + b"\n")
                    break
                if not chunk:
                    break
                chunk_out(chunk)
            self.wfile.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError):
            pass  # client went away; backend read drains on conn.close


class Router:
    """HTTP front door spreading traffic over registered backends.

    ``backends`` seeds the fleet (each is probed and admitted when
    ready). ``port=0`` binds an ephemeral port. ``start()`` boots the
    listener and the prober; ``stop(drain=True)`` refuses new work,
    waits for in-flight proxied requests, and closes both.
    """

    def __init__(self, backends=(), port=0, host="127.0.0.1",
                 probe_interval_s=None, retries=None,
                 connect_timeout_ms=None, request_timeout_s=None):
        self.probe_interval_s = float(
            probe_interval_s if probe_interval_s is not None
            else flag("serving_router_probe_interval_s"))
        self.retries = int(retries if retries is not None
                           else flag("serving_router_retries"))
        if self.retries <= 0:
            raise InvalidArgumentError(
                f"router retry budget must be positive, got {self.retries}")
        self.connect_timeout_s = float(
            connect_timeout_ms if connect_timeout_ms is not None
            else flag("serving_router_connect_timeout_ms")) / 1e3
        self.request_timeout_s = float(
            request_timeout_s if request_timeout_s is not None
            else flag("serving_router_request_timeout_s"))
        # budget for the prefill->slab leg of a disaggregated /generate
        # (one bounded forward; the decode leg keeps the full budget)
        self.handoff_timeout_s = float(flag("serving_handoff_timeout_s"))
        self._lock = threading.Lock()
        self._backends: dict[str, BackendState] = {}
        # keep-alive pools: idle router->backend connections per backend
        # url. Connection-per-request would pay a TCP handshake + a
        # backend handler-thread spawn per dispatch — at fleet request
        # rates that churn IS the bottleneck.
        self._pools: dict[str, list] = {}
        self._pool_max_idle = 32
        self._rng = random.Random(0xB0DE)
        # fleet metrics (router process registry -> /metrics)
        self._m_requests = counter("serving/router_requests_total")
        self._m_retries = counter("serving/router_retries_total")
        self._m_evictions = counter("serving/router_evictions_total")
        self._m_readmissions = counter(
            "serving/router_readmissions_total")
        self._m_no_backend = counter("serving/router_no_backend_total")
        self._m_healthy = gauge("serving/router_backends_healthy")
        self._h_e2e = histogram("serving/router_e2e_ms")
        from .server import ServingHTTPServer

        self._httpd = ServingHTTPServer((host, int(port)),
                                        _RouterHandler)
        self._httpd._inference_server = self
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread = None
        self._prober = None
        self._stop_probe = threading.Event()
        self._t0 = time.monotonic()
        self.draining = False
        self._stopped = False
        for url in backends:
            self.add_backend(url)
        from . import _register_live

        _register_live(self)

    # -- fleet membership ----------------------------------------------------

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    @property
    def ready(self) -> bool:
        return not self.draining and self.healthy_count > 0

    @property
    def healthy_count(self) -> int:
        with self._lock:
            return sum(b.in_rotation for b in self._backends.values())

    def has_kind(self, kind) -> bool:
        """Any in-rotation backend confirmed as ``kind``? (The
        disaggregation switch: /generate orchestrates prefill->decode
        exactly when a prefill tier is live.)"""
        with self._lock:
            return any(b.in_rotation and b.kind == kind
                       for b in self._backends.values())

    def backend_states(self) -> list:
        with self._lock:
            return list(self._backends.values())

    def add_backend(self, url, probe=True) -> BackendState:
        """Register a backend. With ``probe`` (default) it is health-
        checked immediately and admitted if ready; otherwise it waits
        for the prober's next pass."""
        b = BackendState(url)
        with self._lock:
            existing = self._backends.get(b.url)
            if existing is not None:
                return existing
            self._backends[b.url] = b
        _flight.record_event("router_backend_added", url=b.url)
        if probe:
            self._probe_backend(b)
        return b

    def remove_backend(self, url) -> BackendState | None:
        """Drop a backend from the fleet entirely (scale-down path: the
        caller owns draining/terminating the process)."""
        with self._lock:
            b = self._backends.pop(url.rstrip("/"), None)
        self._pool_drop(url)
        if b is not None:
            _flight.record_event("router_backend_removed", url=b.url)
            self._update_healthy_gauge()
        return b

    def _update_healthy_gauge(self):
        self._m_healthy.set(self.healthy_count)

    def _evict(self, b: BackendState, reason: str):
        """Remove from rotation (dispatch stops immediately). The ONLY
        way back in is a later probe seeing /healthz readiness."""
        with self._lock:
            was = b.in_rotation
            b.in_rotation = False
            b.evictions += was
            b.last_error = reason
        if was:
            self._pool_drop(b.url)  # idle conns to a sick backend: out
            self._m_evictions.inc()
            _flight.record_event("router_backend_evicted", url=b.url,
                                 reason=reason)
            self._update_healthy_gauge()

    def note_backend_died(self, b: BackendState, reason: str):
        """A dispatched request's connection died mid-response: the
        client already owns that failure (502 / error chunk), but the
        backend is evidently gone — evict it so the NEXT requests go
        elsewhere instead of each paying the same discovery."""
        self._evict(b, reason=reason)

    def _admit(self, b: BackendState):
        with self._lock:
            was = b.in_rotation
            b.in_rotation = True
            # /healthz readiness implies not draining (ready == warmed
            # AND not draining); clear a stale dispatch-path flag even
            # when the /loadz refresh was skipped — in-rotation with
            # draining stuck True would be unpickable yet counted
            # healthy
            b.draining = False
            b.consecutive_failures = 0
            b.last_error = None
        if not was:
            if b.evictions:
                self._m_readmissions.inc()
                _flight.record_event("router_backend_readmitted",
                                     url=b.url)
            self._update_healthy_gauge()

    # -- backend HTTP --------------------------------------------------------

    def _connect(self, b: BackendState,
                 read_timeout=None) -> HTTPConnection:
        u = urlsplit(b.url)
        conn = HTTPConnection(u.hostname, u.port,
                              timeout=self.connect_timeout_s)
        try:
            conn.connect()
        except OSError as e:
            conn.close()
            raise BackendUnavailableError("connect", str(e)) from None
        conn.sock.settimeout(read_timeout or self.request_timeout_s)
        return conn

    def _send(self, b: BackendState, method, path, body=None,
              read_timeout=None):
        """One request to one backend. Returns ``(conn, resp)`` with the
        response UNREAD (the caller streams or reads it, then closes the
        conn). Raises :class:`BackendUnavailableError` only when no
        response line ever arrived — the definition of "not dispatched"
        the retry policy keys on."""
        conn = self._connect(b, read_timeout=read_timeout)
        try:
            return conn, self._request_on(conn, method, path, body,
                                          read_timeout=read_timeout)
        except BackendTimeoutError:
            conn.close()
            raise
        except (ConnectionError, BadStatusLine, LineTooLong,
                OSError) as e:
            conn.close()
            raise BackendUnavailableError(
                "no_response", f"{type(e).__name__}: {e}") from None

    def _pool_pop(self, b: BackendState):
        with self._lock:
            pool = self._pools.get(b.url)
            return pool.pop() if pool else None

    def _pool_push(self, b_url, conn):
        with self._lock:
            pool = self._pools.setdefault(b_url, [])
            if len(pool) < self._pool_max_idle:
                pool.append(conn)
                return
        conn.close()

    def _pool_drop(self, url):
        with self._lock:
            pool = self._pools.pop(url.rstrip("/"), [])
        for conn in pool:
            conn.close()

    def _dispatch_send(self, b: BackendState, path, body, headers=None,
                       content_type=None, read_timeout=None):
        """POST over a pooled keep-alive connection. A failure on a
        REUSED connection is retried once on a fresh one — the backend
        may simply have timed the idle socket out, which is not evidence
        of death. Only a fresh-connection failure raises the retriable
        :class:`BackendUnavailableError`. ``content_type`` overrides
        the JSON default (KV-slab handoffs are octet bodies);
        ``read_timeout`` overrides the request budget (the prefill leg
        of a handoff runs on the shorter handoff timeout)."""
        conn = self._pool_pop(b)
        if conn is not None:
            try:
                return conn, self._request_on(conn, "POST", path, body,
                                              extra_headers=headers,
                                              content_type=content_type,
                                              read_timeout=read_timeout)
            except BackendTimeoutError:
                conn.close()
                raise
            except (ConnectionError, BadStatusLine, LineTooLong,
                    OSError):
                conn.close()  # stale keep-alive: fall through to fresh
        conn = self._connect(b)
        try:
            return conn, self._request_on(conn, "POST", path, body,
                                          extra_headers=headers,
                                          content_type=content_type,
                                          read_timeout=read_timeout)
        except BackendTimeoutError:
            conn.close()
            raise
        except (ConnectionError, BadStatusLine, LineTooLong,
                OSError) as e:
            conn.close()
            raise BackendUnavailableError(
                "no_response", f"{type(e).__name__}: {e}") from None

    def _request_on(self, conn, method, path, body, extra_headers=None,
                    content_type=None, read_timeout=None):
        timeout = (self.request_timeout_s if read_timeout is None
                   else float(read_timeout))
        if conn.sock is not None:
            # pooled connections keep their previous budget otherwise
            conn.sock.settimeout(timeout)
        try:
            headers = ({"Content-Type": content_type or
                        "application/json"} if body else {})
            if extra_headers:
                headers.update(extra_headers)
            conn.request(method, path, body=body, headers=headers)
            return conn.getresponse()
        except socket.timeout:
            # the request went out but nothing came back in time: the
            # backend may still be computing it — dispatched work, so
            # no retry (504), unlike the connection-failure cases
            raise BackendTimeoutError(
                f"backend gave no response within {timeout}s") from None

    def _get_json(self, b: BackendState, path):
        """Probe GET: ``(status, parsed-json-or-{})``. Probes read on a
        short budget of their own — a hung backend must cost the prober
        seconds, not the full request timeout."""
        conn, resp = self._send(
            b, "GET", path,
            read_timeout=min(5.0, self.request_timeout_s))
        try:
            data = resp.read()
        finally:
            conn.close()
        try:
            payload = json.loads(data) if data else {}
        except ValueError:
            payload = {}
        return resp.status, payload

    # -- dispatch ------------------------------------------------------------

    def _pick(self, kind, exclude) -> BackendState | None:
        """Power-of-two-choices among in-rotation backends serving
        ``kind``: sample two, take the lower load score.

        Kind-CONFIRMED backends always win over kind-unknown ones: a
        not-yet-probed backend (``kind is None``) is only eligible when
        NO confirmed backend serves the kind — with several kinds in
        one fleet, an unprobed decode backend must not siphon
        ``/predict`` traffic it will 404. A mis-guessed unknown is
        re-picked, not failed (see :meth:`dispatch`)."""
        with self._lock:
            pool = [
                b for b in self._backends.values()
                if b.in_rotation and not b.draining
                and b.url not in exclude
            ]
            cands = [b for b in pool if b.kind == kind]
            if not cands:
                cands = [b for b in pool if b.kind is None]
            if not cands:
                return None
            if len(cands) == 1:
                return cands[0]
            a, c = self._rng.sample(cands, 2)
            return min((a, c), key=lambda b: (b.score(), b.url))

    def dispatch(self, kind, path, body, content_type=None,
                 read_timeout=None):
        """Pick-and-forward with the retry policy. Returns ``(backend,
        conn, resp)`` — response unread so the handler can stream it;
        the handler MUST call :meth:`finish` when done. Raises
        :class:`NoBackendError` after the retry budget.

        Every attempt is its own child span under the request's trace
        (the trace_id survives retries; each attempt is distinct), and
        the attempt's ``traceparent`` rides the proxied request so the
        backend's span tree hangs under it."""
        tried: set = set()
        while len(tried) < self.retries:
            b = self._pick(kind, tried)
            if b is None:
                break
            tried.add(b.url)
            kind_known = b.kind is not None
            with self._lock:
                b.inflight += 1
                b.admitted += 1
            # per-attempt span: bound under the handler's router root
            # (NULL outside a trace — direct dispatch() callers pay one
            # flag read). The span is recorded on scope exit whatever
            # the outcome, so even a timed-out attempt leaves a record.
            with _tracing.start_span(
                    "serving::attempt", backend=b.url,
                    attempt=len(tried)) as asp:
                headers = None
                if asp:
                    headers = {
                        _tracing.TRACEPARENT_HEADER:
                            _tracing.format_traceparent(asp.context)}
                try:
                    conn, resp = self._dispatch_send(
                        b, path, body, headers=headers,
                        content_type=content_type,
                        read_timeout=read_timeout)
                except BackendTimeoutError as e:
                    with self._lock:
                        b.inflight -= 1
                    # the work may still be running over there: no
                    # retry, but the orphaned attempt span (with the
                    # backend identity) is recorded and the trace is
                    # retained — an operator inspecting the 504 can see
                    # WHICH backend swallowed the request
                    asp.set_error(f"read timeout: {e}")
                    _tracing.flag_current_trace("timeout")
                    raise  # dispatched: surfaces as 504, never retried
                except BackendUnavailableError as e:
                    with self._lock:
                        b.inflight -= 1
                    # never answered -> the work never ran to completion
                    # anywhere; evict the silent backend and retry the
                    # request on the next one
                    asp.set_error(f"unavailable ({e.reason})")
                    _tracing.flag_current_trace("retry")
                    self._evict(b, reason=e.reason)
                    self._m_retries.inc()
                    _flight.record_event("router_retry", url=b.url,
                                         reason=e.reason, path=path)
                    continue
                if resp.status == 503:
                    # refused at admission (draining / not ready): the
                    # backend did NOT take the work — evict immediately
                    # (readiness re-admits it later) and retry elsewhere
                    try:
                        resp.read()
                    finally:
                        conn.close()
                    with self._lock:
                        b.inflight -= 1
                        b.draining = True
                    asp.set_attributes(status=503, refused=True)
                    _tracing.flag_current_trace("retry")
                    self._evict(b, reason="admission_503")
                    self._m_retries.inc()
                    _flight.record_event("router_retry", url=b.url,
                                         reason="admission_503",
                                         path=path)
                    continue
                if resp.status == 404 and not kind_known:
                    # a kind-unknown backend won the fallback pick for
                    # a route it does not serve: learn its kind from a
                    # probe and RE-PICK — the request never ran, so
                    # failing it would punish the client for the
                    # router's incomplete map
                    try:
                        resp.read()
                    finally:
                        conn.close()
                    with self._lock:
                        b.inflight -= 1
                    asp.set_attributes(status=404, kind_mismatch=True)
                    _tracing.flag_current_trace("retry")
                    self._probe_backend(b)
                    self._m_retries.inc()
                    _flight.record_event("router_retry", url=b.url,
                                         reason="kind_mismatch",
                                         path=path)
                    continue
                asp.set_attributes(status=resp.status)
                return b, conn, resp
        self._m_no_backend.inc()
        _flight.record_event("router_no_backend", path=path,
                             tried=sorted(tried))
        raise NoBackendError(
            f"no backend admitted the request (tried {len(tried)}, "
            f"retry budget {self.retries})")

    def finish(self, b: BackendState, t0, status, conn=None, resp=None):
        with self._lock:
            b.inflight -= 1
            b.completed += 1
        self._m_requests.inc()
        self._h_e2e.observe((time.monotonic() - t0) * 1e3)
        if conn is None:
            return
        # keep-alive recycling: only a FULLY-read response on a
        # connection the backend will keep open may re-enter the pool —
        # a half-read body (client vanished mid-stream) would corrupt
        # the next request on that socket
        if (resp is not None and resp.isclosed()
                and not resp.will_close and b.in_rotation):
            self._pool_push(b.url, conn)
        else:
            conn.close()

    # -- probing -------------------------------------------------------------

    def _probe_backend(self, b: BackendState):
        """One health/load probe: readiness on ``/healthz`` gates
        rotation membership; ``/loadz`` refreshes the dispatch signals
        (and the backend's kind)."""
        try:
            status, _ = self._get_json(b, "/healthz")
            if status != 200:
                raise BackendUnavailableError("not_ready",
                                              f"healthz {status}")
        except (BackendUnavailableError, BackendTimeoutError) as e:
            with self._lock:
                b.consecutive_failures += 1
            self._evict(b, reason=getattr(e, "reason", "probe_timeout"))
            b.last_probe_t = time.monotonic()
            return
        try:
            lz_status, lz = self._get_json(b, "/loadz")
            if lz_status == 200 and lz:
                with self._lock:
                    b.kind = lz.get("kind", b.kind)
                    b.queue_depth = int(lz.get("queue_depth", 0))
                    b.queue_capacity = int(lz.get("queue_capacity", 0))
                    b.load = float(lz.get("load", 0.0))
                    b.mean_fill = lz.get("mean_fill")
                    b.slot_occupancy = lz.get("slot_occupancy")
                    b.compiles = lz.get("compiles", {})
                    b.draining = bool(lz.get("draining", False))
                if b.draining:
                    self._evict(b, reason="draining")
                    return
            self._admit(b)
        except (BackendUnavailableError, BackendTimeoutError) as e:
            with self._lock:
                b.consecutive_failures += 1
            self._evict(b, reason=getattr(e, "reason", "probe_timeout"))
        finally:
            b.last_probe_t = time.monotonic()
        # fleet-metrics scrape rides the same probe pass: the latest
        # registry snapshot (labeled series included) lands on the
        # state, so /fleetz is a dict merge, never a fan-out of
        # on-demand backend GETs. Failure keeps the previous snapshot —
        # load/health already decided rotation, and metrics one probe
        # period stale merge fine.
        try:
            mz_status, mz = self._get_json(b, "/metricz?format=snapshot")
            if mz_status == 200 and isinstance(mz, dict):
                with self._lock:
                    b.metrics = mz.get("metrics") or {}
                    b.metrics_t = time.monotonic()
        except (BackendUnavailableError, BackendTimeoutError):
            pass

    def probe_once(self):
        for b in self.backend_states():
            self._probe_backend(b)

    def _probe_loop(self):
        while not self._stop_probe.wait(self.probe_interval_s):
            try:
                self.probe_once()
            except Exception:  # the prober must never die
                pass

    # -- lifecycle -----------------------------------------------------------

    def start(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._httpd.serve_forever,
                name=f"ptpu-router:{self.port}", daemon=True)
            self._thread.start()
        if self._prober is None or not self._prober.is_alive():
            self._stop_probe.clear()
            self._prober = threading.Thread(
                target=self._probe_loop, name="ptpu-router-prober",
                daemon=True)
            self._prober.start()
        _flight.record_event(
            "router_start", port=self.port,
            backends=[b.url for b in self.backend_states()])
        return self

    def stop(self, drain=True, timeout=10.0):
        """Refuse new work, optionally wait out in-flight proxied
        requests, then close prober + listener. Backends are NOT
        stopped — the router does not own their processes."""
        if self._stopped:
            return
        self._stopped = True
        self.draining = True
        if drain:
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                with self._lock:
                    busy = sum(b.inflight
                               for b in self._backends.values())
                if not busy:
                    break
                time.sleep(0.01)
        self._stop_probe.set()
        p = self._prober
        if p is not None:
            p.join(timeout=self.probe_interval_s + 1.0)
        self._prober = None
        t = self._thread
        if t is not None and t.is_alive():
            # shutdown() blocks on an event only serve_forever() sets —
            # never call it on a listener that never started
            self._httpd.shutdown()
        self._httpd.server_close()
        if t is not None:
            t.join(timeout=5)
        self._thread = None
        for url in list(self._pools):
            self._pool_drop(url)
        _flight.record_event("router_stop", port=self.port, drain=drain)

    # -- introspection -------------------------------------------------------

    def merged_backend_quantiles(self, names=None, timeout_s=2.0) -> dict:
        """Fleet-wide latency quantiles: fetch every in-rotation
        backend's ``/histz`` bucket counts and merge per histogram name
        (exact — summed buckets are the pooled histogram). Returns
        ``{name: {p50_ms, p99_ms, count, backends}}``."""
        per_name: dict[str, list] = {}
        for b in self.backend_states():
            if not b.in_rotation:
                continue
            try:
                status, payload = self._get_json(b, "/histz")
            except (BackendUnavailableError, BackendTimeoutError):
                continue
            if status != 200:
                continue
            for name, snap in payload.get("histograms", {}).items():
                if names is not None and name not in names:
                    continue
                per_name.setdefault(name, []).append(snap)
        out = {}
        for name, snaps in per_name.items():
            merged = merge_histogram_snapshots(snaps, name=name)
            row = _quantile_row(merged)
            if row is None:
                continue
            row["backends"] = len(snaps)
            out[name] = row
        return out

    def fleetz(self) -> dict:
        """``GET /fleetz``: fleet-merged labeled quantiles. Per backend
        kind, per ``serving/*`` histogram, the elementwise bucket sum of
        every in-rotation backend's last prober-scraped snapshot —
        exact, identical to one pooled histogram — with quantiles per
        labeled series riding along. Empty series are omitted entirely
        (a fake 0ms p99 is worse than no row). No backend I/O happens
        here: the prober already paid for the snapshots."""
        groups: dict = {}
        states = self.backend_states()
        scraped = 0
        for b in states:
            if not b.in_rotation or not b.metrics:
                continue
            scraped += 1
            kind = b.kind or "unknown"
            for name, snap in b.metrics.items():
                if (not isinstance(snap, dict)
                        or snap.get("kind") != "histogram"
                        or not name.startswith("serving/")):
                    continue
                groups.setdefault(kind, {}).setdefault(
                    name, []).append(snap)
        fleet: dict = {}
        for kind, per_name in groups.items():
            for name, snaps in per_name.items():
                try:
                    merged = merge_histogram_snapshots(snaps, name=name)
                except ValueError:
                    continue  # mixed bucket ladders: skip, don't 500
                row = _quantile_row(merged)
                if row is None:
                    continue
                row["backends"] = len(snaps)
                series = {}
                for sel, child in sorted(merged.series().items()):
                    srow = _quantile_row(child)
                    if srow is not None:
                        series[sel] = srow
                if series:
                    row["series"] = series
                fleet.setdefault(kind, {})[name] = row
        return {"backends_scraped": scraped, "fleet": fleet}

    def healthz(self) -> dict:
        return {
            "ready": self.ready,
            "draining": self.draining,
            "backends_total": len(self._backends),
            "backends_healthy": self.healthy_count,
            "uptime_s": round(time.monotonic() - self._t0, 3),
        }

    def loadz(self) -> dict:
        """Routers speak the backend load schema too (fleets can stack:
        a region router over host routers). Queue depth aggregates the
        fleet's last-probed depths plus router-side in-flight."""
        states = self.backend_states()
        depth = sum(b.queue_depth + b.inflight for b in states
                    if b.in_rotation)
        cap = sum(b.queue_capacity for b in states if b.in_rotation)
        from .server import LOADZ_SCHEMA_VERSION

        return {
            "schema": LOADZ_SCHEMA_VERSION,
            "kind": "router",
            "ready": self.ready,
            "draining": self.draining,
            "queue_depth": depth,
            "queue_capacity": cap,
            "load": round(depth / cap, 4) if cap else 0.0,
            "mean_fill": None,
            "slot_occupancy": None,
            "compiles": {"expected": 0, "unexpected": 0,
                         "jit_misses": 0},
        }

    def statz(self) -> dict:
        states = self.backend_states()
        scores = {b.url: 1.0 / (1.0 + b.score()) for b in states
                  if b.in_rotation}
        total_w = sum(scores.values()) or 1.0
        backends = []
        for b in states:
            v = b.view()
            v["weight"] = round(scores.get(b.url, 0.0) / total_w, 4)
            backends.append(v)
        from .server import _stats_readers

        _, quantiles = _stats_readers()
        return {
            **self.healthz(),
            "backends": backends,
            "fleet": {
                "requests": self._m_requests.value,
                "retries": self._m_retries.value,
                "evictions": self._m_evictions.value,
                "readmissions": self._m_readmissions.value,
                "no_backend_503": self._m_no_backend.value,
            },
            "latency": {
                "router_e2e": quantiles("serving/router_e2e_ms"),
                "backends_merged": self.merged_backend_quantiles(),
            },
        }


# ---------------------------------------------------------------------------
# process entrypoint
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    """``python -m paddle_tpu.serving.router``: run the router as its
    own process over a static backend list (port announced through
    ``--port-file``, SIGTERM drains — the ``serving/backend.py``
    lifecycle, applied to the front door)."""
    import argparse
    import signal as _sig
    import threading as _threading

    p = argparse.ArgumentParser(
        prog="paddle_tpu.serving.router",
        description="serving-fleet router process")
    p.add_argument("--backend", action="append", default=[],
                   help="backend base URL (repeatable)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--port-file", default="")
    p.add_argument("--probe-interval-s", type=float, default=None)
    p.add_argument("--retries", type=int, default=None)
    args = p.parse_args(argv)

    router = Router(backends=args.backend, host=args.host,
                    port=args.port,
                    probe_interval_s=args.probe_interval_s,
                    retries=args.retries).start()
    # router-local SLOs (e.g. over serving/router_e2e_ms) from
    # FLAGS_slo_objectives; no-op when the flag is empty
    from ..monitor import slo as _slo

    _slo.install_from_flags()
    if args.port_file:
        from .backend import _announce_port

        _announce_port(args.port_file, router.port)
    import os as _os

    print(f"serving router ready on {router.url} "
          f"({len(args.backend)} backends, pid={_os.getpid()})",
          flush=True)
    stop = _threading.Event()
    _sig.signal(_sig.SIGTERM, lambda s, f: stop.set())
    _sig.signal(_sig.SIGINT, lambda s, f: stop.set())
    stop.wait()
    router.stop(drain=True)
    return 0


if __name__ == "__main__":
    import sys as _sys

    _sys.exit(main())

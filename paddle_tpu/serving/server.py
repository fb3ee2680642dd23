"""HTTP frontend for the online serving subsystem.

A stdlib ``ThreadingHTTPServer`` (same pattern as
``monitor/debug_server.py``: no web framework dependency, daemon serving
threads) exposing:

- ``POST /predict`` — JSON ``{"inputs": {feed: nested-list}, ...}``
  through the dynamic batcher; responds ``{"outputs": {fetch: ...}}``.
  Backpressure maps onto status codes instead of unbounded queueing:
  **429** queue full, **504** deadline expired, **400** malformed
  request, **503** draining/not ready.
- ``GET /healthz`` — READINESS, not liveness: 200 only once every batch
  bucket is compiled (warmup-complete) and the server is not draining;
  503 otherwise. Load balancers gate on this, so a replica never
  receives traffic it would stall on with an XLA compile.
- ``GET /statz`` — serving stats JSON: queue depth, bucket ladder,
  request/batch counters, batch fill, latency quantiles (p50/p99 from
  the stage histograms), compile accounting (warmup vs unexpected), and
  MFU from the cost-model ledger — the ``/clusterz``-style capacity
  math, extended to serving.
- ``GET /metrics`` — the Prometheus dump (every ``serving/*`` metric
  rides the same exporter the training stack uses).
- ``GET /profilez`` — per-op device-time profiles (monitor.opprof):
  replay-measured op table, attribution coverage, time-accuracy
  closure; ``?program=``/``?topk=`` views. Served by both server kinds.

``stop(drain=True)`` is a graceful drain: new work is refused (503),
queued work is flushed through the replicas, waiting HTTP handlers get
their real responses, then the listener closes.
"""
from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from ..errors import InvalidArgumentError
from ..monitor import all_metrics, counter, gauge
from ..monitor import cost_model as _cost
from ..monitor import flight_recorder as _flight
from ..monitor import histogram_quantile, registry_snapshot
from ..monitor import tracing as _tracing
from .batcher import (
    DeadlineExceededError,
    DynamicBatcher,
    QueueFullError,
    ServingClosedError,
)
from .continuous import ContinuousBatcher
from .replica import ReplicaPool

__all__ = ["InferenceServer", "GenerationServer"]


def _json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


#: The machine-oriented load-signal schema (``GET /loadz``) the router
#: tier scrapes instead of the human-oriented ``/statz`` blob. STABLE:
#: fields are only ever added, never renamed or removed, and additions
#: bump ``schema``. Every backend kind serves exactly these keys:
#:
#: - ``schema``      int   — schema version (currently 1)
#: - ``kind``        str   — "predict" | "generate" (routes the router
#:                            may send here)
#: - ``ready``       bool  — warmed AND not draining (admission works)
#: - ``draining``    bool  — shutdown in progress; admissions get 503
#: - ``queue_depth`` int   — requests waiting for a batch/slot
#: - ``queue_capacity`` int
#: - ``load``        float — queue_depth / queue_capacity (the p2c
#:                            comparison signal, normalized)
#: - ``mean_fill``   float|None — predict: batch-slot utilization
#: - ``slot_occupancy`` float|None — generate: busy decode slots ratio
#: - ``compiles``    {"expected": int, "unexpected": int,
#:                    "jit_misses": int} — per-process compile
#:                    accounting
LOADZ_SCHEMA_VERSION = 1


def _histz_payload() -> dict:
    """``GET /histz``: raw snapshots (bounds + per-bucket counts + sum +
    count) of every ``serving/*`` histogram in this process — the
    machine-oriented feed for cross-backend quantile merging
    (``monitor.merge_histogram_snapshots`` on the router side). The
    human-oriented quantiles stay on ``/statz``."""
    return {
        "histograms": {
            name: m.snapshot() for name, m in all_metrics().items()
            if m.kind == "histogram" and name.startswith("serving/")
        },
    }


def _jit_misses() -> int:
    from ..profiler import counters as _pc

    return int(_pc().get("executor::jit_cache_miss", 0))


def _tuned_kernels() -> dict:
    """The /statz tuned-kernel table: every autotuned schedule active
    for THIS device kind (tuning cache entries) plus the tuner's
    dispatch counters — a reader sees which kernels run on measured
    geometry and which still ride the defaults."""
    from ..profiler import counters as _pc
    from ..tuning import tuned_table

    from ..flags import flag as _flag

    c = _pc()
    try:
        rows = tuned_table()
    except Exception:  # a broken tuning cache must not 500 /statz
        rows = []
    return {
        "mode": _flag("kernel_autotune"),
        "entries": rows,
        "counters": {
            "cache_hit": int(c.get("autotune::cache_hit", 0)),
            "cache_miss": int(c.get("autotune::cache_miss", 0)),
            "cache_reject": int(c.get("autotune::cache_reject", 0)),
            "searches": int(c.get("autotune::search", 0)),
        },
    }


def _ir_opt_stats() -> dict:
    """The /statz IR-optimizer table: per-pass rewrite totals from the
    program-IR optimizer (analysis.optimizer) plus its program-version
    cache counters — a reader sees which fusion/remat passes actually
    fired on the programs this process serves and whether steady-state
    dispatch is paying the pipeline or riding the cache."""
    from ..analysis.optimizer import optimizer_stats
    from ..flags import flag as _flag
    from ..profiler import counters as _pc

    c = _pc()
    try:
        passes = optimizer_stats()
    except Exception:  # a broken stats table must not 500 /statz
        passes = {}
    return {
        "level": _flag("ir_opt_level"),
        "passes": passes,
        "counters": {
            "cache_hit": int(c.get("ir_opt::cache_hit", 0)),
            "cache_miss": int(c.get("ir_opt::cache_miss", 0)),
        },
    }


def _opprof_stats() -> dict:
    """The /statz per-op profiler block: stored replay profiles + the
    top-K ops by measured device time (monitor.opprof) — a reader sees
    which ops actually dominate the programs this process serves, with
    the time-accuracy closure next to the predicted cost sheets."""
    from ..monitor import opprof as _opprof

    try:
        return _opprof.opprof_stats()
    except Exception:  # a broken profile store must not 500 /statz
        return {"programs": [], "latest": None, "top_ops": []}


def _stats_readers():
    """One registry snapshot + the counter/quantile readers both statz
    endpoints share (a change to the quantile fields must not have to be
    made twice)."""
    snap = registry_snapshot()
    metrics = all_metrics()

    def val(name):
        return snap.get(name, {}).get("value", 0)

    def quantiles(name):
        h = metrics.get(name)
        if h is None or h.kind != "histogram" or h.count == 0:
            return None
        return {"p50_ms": round(histogram_quantile(h, 0.5), 3),
                "p99_ms": round(histogram_quantile(h, 0.99), 3),
                "count": h.count}

    return val, quantiles


def _utilization(t0, flops0, val):
    """Capacity math from the cost-model ledger: the engine/executor
    dispatches every serving program, so executed FLOPs accumulate
    there; the delta since server construction over uptime is average
    achieved FLOP/s -> MFU against the device peak (the ``/clusterz``
    denominator, extended to serving). Returns (uptime_s, block)."""
    uptime = max(time.monotonic() - t0, 1e-9)
    executed = val("cost/executed_flops") - flops0
    peaks = _cost.device_peaks()
    # plan_accuracy: predicted-vs-actual peak HBM of the most recently
    # compiled statically-planned program (analysis.memory.note_actual);
    # 0 means no planned compile has closed the loop yet
    accuracy = val("memplan/plan_accuracy")
    return uptime, {
        "executed_flops": executed,
        "mfu_avg": round(_cost.mfu(executed / uptime, peaks), 6),
        "device_kind": peaks.get("kind"),
        "peaks_nominal": peaks.get("nominal"),
        "hbm_budget_bytes": peaks.get("hbm_bytes"),
        "plan_accuracy": round(accuracy, 4) if accuracy else None,
    }


def _utilization_window(state, val):
    """Windowed serving MFU/goodput: the executed-FLOPs delta over the
    wall since the PREVIOUS statz read (the stats window), published as
    the ``serving/mfu`` and ``serving/goodput_flops_per_s`` gauges so
    the fleet scrape (/metricz, /fleetz) sees utilization without
    redoing the ledger math. ``state`` is the server's mutable
    ``[t_last, flops_last]`` cell; returns the statz block (None until
    a full window has elapsed)."""
    now = time.monotonic()
    flops = val("cost/executed_flops")
    dt = now - state[0]
    block = None
    if dt > 1e-3:
        rate = max(0.0, flops - state[1]) / dt
        m = _cost.mfu(rate, _cost.device_peaks())
        gauge("serving/goodput_flops_per_s").set(round(rate, 3))
        gauge("serving/mfu").set(round(m, 6))
        block = {"window_s": round(dt, 3),
                 "goodput_flops_per_s": round(rate, 3),
                 "mfu": round(m, 6)}
        state[0] = now
        state[1] = flops
    return block


class ServingHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with a fleet-sized accept backlog. The
    stdlib default (request_queue_size=5) refuses connections under a
    burst of connection-per-request clients — which the router would
    read as a dead backend and evict. Refusals belong to the bounded
    ADMISSION queue (429), never to the TCP accept queue."""

    request_queue_size = 128
    daemon_threads = True


class _BaseHandler(BaseHTTPRequestHandler):
    """Shared plumbing for the serving frontends: JSON replies, silent
    request logging, and the introspection GET routes every server
    exposes (``/healthz`` readiness, ``/statz``, ``/metrics``).

    HTTP/1.1 across the board: every reply carries Content-Length (or
    chunked transfer encoding), so keep-alive is safe — and the fleet
    NEEDS it: connection-per-request across the client->router->backend
    hops costs a TCP handshake plus a handler-thread spawn per hop per
    request, which caps a fleet well below one backend's capacity."""

    server_version = "ptpu-serving/1"
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):  # no per-request stderr chatter
        pass

    @property
    def _srv(self):
        return self.server._inference_server

    def _reply(self, status, payload, ctype="application/json"):
        # status lands on the current request span (>=500 marks the
        # trace errored, so the tail sampler keeps it); a no-op on the
        # untraced GET routes
        _tracing.note_status(status)
        body = (payload if isinstance(payload, str)
                else json.dumps(payload, default=_json_default))
        data = body.encode("utf-8")
        self.send_response(status)
        self.send_header("Content-Type", f"{ctype}; charset=utf-8")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        try:
            self.wfile.write(data)
        except (BrokenPipeError, ConnectionResetError):
            pass

    def _reply_raw(self, status, data: bytes, ctype):
        """Raw-bytes reply (proxied payloads, KV slabs): the caller
        owns the exact Content-Type; everything else matches
        :meth:`_reply`."""
        _tracing.note_status(status)
        self.send_response(status)
        self.send_header("Content-Type", ctype or "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        try:
            self.wfile.write(data)
        except (BrokenPipeError, ConnectionResetError):
            pass

    def _read_body(self):
        """Read (and thereby DRAIN) the POST body before any reply — an
        unread body left on a keep-alive connection parses as the next
        request line and poisons every later request on that socket.
        Returns the raw bytes, or ``None`` after answering 400 to a
        malformed Content-Length (the connection is closed then: with
        an unparseable length the body cannot be drained)."""
        try:
            length = int(self.headers.get("Content-Length", 0) or 0)
        except (TypeError, ValueError):
            self.close_connection = True
            self._reply(400, {"error": "malformed Content-Length"})
            return None
        return self.rfile.read(length) if length > 0 else b"{}"

    def _trace_request(self, name):
        """Open this request's local trace root. An incoming
        ``traceparent`` (the router's per-attempt span) parents this
        process's span tree under the caller's — one trace_id, correct
        parentage, across the process hop."""
        parent = _tracing.parse_traceparent(
            self.headers.get(_tracing.TRACEPARENT_HEADER))
        return _tracing.start_trace(name, parent=parent,
                                    client=self.client_address[0])

    def _try_submit(self, fn):
        """Run an admission call, mapping the shared backpressure
        contract onto statuses: full queue 429, draining/closed 503,
        malformed 400. Returns the submitted request, or ``None`` after
        replying with the error."""
        try:
            return fn()
        except QueueFullError as e:
            self._reply(429, {"error": str(e)})
        except ServingClosedError as e:
            self._reply(503, {"error": str(e)})
        except InvalidArgumentError as e:
            self._reply(400, {"error": str(e)})
        return None

    def _get_common(self, path) -> bool:
        """Serve the shared GET routes; True when handled."""
        srv = self._srv
        if path == "/healthz":
            self._reply(200 if srv.ready else 503, srv.healthz())
        elif path == "/statz":
            self._reply(200, srv.statz())
        elif path == "/loadz":
            self._reply(200, srv.loadz())
        elif path == "/histz":
            self._reply(200, _histz_payload())
        elif path == "/tracez":
            status, payload = _tracing.tracez_payload(
                _tracing.parse_query(self.path))
            self._reply(status, payload)
        elif path == "/profilez":
            from ..monitor import opprof as _opprof

            status, payload = _opprof.profilez_payload(
                _tracing.parse_query(self.path))
            self._reply(status, payload)
        elif path == "/metrics":
            from ..monitor.export import (
                PROMETHEUS_CONTENT_TYPE,
                prometheus_text,
            )

            self._reply(200, prometheus_text(), PROMETHEUS_CONTENT_TYPE)
        elif path == "/metricz":
            # the fleet scrape surface: prometheus text by default;
            # ?format=snapshot is the machine feed (labeled series
            # included) the router's prober merges into /fleetz
            if _tracing.parse_query(self.path).get("format") == "snapshot":
                self._reply(200, {"metrics": registry_snapshot()})
            else:
                from ..monitor.export import (
                    PROMETHEUS_CONTENT_TYPE,
                    prometheus_text,
                )

                self._reply_raw(200, prometheus_text().encode("utf-8"),
                                PROMETHEUS_CONTENT_TYPE)
        elif path == "/sloz":
            from ..monitor import slo as _slo

            self._reply(200, _slo.sloz_payload())
        else:
            return False
        return True


class _ServingHandler(_BaseHandler):
    def do_GET(self):
        path = self.path.split("?", 1)[0].rstrip("/") or "/"
        if self._get_common(path):
            return
        if path == "/":
            self._reply(200, {
                "service": "paddle_tpu serving",
                "routes": ["/predict (POST)", "/healthz", "/statz",
                           "/loadz", "/histz", "/tracez", "/profilez",
                           "/metrics", "/metricz", "/sloz"]})
        else:
            self._reply(404, {"error": f"unknown path {path!r}"})

    def do_POST(self):
        path = self.path.split("?", 1)[0].rstrip("/")
        raw = self._read_body()
        if raw is None:
            return
        if path != "/predict":
            self._reply(404, {"error": f"unknown path {path!r}"})
            return
        # the request's local trace root: batcher/replica/executor spans
        # nest under it; exiting runs the tail-sampling retention
        with self._trace_request("serving::predict"):
            self._predict(raw)

    def _predict(self, raw):
        srv = self._srv
        if not srv.ready:
            self._reply(503, {"error": "not ready"
                              if not srv.draining else "draining"})
            return
        try:
            body = json.loads(raw or b"{}")
            if not isinstance(body, dict):
                raise InvalidArgumentError(
                    "request body must be a JSON object with an "
                    '"inputs" key')
            inputs = self._parse_inputs(body)
            deadline_ms = body.get("deadline_ms")
            if deadline_ms is not None:
                deadline_ms = float(deadline_ms)  # "abc" -> 400, not 500
            tenant = body.get("tenant")
        except (ValueError, TypeError, InvalidArgumentError) as e:
            self._reply(400, {"error": str(e)})
            return
        req = self._try_submit(
            lambda: srv.batcher.submit(inputs, deadline_ms=deadline_ms,
                                       tenant=tenant))
        if req is None:
            return
        _tracing.annotate(rows=int(req.rows))
        try:
            outs = req.wait(srv.request_timeout_s)
        except DeadlineExceededError as e:
            self._reply(504, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001 — a bad batch must answer
            self._reply(500, {"error": f"{type(e).__name__}: {e}"})
            return
        self._reply(200, {
            "outputs": {n: o.tolist()
                        for n, o in zip(srv.fetch_names, outs)},
            "rows": int(req.rows),
        })

    def _parse_inputs(self, body) -> dict:
        srv = self._srv
        raw = body.get("inputs")
        if raw is None:
            raise InvalidArgumentError('request body needs an "inputs" key')
        # single-input convenience: a bare nested list maps to the feed
        if not isinstance(raw, dict):
            if len(srv.feed_names) != 1:
                raise InvalidArgumentError(
                    f'"inputs" must be a dict naming the feeds '
                    f"{srv.feed_names}")
            raw = {srv.feed_names[0]: raw}
        parsed = {}
        for name, val in raw.items():
            spec = srv.input_specs.get(name)
            dtype = spec[1] if spec else None
            try:
                arr = np.asarray(val, dtype=dtype)
            except (ValueError, TypeError) as e:
                raise InvalidArgumentError(
                    f"input {name!r} is not a well-formed {dtype} "
                    f"array: {e}") from None
            parsed[name] = arr
        return parsed


class InferenceServer:
    """Composed serving stack: HTTP frontend -> DynamicBatcher ->
    ReplicaPool over one shared-executable Predictor.

    ``port=0`` binds an ephemeral port (tests, smoke). ``start()`` runs
    warmup by default so ``/healthz`` flips to ready only after every
    bucket is compiled; pass ``warmup=False`` and call :meth:`warmup`
    later to observe the readiness gate from outside.
    """

    def __init__(self, predictor, port=0, host="127.0.0.1", replicas=None,
                 buckets=None, queue_capacity=None, batch_timeout_ms=None,
                 request_timeout_s=60.0):
        self.feed_names = list(predictor.get_input_names())
        self.fetch_names = list(predictor.get_output_names())
        self.batcher = DynamicBatcher(
            self.feed_names, buckets=buckets,
            queue_capacity=queue_capacity,
            batch_timeout_ms=batch_timeout_ms)
        self.pool = ReplicaPool(predictor, self.batcher, replicas=replicas)
        self.input_specs = self.pool._specs
        self.request_timeout_s = request_timeout_s
        self._httpd = ServingHTTPServer((host, int(port)),
                                        _ServingHandler)
        self._httpd._inference_server = self
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread = None
        self._t0 = time.monotonic()
        # MFU baseline: the executed-work ledger is process-global (a
        # model.fit before model.serve leaves training FLOPs in it);
        # statz attributes only the delta since construction to serving
        self._flops0 = registry_snapshot().get(
            "cost/executed_flops", {}).get("value", 0.0)
        self._mfu_window = [self._t0, self._flops0]
        self.draining = False
        self._stopped = False
        from . import _register_live

        _register_live(self)

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    @property
    def ready(self) -> bool:
        return self.pool.warmed and not self.draining

    # -- lifecycle -----------------------------------------------------------

    def start(self, warmup=True):
        """Start replica workers and the HTTP listener; by default also
        warm every bucket so the server comes up ready."""
        self.pool.start()
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._httpd.serve_forever,
                name=f"ptpu-serving:{self.port}", daemon=True)
            self._thread.start()
        _flight.record_event(
            "serving_start", port=self.port,
            replicas=self.pool.replicas,
            buckets=list(self.batcher.buckets))
        if warmup:
            self.warmup()
        return self

    def warmup(self):
        self.pool.warmup()
        return self

    def stop(self, drain=True, timeout=10.0):
        """Graceful shutdown: refuse new work (healthz -> 503,
        /predict -> 503), flush queued work through the replicas when
        ``drain``, then close the listener."""
        if self._stopped:
            return
        self._stopped = True
        self.draining = True
        self.pool.stop(drain=drain, timeout=timeout)  # closes the batcher
        t = self._thread
        if t is not None and t.is_alive():
            # shutdown() blocks on an event only serve_forever() sets —
            # calling it on a never-started listener would hang forever
            self._httpd.shutdown()
        self._httpd.server_close()
        if t is not None:
            t.join(timeout=5)
        self._thread = None
        _flight.record_event("serving_stop", port=self.port, drain=drain)

    # -- introspection payloads ---------------------------------------------

    def healthz(self) -> dict:
        return {
            "ready": self.ready,
            "warmed": self.pool.warmed,
            "draining": self.draining,
            "uptime_s": round(time.monotonic() - self._t0, 3),
            "buckets": list(self.batcher.buckets),
            "replicas": self.pool.replicas,
            "queue_depth": self.batcher.queue_depth(),
            "queue_capacity": self.batcher.queue_capacity,
        }

    def loadz(self) -> dict:
        """The compact router-facing load signal (see
        :data:`LOADZ_SCHEMA_VERSION` for the schema contract). Direct
        counter reads only — no registry walk, cheap enough to scrape
        every probe interval."""
        rows = counter("serving/batched_rows_total").value
        slots = counter("serving/batch_slots_total").value
        depth = self.batcher.queue_depth()
        return {
            "schema": LOADZ_SCHEMA_VERSION,
            "kind": "predict",
            "ready": self.ready,
            "draining": self.draining,
            "queue_depth": depth,
            "queue_capacity": self.batcher.queue_capacity,
            "load": round(depth / self.batcher.queue_capacity, 4),
            "mean_fill": round(rows / slots, 4) if slots else None,
            "slot_occupancy": None,
            "compiles": {
                "expected": len(self.batcher.buckets),
                "unexpected": counter(
                    "serving/unexpected_compiles").value,
                "jit_misses": _jit_misses(),
            },
        }

    def statz(self) -> dict:
        val, quantiles = _stats_readers()
        batches = val("serving/batches_total")
        slots = val("serving/batch_slots_total")
        rows = val("serving/batched_rows_total")
        out = {
            **self.healthz(),
            "requests": {
                "submitted": val("serving/requests_total"),
                "completed": val("serving/responses_total"),
                "rejected_429": val("serving/rejected_total"),
                "deadline_expired": val("serving/deadline_expired_total"),
                "errors": val("serving/errors_total"),
            },
            "batches": {
                "dispatched": batches,
                "rows": rows,
                "padded_rows": val("serving/padded_rows_total"),
                "mean_fill": round(rows / slots, 4) if slots else 0.0,
            },
            "latency": {
                "queue": quantiles("serving/queue_ms"),
                "assemble": quantiles("serving/assemble_ms"),
                "dispatch": quantiles("serving/dispatch_ms"),
                "e2e": quantiles("serving/e2e_ms"),
            },
            "compiles": {
                "buckets": len(self.batcher.buckets),
                "unexpected": val("serving/unexpected_compiles"),
            },
            # top-5 end-to-end requests from the trace store: trace_id +
            # per-stage breakdown, the jump-off point to /tracez?id=...
            "slowest": _tracing.slowest_table(5, root_prefix="serving::"),
            # which pallas kernels run on autotuned geometry here
            "tuned_kernels": _tuned_kernels(),
            # which IR-optimizer passes rewrote the served programs
            "ir_opt": _ir_opt_stats(),
            # per-op replay profiles + top-K ops by measured device time
            "opprof": _opprof_stats(),
        }
        _, out["utilization"] = _utilization(self._t0, self._flops0, val)
        out["utilization"]["window"] = _utilization_window(
            self._mfu_window, val)
        return out


# ---------------------------------------------------------------------------
# generative inference frontend
# ---------------------------------------------------------------------------


#: POST route each generation backend kind answers (the disaggregation
#: contract: a prefill tier only prefills, a decode tier only continues
#: handed-off slabs — anything else 404s, which the router's kind-aware
#: pick treats as "re-pick", never "fail the request")
_KIND_ROUTES = {"generate": "/generate", "prefill": "/prefill",
                "decode": "/generate_kv"}


class _GenerationHandler(_BaseHandler):
    def do_GET(self):
        path = self.path.split("?", 1)[0].rstrip("/") or "/"
        if self._get_common(path):
            return
        if path == "/":
            self._reply(200, {
                "service": "paddle_tpu generation",
                "kind": self._srv.kind,
                "routes": [f"{_KIND_ROUTES[self._srv.kind]} (POST)",
                           "/healthz", "/statz", "/loadz", "/histz",
                           "/tracez", "/profilez", "/metrics",
                           "/metricz", "/sloz"]})
        else:
            self._reply(404, {"error": f"unknown path {path!r}"})

    def do_POST(self):
        path = self.path.split("?", 1)[0].rstrip("/")
        raw = self._read_body()
        if raw is None:
            return
        if path == "/prefix_known":
            # prefix-cache peer negotiation: a prefill tier (via the
            # router) asks which page chain-hashes this backend's index
            # already holds, then ships only the rest header-only
            self._prefix_known(raw)
            return
        if path != _KIND_ROUTES[self._srv.kind]:
            self._reply(404, {
                "error": f"unknown path {path!r} (this backend's kind "
                         f"is {self._srv.kind!r})"})
            return
        if path == "/generate":
            with self._trace_request("serving::generate"):
                self._generate(raw)
        elif path == "/prefill":
            with self._trace_request("serving::prefill"):
                self._prefill(raw)
        else:
            with self._trace_request("serving::generate_kv"):
                self._generate_kv(raw)

    def _prefix_known(self, raw):
        """``POST /prefix_known`` ``{"hashes": [...]}``: the subset (as
        a prefix chain) this backend's page index holds. Ring layouts
        answer an empty set — every page must ship."""
        try:
            body = json.loads(raw or b"{}")
            hashes = [str(h) for h in (body.get("hashes") or [])]
        except (ValueError, TypeError) as e:
            self._reply(400, {"error": f"malformed body: {e}"})
            return
        known = self._srv.engine.known_page_hashes(hashes)
        self._reply(200, {"known": sorted(known),
                          "layout": self._srv.engine.kv_cache_layout})

    @staticmethod
    def _parse_gen_body(raw) -> dict:
        """Parse/validate the ``/generate`` (and ``/prefill``) JSON
        body into its parameters; raises on malformed input (mapped to
        400 by the callers)."""
        body = json.loads(raw or b"{}")
        if not isinstance(body, dict):
            raise InvalidArgumentError(
                'request body must be a JSON object with a "prompt" key')
        prompt = body.get("prompt")
        if (not isinstance(prompt, (list, tuple)) or not prompt
                or not all(isinstance(t, int) for t in prompt)):
            raise InvalidArgumentError(
                '"prompt" must be a non-empty list of token ids (ints)')
        max_new = body.get("max_new_tokens")
        temperature = body.get("temperature")
        deadline_ms = body.get("deadline_ms")
        return {
            "prompt": list(prompt),
            "max_new_tokens": int(max_new) if max_new is not None
            else None,
            "temperature": float(temperature)
            if temperature is not None else None,
            "deadline_ms": float(deadline_ms)
            if deadline_ms is not None else None,
            "stream": bool(body.get("stream", False)),
            # tenant dimension for the labeled serving histograms (the
            # cardinality bound makes a hostile value cost one series)
            "tenant": str(body["tenant"])
            if body.get("tenant") is not None else None,
        }

    def _check_ready(self, srv) -> bool:
        if not srv.ready:
            self._reply(503, {"error": "not ready"
                              if not srv.draining else "draining"})
            return False
        return True

    def _wait_and_reply(self, srv, req):
        """Block on a submitted request and answer with the standard
        non-streamed payload / error mapping."""
        try:
            tokens = req.wait(srv.request_timeout_s)
        except DeadlineExceededError as e:
            self._reply(504, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001 — a failed step must answer
            self._reply(500, {"error": f"{type(e).__name__}: {e}"})
            return
        self._reply(200, {
            "tokens": tokens,
            "finish_reason": req.finish_reason,
            "prompt_tokens": req.prompt_len,
        })

    def _generate(self, raw):
        srv = self._srv
        if not self._check_ready(srv):
            return
        try:
            p = self._parse_gen_body(raw)
        except (ValueError, TypeError, InvalidArgumentError) as e:
            self._reply(400, {"error": str(e)})
            return
        _tracing.annotate(prompt_tokens=len(p["prompt"]),
                          stream=p["stream"])
        submit = lambda **kw: srv.scheduler.submit(  # noqa: E731
            p["prompt"], max_new_tokens=p["max_new_tokens"],
            temperature=p["temperature"], deadline_ms=p["deadline_ms"],
            tenant=p["tenant"], **kw)
        if p["stream"]:
            self._generate_stream(srv, submit)
            return
        req = self._try_submit(submit)
        if req is None:
            return
        self._wait_and_reply(srv, req)

    def _prefill(self, raw):
        """Prefill-tier leg of a disaggregated ``/generate``: run the
        bucket-ladder forward, sample the first token, and answer with
        the slot's KV slab (``generation.handoff`` wire format). The
        original request's generation parameters — and the prompt
        itself, which a speculative decode tier needs — ride in the
        slab header, so the router can forward bytes without
        re-parsing anything.

        A paged prefill tier answers PAGE-GRANULAR (``PTKP``) when the
        body asks with ``"page_format": true``; ``"known_hashes"`` (the
        decode tier's ``known_page_hashes`` answer, forwarded by the
        router) lets it ship header-only entries for pages the far side
        already holds — the prefix-cache wire saving."""
        from ..generation.handoff import (
            HANDOFF_CONTENT_TYPE,
            HANDOFF_PAGED_CONTENT_TYPE,
            pack_kv_pages,
            pack_kv_slab,
        )

        srv = self._srv
        if not self._check_ready(srv):
            return
        try:
            p = self._parse_gen_body(raw)
            body = json.loads(raw or b"{}")
            page_format = bool(body.get("page_format", False))
            known_hashes = [str(h) for h in
                            (body.get("known_hashes") or [])]
            if page_format and not srv.engine.paged:
                raise InvalidArgumentError(
                    "page_format needs kv_cache_layout=paged on the "
                    "prefill tier")
            srv.engine.validate(
                p["prompt"],
                p["max_new_tokens"]
                if p["max_new_tokens"] is not None
                else srv.engine.default_max_new_tokens)
        except (ValueError, TypeError, InvalidArgumentError) as e:
            self._reply(400, {"error": str(e)})
            return
        _tracing.annotate(prompt_tokens=len(p["prompt"]), prefill=True,
                          page_format=page_format)
        meta = {
            "params": {k: p[k] for k in
                       ("prompt", "max_new_tokens", "temperature",
                        "deadline_ms", "stream", "tenant")},
            "cache": srv.cache_geometry(),
        }
        try:
            if page_format:
                pages, length, first = srv.run_prefill_pages(
                    p["prompt"], p["temperature"],
                    known_hashes=known_hashes)
                blob = pack_kv_pages(pages, length, first,
                                     srv.engine.page_size, meta=meta)
                ctype = HANDOFF_PAGED_CONTENT_TYPE
            else:
                planes, length, first = srv.run_prefill(
                    p["prompt"], p["temperature"])
                blob = pack_kv_slab(planes, length, first, meta=meta)
                ctype = HANDOFF_CONTENT_TYPE
        except ServingClosedError as e:
            self._reply(503, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001 — a failed forward must answer
            self._reply(500, {"error": f"{type(e).__name__}: {e}"})
            return
        self._reply_raw(200, blob, ctype)

    def _generate_kv(self, raw):
        """Decode-tier leg: land a handed-off KV slab in a decode slot
        and continue the generation — the slab's riding parameters
        reconstruct the original request (including streaming). Both
        wire formats land here, told apart by magic: ``PTKV``
        (contiguous slab) and ``PTKP`` (page-granular, paged tiers
        only)."""
        from ..generation.handoff import (
            HandoffError,
            unpack_kv_pages,
            unpack_kv_slab,
        )

        srv = self._srv
        if not self._check_ready(srv):
            return
        paged_wire = raw[:4] == b"PTKP"
        try:
            if paged_wire:
                if not srv.engine.paged:
                    raise HandoffError(
                        "page-granular slab needs kv_cache_layout=paged "
                        "on this decode tier (ring tiers speak PTKV)")
                slab = unpack_kv_pages(raw)
                length, meta = slab.length, slab.meta
                if slab.page_size != srv.engine.page_size:
                    raise HandoffError(
                        f"KV page slab page_size {slab.page_size} does "
                        f"not match this tier's {srv.engine.page_size}")
            else:
                planes, length, first, meta = unpack_kv_slab(raw)
            mine = srv.cache_geometry()
            theirs = meta.get("cache") or {}
            bad = {k: (theirs.get(k), mine[k]) for k in mine
                   if theirs.get(k) != mine[k]}
            if bad:
                raise HandoffError(
                    f"KV slab geometry does not match this decode tier: "
                    f"{bad} (sender vs receiver)")
            if srv.engine.speculative:
                # a speculative decode tier re-prefills the DRAFT from
                # the prompt at admission, which needs a covering
                # bucket on THIS tier's ladder — reject now as the 400
                # the handoff promises, not a 500 out of the decode
                # loop after a prefill-tier forward was already spent
                srv.engine.bucket_for(length)
        except (HandoffError, InvalidArgumentError) as e:
            self._reply(400, {"error": str(e)})
            return
        p = dict(meta.get("params") or {})
        stream = bool(p.get("stream", False))
        _tracing.annotate(prompt_tokens=length, handoff=True,
                          stream=stream, page_granular=paged_wire)
        if paged_wire:
            submit = lambda **kw: srv.scheduler.submit_prefilled_pages(  # noqa: E731,E501
                slab,
                max_new_tokens=p.get("max_new_tokens"),
                temperature=p.get("temperature"),
                deadline_ms=p.get("deadline_ms"),
                prompt=p.get("prompt"), tenant=p.get("tenant"), **kw)
        else:
            submit = lambda **kw: srv.scheduler.submit_prefilled(  # noqa: E731,E501
                planes, length, first,
                max_new_tokens=p.get("max_new_tokens"),
                temperature=p.get("temperature"),
                deadline_ms=p.get("deadline_ms"),
                prompt=p.get("prompt"), tenant=p.get("tenant"), **kw)
        if stream:
            self._generate_stream(srv, submit)
            return
        req = self._try_submit(submit)
        if req is None:
            return
        self._wait_and_reply(srv, req)

    def _generate_stream(self, srv, submit):
        """Chunked ndjson streaming: one ``{"token": id}`` line per
        decoded token as it is produced, then a final ``{"done": ...}``
        line with the full result — the scheduler's ``on_token`` hook
        feeding an HTTP chunk per decode step. ``submit`` is the
        parameter-bound scheduler call (plain or handed-off)."""
        import queue as _queue

        q = _queue.Queue()
        req = self._try_submit(lambda: submit(on_token=q.put))
        if req is None:
            return
        # the chunked path bypasses _reply — record the status here
        _tracing.note_status(200)
        self.send_response(200)
        self.send_header("Content-Type",
                         "application/x-ndjson; charset=utf-8")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def chunk(obj):
            data = (json.dumps(obj, default=_json_default) + "\n").encode()
            self.wfile.write(f"{len(data):x}\r\n".encode()
                             + data + b"\r\n")

        t_end = time.monotonic() + srv.request_timeout_s
        try:
            while True:
                try:
                    chunk({"token": q.get(timeout=0.1)})
                    continue
                except _queue.Empty:
                    pass
                if req.finished or time.monotonic() > t_end:
                    break
            while not q.empty():  # tokens landed between poll and finish
                chunk({"token": q.get_nowait()})
            if req.error is not None:
                # the 200 status line is long gone: mark the trace
                # errored so the tail sampler keeps this stream
                sp = _tracing.current_span()
                if sp is not None:
                    sp.set_error(f"{type(req.error).__name__}: "
                                 f"{req.error}")
                chunk({"error": f"{type(req.error).__name__}: "
                                f"{req.error}"})
            elif not req.finished:
                sp = _tracing.current_span()
                if sp is not None:
                    sp.set_error("stream timeout")
                _tracing.flag_current_trace("timeout")
                chunk({"error": "stream timeout"})
            else:
                chunk({"done": True, "tokens": req.tokens,
                       "finish_reason": req.finish_reason,
                       "prompt_tokens": req.prompt_len})
            self.wfile.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError):
            pass  # client went away; decoding continues
        finally:
            # every exit abandons the local queue — a still-decoding
            # request must stop feeding it (timeout/error paths would
            # otherwise accumulate every remaining token unread)
            req.on_token = None


class GenerationServer:
    """Composed generative-serving stack: HTTP frontend ->
    ContinuousBatcher (slot scheduler) -> GenerationEngine over a causal
    LM.

    ``model_or_engine`` is either a ready :class:`GenerationEngine` or a
    causal LM (``GPTForCausalLM``-shaped), in which case an engine is
    built from the ``generation_*`` flags / keyword overrides. As with
    :class:`InferenceServer`, ``start()`` warms by default so
    ``/healthz`` readiness means every prefill bucket AND the decode
    step are compiled.

    ``kind`` is the backend's role in a (possibly disaggregated) fleet
    — ``generate`` serves ``/generate`` end to end; ``prefill`` runs
    only the bucket-ladder forward and ships KV slabs (``/prefill``);
    ``decode`` admits handed-off slabs into decode slots
    (``/generate_kv``). Each kind warms exactly its own program set
    (``engine.expected_compiles(kind)``) and reports its kind on
    ``/loadz`` so the router can route and the autoscaler can size the
    tiers independently.
    """

    def __init__(self, model_or_engine, port=0, host="127.0.0.1",
                 slots=None, cache_len=None, prefill_buckets=None,
                 queue_capacity=None, max_new_tokens=None,
                 temperature=None, top_k=None, kv_cache_dtype=None,
                 draft_model=None, draft_k=None, kind=None,
                 request_timeout_s=120.0):
        from ..flags import flag as _flag

        self.kind = str(kind if kind is not None else _flag("backend_kind"))
        if self.kind not in _KIND_ROUTES:
            raise InvalidArgumentError(
                f"backend kind must be one of {sorted(_KIND_ROUTES)}, "
                f"got {self.kind!r}")
        if hasattr(model_or_engine, "step") and hasattr(
                model_or_engine, "admit"):
            dropped = {
                "slots": slots, "cache_len": cache_len,
                "prefill_buckets": prefill_buckets,
                "max_new_tokens": max_new_tokens,
                "temperature": temperature, "top_k": top_k,
                "kv_cache_dtype": kv_cache_dtype,
                "draft_model": draft_model, "draft_k": draft_k,
            }
            bad = sorted(k for k, v in dropped.items() if v is not None)
            if bad:
                raise InvalidArgumentError(
                    f"GenerationServer got a ready engine AND engine-"
                    f"construction kwargs {bad}; configure them on the "
                    "engine, or pass the model instead")
            self.engine = model_or_engine
        else:
            from ..generation.engine import GenerationEngine

            self.engine = GenerationEngine(
                model_or_engine, slots=slots, cache_len=cache_len,
                prefill_buckets=prefill_buckets,
                max_new_tokens=max_new_tokens, temperature=temperature,
                top_k=top_k, kv_cache_dtype=kv_cache_dtype,
                draft_model=draft_model, draft_k=draft_k)
        self.scheduler = ContinuousBatcher(
            self.engine, queue_capacity=queue_capacity, kind=self.kind)
        # prefill tier: prefill_export mutates no cache state, so
        # handler threads run a few forwards CONCURRENTLY (XLA overlaps
        # one dispatch's compute with the next one's host prep) behind
        # a bounded semaphore; the waiter count is the tier's /loadz
        # queue-depth pressure (what the autoscaler sizes on)
        self._prefill_concurrency = 4
        self._prefill_sem = threading.BoundedSemaphore(
            self._prefill_concurrency)
        # waiter count mutated by concurrent handler threads: the +=/-=
        # read-modify-write needs a guard or the /loadz gauge the tier
        # autoscaler sizes on drifts permanently
        self._prefill_count_lock = threading.Lock()
        self._prefill_waiting = 0
        self._prefill_active = 0
        self.request_timeout_s = request_timeout_s
        self._httpd = ServingHTTPServer((host, int(port)),
                                        _GenerationHandler)
        self._httpd._inference_server = self
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread = None
        self._t0 = time.monotonic()
        snap = registry_snapshot()
        self._flops0 = snap.get(
            "cost/executed_flops", {}).get("value", 0.0)
        self._mfu_window = [self._t0, self._flops0]
        self._tokens0 = snap.get(
            "serving/gen_tokens_total", {}).get("value", 0)
        self.draining = False
        self._stopped = False
        from . import _register_live

        _register_live(self)

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    @property
    def ready(self) -> bool:
        return self.engine.warmed and not self.draining

    # -- lifecycle -----------------------------------------------------------

    def start(self, warmup=True):
        if self.kind != "prefill":
            # a prefill tier never decodes: no slot scheduler loop —
            # its engine runs synchronously under the prefill lock
            self.scheduler.start()
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._httpd.serve_forever,
                name=f"ptpu-generation:{self.port}", daemon=True)
            self._thread.start()
        _flight.record_event(
            "generation_server_start", port=self.port,
            backend_kind=self.kind, slots=self.engine.slots,
            prefill_buckets=list(self.engine.prefill_buckets),
            cache_len=self.engine.cache_len,
            speculative=self.engine.speculative)
        if warmup:
            self.warmup()
        return self

    def warmup(self):
        self.engine.warmup(kind=self.kind)
        return self

    def run_prefill(self, prompt, temperature=None):
        """Bounded-concurrency prefill-tier forward (the waiter count
        is this tier's /loadz pressure)."""
        if self.draining:
            raise ServingClosedError("prefill backend draining")
        with self._prefill_count_lock:
            self._prefill_waiting += 1
        acquired = False
        try:
            with self._prefill_sem:
                # holding a slot is utilization, not backlog: move out
                # of the waiter count so queue_depth means QUEUED (the
                # decode tier's semantics — a tier at full concurrency
                # with nothing waiting must not read as backlogged)
                with self._prefill_count_lock:
                    self._prefill_waiting -= 1
                    self._prefill_active += 1
                    acquired = True
                return self.engine.prefill_export(prompt, temperature)
        finally:
            with self._prefill_count_lock:
                if acquired:
                    self._prefill_active -= 1
                else:
                    self._prefill_waiting -= 1

    def run_prefill_pages(self, prompt, temperature=None,
                          known_hashes=()):
        """Page-granular :meth:`run_prefill`: same bounded-concurrency
        forward, answered as content-hashed pages with the ones in
        ``known_hashes`` shipped header-only."""
        if self.draining:
            raise ServingClosedError("prefill backend draining")
        with self._prefill_count_lock:
            self._prefill_waiting += 1
        acquired = False
        try:
            with self._prefill_sem:
                with self._prefill_count_lock:
                    self._prefill_waiting -= 1
                    self._prefill_active += 1
                    acquired = True
                return self.engine.prefill_export_pages(
                    prompt, temperature, known_hashes=known_hashes)
        finally:
            with self._prefill_count_lock:
                if acquired:
                    self._prefill_active -= 1
                else:
                    self._prefill_waiting -= 1

    def _suggested_slots(self):
        """Decode slots the device HBM budget would fit at this
        geometry, or None when the budget is unknown (statz field)."""
        try:
            return self.engine.suggest_decode_slots()
        except Exception:
            return None

    def cache_geometry(self) -> dict:
        """The slab-compatibility contract both handoff tiers must
        agree on — checked before any insert."""
        e = self.engine
        return {
            "layers": e._num_layers, "heads": e._num_heads,
            "head_dim": e._head_dim, "cache_len": e.cache_len,
            "kv_dtype": e.kv_cache_dtype,
        }

    def stop(self, drain=True, timeout=30.0):
        if self._stopped:
            return
        self._stopped = True
        self.draining = True
        self.scheduler.stop(drain=drain, timeout=timeout)
        t = self._thread
        if t is not None and t.is_alive():
            # shutdown() blocks on an event only serve_forever() sets —
            # calling it on a never-started listener would hang forever
            self._httpd.shutdown()
        self._httpd.server_close()
        if t is not None:
            t.join(timeout=5)
        self._thread = None
        _flight.record_event("generation_server_stop", port=self.port,
                             drain=drain)

    # -- introspection payloads ---------------------------------------------

    def healthz(self) -> dict:
        return {
            "ready": self.ready,
            "kind": self.kind,
            "warmed": self.engine.warmed,
            "draining": self.draining,
            "uptime_s": round(time.monotonic() - self._t0, 3),
            "slots": self.engine.slots,
            "slots_busy": self.scheduler.live_slots,
            "cache_len": self.engine.cache_len,
            "kv_cache_layout": self.engine.kv_cache_layout,
            "prefill_buckets": list(self.engine.prefill_buckets),
            "queue_depth": self.scheduler.queue_depth(),
            "queue_capacity": self.scheduler.queue_capacity,
        }

    def loadz(self) -> dict:
        """Router-facing load signal; same stable schema as the predict
        server's (``mean_fill`` is the predict-side field, decode-slot
        occupancy is the generation analog). The ``kind`` field routes
        a disaggregated fleet: prefill tiers report their serialized-
        forward waiter count as queue depth (compute pressure), decode
        tiers the slot queue (HBM pressure) — each tier's autoscaler
        sizes on its own signal."""
        if self.kind == "prefill":
            depth = self._prefill_waiting
            occupancy = round(
                self._prefill_active / self._prefill_concurrency, 4)
        else:
            depth = self.scheduler.queue_depth()
            occupancy = round(self.scheduler.occupancy(), 4)
        return {
            "schema": LOADZ_SCHEMA_VERSION,
            "kind": self.kind,
            "ready": self.ready,
            "draining": self.draining,
            "queue_depth": depth,
            "queue_capacity": self.scheduler.queue_capacity,
            "load": round(depth / self.scheduler.queue_capacity, 4),
            "mean_fill": None,
            "slot_occupancy": occupancy,
            "compiles": {
                "expected": self.engine.expected_compiles(self.kind),
                "unexpected": counter(
                    "serving/gen_unexpected_compiles").value,
                "jit_misses": _jit_misses(),
            },
        }

    def statz(self) -> dict:
        val, quantiles = _stats_readers()
        uptime, utilization = _utilization(self._t0, self._flops0, val)
        utilization["window"] = _utilization_window(self._mfu_window, val)
        tokens = val("serving/gen_tokens_total") - self._tokens0
        out = {
            **self.healthz(),
            "requests": {
                "submitted": val("serving/gen_requests_total"),
                "completed": val("serving/gen_responses_total"),
                "rejected_429": val("serving/gen_rejected_total"),
                "deadline_expired": val("serving/gen_expired_total"),
                "errors": val("serving/gen_errors_total"),
            },
            "generation": {
                "tokens_generated": tokens,
                "tokens_per_sec": round(tokens / uptime, 3),
                "slot_occupancy": round(self.scheduler.occupancy(), 4),
                "midbatch_admissions": val(
                    "serving/gen_midbatch_admissions_total"),
                # KV-cache economics: what decode capacity costs in HBM
                # (int8 mode ~4x fewer bytes/token -> ~2x the slots at
                # equal HBM; FLAGS_generation_kv_cache_dtype)
                "kv_cache_dtype": self.engine.kv_cache_dtype,
                "kv_bytes_per_token": self.engine.kv_bytes_per_token(),
                "kv_cache_bytes": self.engine.cache_nbytes(),
                # static capacity plan: what the geometry needs vs what
                # the device offers, and the slots the budget would fit
                # (analysis/memory + engine.suggest_decode_slots)
                "hbm_required_bytes": self.engine.hbm_required_bytes(),
                "suggested_decode_slots": self._suggested_slots(),
            },
            # speculative decoding economics: proposals accepted per
            # round decide how many full-model dispatches each token
            # costs (acceptance_rate * k + 1 tokens per verify)
            "speculative": self.engine.spec_stats(),
            # paged-KV economics: pool occupancy, CoW traffic, and the
            # prefix index's hit accounting, global + per tenant
            # (layout "ring" reports just the layout name)
            "paging": self.engine.paging_stats(),
            "latency": {
                "token": quantiles("serving/gen_token_ms"),
                "ttft": quantiles("serving/gen_ttft_ms"),
                "e2e": quantiles("serving/gen_e2e_ms"),
            },
            "compiles": {
                "prefill_buckets": len(self.engine.prefill_buckets),
                "decode": 2 if self.engine.speculative else 1,
                "expected": self.engine.expected_compiles(self.kind),
                "unexpected": val("serving/gen_unexpected_compiles"),
            },
            "slowest": _tracing.slowest_table(5, root_prefix="serving::"),
            "utilization": utilization,
            # which pallas kernels run on autotuned geometry here
            "tuned_kernels": _tuned_kernels(),
            # which IR-optimizer passes rewrote the served programs
            "ir_opt": _ir_opt_stats(),
            # per-op replay profiles + top-K ops by measured device time
            "opprof": _opprof_stats(),
        }
        return out

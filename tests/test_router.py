"""Serving fleet: router tier + autoscaler.

Pins the fleet contracts: power-of-two-choices dispatch prefers the
less-loaded backend, connection failures retry on the next backend while
ANSWERED work never replays, a draining backend (503 at admission) is
evicted immediately and re-admitted only via /healthz readiness —
including the race where the drain starts mid-dispatch — fleet p50/p99
merged from backend /histz bucket counts match a single pooled-histogram
golden, and the autoscaler's hysteresis/cooldown decisions are
deterministic under an injected clock.

Router mechanics run against in-process STUB backends (no XLA) so the
policies are tested in isolation; one end-to-end test drives real
InferenceServers through the router for the full-stack contract.
"""
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.error import HTTPError
from urllib.request import Request, urlopen

import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.monitor import (
    Histogram,
    histogram_quantile,
    merge_histogram_snapshots,
)
from paddle_tpu.serving import (
    AutoScaler,
    BackendState,
    FleetSignals,
    InferenceServer,
    LaunchedBackend,
    Router,
)

FEED = "x"
IN_DIM = 6
OUT_DIM = 3


# -- stub backend -------------------------------------------------------------


class StubBackend:
    """A fake serving backend: speaks /healthz, /loadz, /histz, and the
    POST routes with scriptable behavior — router policies get tested
    without XLA in the loop."""

    def __init__(self, kind="predict", name="stub"):
        self.kind = kind
        self.name = name
        self.ready = True
        self.draining = False
        self.queue_depth = 0
        self.queue_capacity = 8
        self.hist = {}
        self.post_hits = 0
        self.post_status = 200
        self.post_delay_s = 0.0
        self.on_post = None       # hook(stub) called while handling
        self.stream_chunks = None  # list[bytes] -> chunked reply
        stub = self

        class _H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _json(self, status, payload):
                data = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                path = self.path.rstrip("/")
                if path == "/healthz":
                    ok = stub.ready and not stub.draining
                    self._json(200 if ok else 503, {"ready": ok})
                elif path == "/loadz":
                    self._json(200, {
                        "schema": 1, "kind": stub.kind,
                        "ready": stub.ready and not stub.draining,
                        "draining": stub.draining,
                        "queue_depth": stub.queue_depth,
                        "queue_capacity": stub.queue_capacity,
                        "load": stub.queue_depth / stub.queue_capacity,
                        "mean_fill": None, "slot_occupancy": None,
                        "compiles": {"expected": 0, "unexpected": 0,
                                     "jit_misses": 0}})
                elif path == "/histz":
                    self._json(200, {"histograms": stub.hist})
                else:
                    self._json(404, {"error": path})

            def do_POST(self):
                # drain the body: unread bytes would poison the
                # keep-alive connection the router pools
                length = int(self.headers.get("Content-Length", 0))
                if length:
                    self.rfile.read(length)
                stub.post_hits += 1
                if stub.on_post is not None:
                    stub.on_post(stub)
                if stub.post_delay_s:
                    time.sleep(stub.post_delay_s)
                if stub.stream_chunks is not None:
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/x-ndjson")
                    self.send_header("Transfer-Encoding", "chunked")
                    self.end_headers()
                    for chunk in stub.stream_chunks:
                        self.wfile.write(f"{len(chunk):x}\r\n".encode()
                                         + chunk + b"\r\n")
                        self.wfile.flush()
                    self.wfile.write(b"0\r\n\r\n")
                    return
                self._json(stub.post_status,
                           {"ok": stub.post_status == 200,
                            "backend": stub.name})

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), _H)
        self._httpd.daemon_threads = True
        self.url = f"http://127.0.0.1:{self._httpd.server_address[1]}"
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)
        self._thread.start()

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


@pytest.fixture()
def stubs():
    live = []

    def make(**kw):
        s = StubBackend(**kw)
        live.append(s)
        return s

    yield make
    for s in live:
        try:
            s.stop()
        except Exception:
            pass


def _post(url, path="/predict", payload=None):
    body = json.dumps(payload or {"inputs": [[0.0]]}).encode()
    try:
        r = urlopen(Request(url + path, data=body,
                            headers={"Content-Type": "application/json"}))
        return r.status, json.loads(r.read())
    except HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


# -- dispatch policies --------------------------------------------------------


def test_p2c_prefers_less_loaded_backend(stubs):
    """With two candidates, p2c compares both every time — a heavily
    queued backend must receive none of the traffic."""
    light, heavy = stubs(name="light"), stubs(name="heavy")
    heavy.queue_depth = 7
    router = Router(backends=[light.url, heavy.url],
                    probe_interval_s=30).start()
    try:
        for _ in range(8):
            status, out = _post(router.url)
            assert status == 200 and out["backend"] == "light"
        assert heavy.post_hits == 0
        assert light.post_hits == 8
    finally:
        router.stop(drain=False)


def test_connect_failure_retries_next_backend_and_evicts(stubs):
    """A backend that dies after admission: dispatch hits a closed port,
    the router evicts it and replays the request on the survivor — the
    client sees one clean 200."""
    dead, live = stubs(name="dead"), stubs(name="live")
    live.queue_depth = 3  # steer the first pick onto the dying backend
    router = Router(backends=[dead.url, live.url],
                    probe_interval_s=30).start()
    try:
        assert router.healthy_count == 2
        dead.stop()  # listener gone; router hasn't probed since
        for _ in range(4):
            status, out = _post(router.url)
            assert status == 200 and out["backend"] == "live"
        states = {b.url: b for b in router.backend_states()}
        assert not states[dead.url].in_rotation
        assert states[dead.url].last_error in ("connect", "no_response")
        sz = router.statz()
        assert sz["fleet"]["evictions"] >= 1
        assert sz["fleet"]["retries"] >= 1
    finally:
        router.stop(drain=False)


def test_answered_errors_pass_through_without_retry(stubs):
    """Statuses a backend actually ANSWERED (429/400/500) must surface
    to the client untouched: the work was dispatched (or the request is
    bad) and replaying it elsewhere would double-execute / re-fail."""
    a, b = stubs(name="a"), stubs(name="b")
    router = Router(backends=[a.url, b.url], probe_interval_s=30).start()
    try:
        for status in (429, 400, 500):
            a.post_status = b.post_status = status
            got, _ = _post(router.url)
            assert got == status
        hits = a.post_hits + b.post_hits
        assert hits == 3  # one attempt per request: no retries
        assert all(s.in_rotation for s in router.backend_states())
    finally:
        router.stop(drain=False)


def test_admission_503_evicts_immediately_and_retries(stubs):
    """A draining backend answers 503 at admission: the request was
    REFUSED, not dispatched — the router must evict it from rotation at
    once and land the request on the next backend."""
    draining, ok = stubs(name="draining"), stubs(name="ok")
    ok.queue_depth = 5  # steer the first pick onto the draining backend
    router = Router(backends=[draining.url, ok.url],
                    probe_interval_s=30).start()
    # the drain begins AFTER admission to the fleet (no probe will run
    # before the dispatch: the 503 answer itself must do the evicting)
    draining.post_status = 503
    draining.draining = True
    try:
        status, out = _post(router.url)
        assert status == 200 and out["backend"] == "ok"
        states = {b.url: b for b in router.backend_states()}
        assert not states[draining.url].in_rotation
        assert states[draining.url].last_error == "admission_503"
        # evicted means evicted: the next request never knocks there
        hits0 = draining.post_hits
        assert _post(router.url)[0] == 200
        assert draining.post_hits == hits0
    finally:
        router.stop(drain=False)


def test_drain_mid_dispatch_completes_in_flight_work(stubs):
    """THE RACE: a backend starts draining while a dispatched request is
    in flight. Draining servers complete already-admitted work, so the
    in-flight request must come back 200 (and must NOT be replayed);
    only LATER admissions see 503 and trigger the eviction."""
    b1, b2 = stubs(name="b1"), stubs(name="b2")
    b2.queue_depth = 99  # steer the first request onto b1

    def begin_drain(stub):
        # the drain races the dispatch: admission already happened, the
        # handler is running — from now on new admissions get 503
        stub.draining = True

    b1.on_post = begin_drain
    router = Router(backends=[b1.url, b2.url], probe_interval_s=30).start()
    try:
        status, out = _post(router.url)
        assert status == 200 and out["backend"] == "b1"
        assert b1.post_hits == 1  # answered once, replayed nowhere
        # the backend is now draining; its next admission refuses and
        # the router evicts + retries onto b2
        b1.on_post = None
        b1.post_status = 503
        b2.queue_depth = 0
        status, out = _post(router.url)
        assert status == 200 and out["backend"] == "b2"
        states = {b.url: b for b in router.backend_states()}
        assert not states[b1.url].in_rotation
    finally:
        router.stop(drain=False)


def test_readmission_only_after_healthz_readiness(stubs):
    """An evicted backend rejoins rotation ONLY when a probe sees
    /healthz readiness flip back — not via a lucky dispatch."""
    s = stubs(name="s")
    router = Router(backends=[s.url], probe_interval_s=0.05).start()
    try:
        assert router.healthy_count == 1
        s.draining = True
        s.post_status = 503
        deadline = time.monotonic() + 5
        while router.healthy_count and time.monotonic() < deadline:
            time.sleep(0.02)
        assert router.healthy_count == 0  # probe evicted it
        assert _post(router.url)[0] == 503  # no backend in rotation
        s.draining = False  # readiness flips back
        s.post_status = 200
        deadline = time.monotonic() + 5
        while not router.healthy_count and time.monotonic() < deadline:
            time.sleep(0.02)
        assert router.healthy_count == 1
        assert _post(router.url)[0] == 200
        assert router.statz()["fleet"]["readmissions"] >= 1
    finally:
        router.stop(drain=False)


def test_no_backend_is_503(stubs):
    router = Router(probe_interval_s=30).start()
    try:
        status, out = _post(router.url)
        assert status == 503
        assert "no backend" in out["error"]
        assert router.statz()["fleet"]["no_backend_503"] == 1
    finally:
        router.stop(drain=False)


def test_kind_routing_generate_vs_predict(stubs):
    """/generate traffic must only land on generate-kind backends (and
    vice versa) — a mixed fleet is two logical pools behind one door."""
    p = stubs(name="p", kind="predict")
    g = stubs(name="g", kind="generate")
    router = Router(backends=[p.url, g.url], probe_interval_s=30).start()
    try:
        for _ in range(3):
            status, out = _post(router.url, path="/generate",
                                payload={"prompt": [1, 2]})
            assert status == 200 and out["backend"] == "g"
        for _ in range(3):
            status, out = _post(router.url, path="/predict")
            assert status == 200 and out["backend"] == "p"
        assert g.post_hits == 3 and p.post_hits == 3
    finally:
        router.stop(drain=False)


def test_streaming_response_proxies_chunks(stubs):
    """A chunked backend reply (streaming /generate) must arrive at the
    client through the router intact and in order."""
    g = stubs(name="g", kind="generate")
    lines = [json.dumps({"token": i}).encode() + b"\n" for i in range(5)]
    g.stream_chunks = lines
    router = Router(backends=[g.url], probe_interval_s=30).start()
    try:
        r = urlopen(Request(
            router.url + "/generate",
            data=json.dumps({"prompt": [1], "stream": True}).encode(),
            headers={"Content-Type": "application/json"}))
        got = r.read()
        assert got == b"".join(lines)
    finally:
        router.stop(drain=False)


# -- merged fleet quantiles (satellite: histogram merging golden) -------------


def _observe_split(values, shards):
    """Observe ``values`` round-robin into ``shards`` histograms AND one
    pooled histogram; returns (shard_list, pooled)."""
    bounds = (1.0, 5.0, 10.0, 50.0, 100.0)
    hs = [Histogram(f"shard{i}", buckets=bounds) for i in range(shards)]
    pooled = Histogram("pooled", buckets=bounds)
    for i, v in enumerate(values):
        hs[i % shards].observe(v)
        pooled.observe(v)
    return hs, pooled


def test_merge_histogram_snapshots_matches_pooled_golden():
    """Summed bucket counts over shards ≡ one pooled histogram: the
    merged p50/p99 must equal the pooled quantiles EXACTLY (same bounds,
    same counts — not approximately)."""
    rng = np.random.RandomState(7)
    values = rng.gamma(2.0, 9.0, size=600)
    hs, pooled = _observe_split(values, shards=3)
    merged = merge_histogram_snapshots([h.snapshot() for h in hs])
    assert merged.count == pooled.count == 600
    assert merged.bucket_counts() == pooled.bucket_counts()
    for q in (0.5, 0.9, 0.99):
        assert histogram_quantile(merged, q) == pytest.approx(
            histogram_quantile(pooled, q), abs=0.0)


def test_merge_histogram_snapshots_rejects_bound_mismatch():
    a = Histogram("a", buckets=(1.0, 2.0))
    b = Histogram("b", buckets=(1.0, 3.0))
    with pytest.raises(ValueError, match="bounds mismatch"):
        merge_histogram_snapshots([a.snapshot(), b.snapshot()])
    with pytest.raises(ValueError, match=">= 1 snapshot"):
        merge_histogram_snapshots([])


def test_router_statz_merges_backend_histograms(stubs):
    """Router-side p50/p99 computed from two backends' /histz bucket
    counts must match the single pooled histogram golden."""
    rng = np.random.RandomState(11)
    values = rng.gamma(2.0, 9.0, size=400)
    hs, pooled = _observe_split(values, shards=2)
    b1, b2 = stubs(name="b1"), stubs(name="b2")
    b1.hist = {"serving/e2e_ms": hs[0].snapshot()}
    b2.hist = {"serving/e2e_ms": hs[1].snapshot()}
    router = Router(backends=[b1.url, b2.url],
                    probe_interval_s=30).start()
    try:
        merged = router.merged_backend_quantiles(
            names=("serving/e2e_ms",))
        got = merged["serving/e2e_ms"]
        assert got["backends"] == 2
        assert got["count"] == pooled.count
        assert got["p50_ms"] == pytest.approx(
            round(histogram_quantile(pooled, 0.5), 3))
        assert got["p99_ms"] == pytest.approx(
            round(histogram_quantile(pooled, 0.99), 3))
        # the same numbers ride /statz
        sz = router.statz()
        assert sz["latency"]["backends_merged"][
            "serving/e2e_ms"]["p50_ms"] == got["p50_ms"]
    finally:
        router.stop(drain=False)


# -- autoscaler ---------------------------------------------------------------


class _FakeRouter:
    def __init__(self, states=()):
        self.states = list(states)
        self.added = []
        self.removed = []

    def backend_states(self):
        return list(self.states)

    def add_backend(self, url, probe=True):
        self.added.append(url)
        b = BackendState(url)
        b.in_rotation = True
        self.states.append(b)
        return b

    def remove_backend(self, url):
        self.removed.append(url)
        self.states = [b for b in self.states
                       if b.url != url.rstrip("/")]


class _FakeLauncher:
    def __init__(self):
        self.launched = 0
        self.terminated = []

    def launch(self):
        self.launched += 1
        return LaunchedBackend(url=f"http://b{self.launched}")

    def terminate(self, handle, drain=True, timeout_s=15.0):
        self.terminated.append((handle.url, drain))


def _state(url, depth=0, inflight=0, rotation=True):
    b = BackendState(url)
    b.in_rotation = rotation
    b.queue_depth = depth
    b.inflight = inflight
    return b


def _sig(now, healthy=1, total=None, depth=0.0, inflight=0):
    return FleetSignals(
        time=now, backends_total=total if total is not None else healthy,
        backends_healthy=healthy, mean_queue_depth=depth,
        max_queue_depth=int(depth), total_inflight=inflight, host={})


def test_scaler_hysteresis_requires_full_window():
    """One spiky tick must not scale; `window` CONSECUTIVE pressured
    ticks must — and a neutral tick in between resets the streak."""
    sc = AutoScaler(_FakeRouter(), _FakeLauncher(), min_backends=1,
                    max_backends=4, up_queue_depth=4.0, window=3,
                    cooldown_s=60, clock=lambda: 0.0)
    assert sc.decide(_sig(0, depth=9)) is None
    assert sc.decide(_sig(1, depth=9)) is None
    assert sc.decide(_sig(2, depth=0, inflight=1)) is None  # reset
    assert sc.decide(_sig(3, depth=9)) is None
    assert sc.decide(_sig(4, depth=9)) is None
    assert sc.decide(_sig(5, depth=9)) == "up"


def test_scaler_cooldown_suppresses_and_resets():
    """After an action, pressure during the cooldown neither acts nor
    pre-charges the streak; past the cooldown a full fresh window is
    required again."""
    clk = [0.0]
    router, launcher = _FakeRouter(), _FakeLauncher()
    sc = AutoScaler(router, launcher, min_backends=1, max_backends=4,
                    up_queue_depth=4.0, window=2, cooldown_s=100,
                    clock=lambda: clk[0])
    for t in (0, 1):
        clk[0] = t
        action = sc.decide(_sig(t, depth=9))
    assert action == "up"
    sc.scale_up(_sig(1, depth=9))
    assert launcher.launched == 1 and router.added == ["http://b1"]
    for t in (2, 50, 99):  # inside cooldown: nothing accumulates
        clk[0] = t
        assert sc.decide(_sig(t, depth=9)) is None
    clk[0] = 102  # past cooldown: streak must rebuild from zero
    assert sc.decide(_sig(102, depth=9)) is None
    clk[0] = 103
    assert sc.decide(_sig(103, depth=9)) == "up"


def test_scaler_bounds_and_dark_fleet():
    """max_backends caps scale-up; zero healthy backends IS scale-up
    pressure regardless of queue math (the fleet is answering 503s)."""
    sc = AutoScaler(_FakeRouter(), _FakeLauncher(), min_backends=1,
                    max_backends=2, up_queue_depth=4.0, window=1,
                    cooldown_s=0, clock=lambda: 0.0)
    assert sc.decide(_sig(0, healthy=0, total=1, depth=0.0)) == "up"
    # at the ceiling: pressure no longer scales
    assert sc.decide(_sig(1, healthy=2, total=2, depth=99.0)) is None


def test_scaler_scale_down_drains_least_loaded_owned():
    """Scale-down picks the least-loaded backend the scaler OWNS,
    removes it from rotation first, then terminates with drain=True;
    min_backends floors the fleet."""
    seed = _state("http://seed", depth=1)
    router = _FakeRouter([seed])
    launcher = _FakeLauncher()
    sc = AutoScaler(router, launcher, min_backends=1, max_backends=4,
                    up_queue_depth=4.0, down_queue_depth=0.5, window=2,
                    cooldown_s=0, clock=lambda: 0.0)
    h1 = sc.scale_up(_sig(0, healthy=1))   # owns b1
    h2 = sc.scale_up(_sig(0, healthy=2))   # owns b2
    states = {b.url: b for b in router.backend_states()}
    states[h1.url].queue_depth = 3
    states[h2.url].queue_depth = 0         # least loaded owned
    assert sc.decide(_sig(1, healthy=3, depth=0.0)) is None
    assert sc.decide(_sig(2, healthy=3, depth=0.0)) == "down"
    sc.scale_down(_sig(2, healthy=3, depth=0.0))
    assert router.removed == [h2.url]
    assert launcher.terminated == [(h2.url, True)]
    assert sorted(sc.owned) == [h1.url]
    # the seed backend (not owned) is never a victim, and min_backends
    # holds: healthy==min -> no further down decision
    assert sc.decide(_sig(3, healthy=1, depth=0.0)) is None
    sc.stop(drain=False)
    assert not sc.owned and len(launcher.terminated) == 2


def test_scaler_reaps_crashed_owned_backends():
    """A dead backend PROCESS must be forgotten (router + owned) so it
    stops holding a backends_total slot — otherwise it blocks its own
    replacement at max_backends forever."""

    class _DeadProc:
        returncode = -9

        def poll(self):
            return -9

    router = _FakeRouter()
    sc = AutoScaler(router, _FakeLauncher(), min_backends=1,
                    max_backends=2, up_queue_depth=4.0, window=1,
                    cooldown_s=0, clock=lambda: 0.0)
    h = sc.scale_up(_sig(0, healthy=0, total=0))
    states = {b.url: b for b in router.backend_states()}
    states[h.url].in_rotation = False  # the router already evicted it
    h.proc = _DeadProc()
    assert sc.reap_dead() == [h.url]
    assert not sc.owned and router.removed == [h.url]
    # the slot is free again: sustained pressure can now replace it
    sc._last_action_t = None
    assert sc.decide(_sig(1, healthy=0, total=0)) == "up"


def test_scaler_step_acts_through_real_router(stubs):
    """step() against a real Router: sustained pressure launches a stub
    backend (fake launcher boots it) and the router admits it."""
    busy = stubs(name="busy")
    busy.queue_depth = 8
    router = Router(backends=[busy.url], probe_interval_s=30).start()

    live = []

    class _StubLauncher:
        def launch(self):
            s = stubs(name=f"scaled{len(live)}")
            live.append(s)
            return LaunchedBackend(url=s.url)

        def terminate(self, handle, drain=True, timeout_s=15.0):
            pass

    sc = AutoScaler(router, _StubLauncher(), min_backends=1,
                    max_backends=2, up_queue_depth=4.0, window=2,
                    cooldown_s=0, clock=time.monotonic)
    try:
        assert sc.step() is None
        assert sc.step() == "up"
        assert router.healthy_count == 2
        assert len(live) == 1 and live[0].url in sc.owned
        # traffic now reaches the scaled-up backend (it is the lighter)
        status, out = _post(router.url)
        assert status == 200 and out["backend"] == "scaled0"
    finally:
        sc.stop(drain=False)
        router.stop(drain=False)


# -- real-backend end-to-end --------------------------------------------------


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("fleet") / "model")
    static.enable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    try:
        x = static.data(FEED, [None, IN_DIM], "float32")
        h = static.nn.fc(x, 8, name="rt_fc1")
        y = static.nn.fc(h, OUT_DIM, name="rt_fc2")
        exe = static.Executor()
        exe.run_startup()
        static.save_inference_model(d, [FEED], [y], exe)
    finally:
        static.disable_static()
        static.reset_default_programs()
    return d


def test_router_e2e_real_backends(model_dir):
    """Full stack: two real InferenceServers behind the router — parity
    with a direct predictor, /loadz discovery (kind, compile counters),
    fleet statz, and a live drain: the drained backend is evicted while
    every request still answers 200."""
    pred_ref = create_predictor(Config(model_dir))
    rng = np.random.RandomState(0)
    reqs = [rng.randn(r, IN_DIM).astype("float32")
            for r in (1, 2, 3, 1, 2, 3)]
    refs = [np.asarray(pred_ref.run([a])[0]) for a in reqs]

    s1 = InferenceServer(create_predictor(Config(model_dir)), port=0,
                         buckets=(1, 2, 4), batch_timeout_ms=1.0).start()
    s2 = InferenceServer(create_predictor(Config(model_dir)), port=0,
                         buckets=(1, 2, 4), batch_timeout_ms=1.0).start()
    router = Router(backends=[s1.url, s2.url],
                    probe_interval_s=0.1).start()
    try:
        assert router.healthy_count == 2
        states = {b.url: b for b in router.backend_states()}
        for b in states.values():
            assert b.kind == "predict"
            assert b.compiles["expected"] == 3
        for a, ref in zip(reqs, refs):
            status, out = _post(router.url,
                                payload={"inputs": a.tolist()})
            assert status == 200, out
            got = np.asarray(next(iter(out["outputs"].values())),
                             dtype="float32")
            np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
        # drain one backend mid-fleet: requests keep answering 200 on
        # the survivor, the drained one leaves rotation via probe/503
        s1.draining = True
        for a, ref in zip(reqs, refs):
            status, out = _post(router.url,
                                payload={"inputs": a.tolist()})
            assert status == 200, out
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            states = {b.url: b for b in router.backend_states()}
            if not states[s1.url].in_rotation:
                break
            time.sleep(0.02)
        assert not states[s1.url].in_rotation
        # a request is counted after its reply is relayed: wait for the
        # last one's
        while (router.statz()["fleet"]["requests"] < 12
               and time.monotonic() < deadline):
            time.sleep(0.02)
        sz = router.statz()
        assert sz["fleet"]["requests"] >= 12
        assert sz["backends_healthy"] == 1
    finally:
        router.stop(drain=True)
        s1.stop(drain=False)
        s2.stop(drain=False)


def test_kill9_of_a_backend_process_is_invisible_to_clients(model_dir):
    """Two real backend PROCESSES behind the router, one SIGKILLed in the
    middle of a burst: every client request still answers 200 (the
    refused connection retries on the survivor and evicts the victim),
    the retried request keeps ONE trace_id over two attempt spans, the
    survivor drains to exit code 0 and nothing is left listening."""
    import os
    import signal
    from urllib.error import URLError

    from paddle_tpu.monitor import tracing
    from paddle_tpu.serving import SubprocessLauncher

    clients, per_client = 4, 15
    launcher = SubprocessLauncher(model_dir, buckets=(1, 2, 4),
                                  batch_timeout_ms=1.0, queue_capacity=256,
                                  startup_timeout_s=120.0)
    handles = [launcher.launch(), launcher.launch()]
    # a long probe interval: the victim must leave rotation through the
    # DISPATCH path (refused connection -> evict -> retry), not a probe
    router = Router(backends=[h.url for h in handles],
                    probe_interval_s=60.0).start()
    try:
        assert router.healthy_count == 2
        for h in handles:
            lz = json.loads(urlopen(h.url + "/loadz", timeout=10).read())
            assert lz["ready"] and lz["kind"] == "predict"
            assert lz["compiles"]["jit_misses"] == 3
            assert lz["compiles"]["unexpected"] == 0

        statuses, lock = [], threading.Lock()

        def client():
            for i in range(per_client):
                rows = np.zeros(((i % 3) + 1, IN_DIM)).tolist()
                try:
                    s = _post(router.url, payload={"inputs": rows})[0]
                except (URLError, ConnectionError, OSError) as e:
                    s = f"conn: {type(e).__name__}"  # counts as a failure
                with lock:
                    statuses.append(s)

        threads = [threading.Thread(target=client) for _ in range(clients)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 60
        while (len(statuses) < clients * per_client // 4
               and time.monotonic() < deadline):
            time.sleep(0.002)  # kill once the burst is in flight
        victim, survivor = handles
        os.kill(victim.proc.pid, signal.SIGKILL)
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        victim.proc.wait(10)

        assert statuses == [200] * (clients * per_client), sorted(
            set(map(str, statuses)))
        sz = router.statz()
        assert sz["fleet"]["evictions"] >= 1 and sz["fleet"]["retries"] >= 1
        assert sz["backends_healthy"] == 1
        merged = sz["latency"]["backends_merged"]
        assert merged["serving/e2e_ms"]["count"] > 0
        # the retried request: one trace, two attempts, the first errored
        retried = [r for r in tracing.store().summaries()
                   if "retry" in r["kept"]]
        assert retried
        atts = [s for s in tracing.store().get(retried[0]["trace_id"])["spans"]
                if s["name"] == "serving::attempt"]
        assert len(atts) >= 2
        assert len({s["trace_id"] for s in atts}) == 1
        assert len({s["span_id"] for s in atts}) == len(atts)
        failed = [s for s in atts if s.get("error")]
        ok = [s for s in atts if s["attrs"].get("status") == 200]
        assert failed[0]["attrs"]["backend"] == victim.url
        assert ok[0]["attrs"]["backend"] == survivor.url

        launcher.terminate(survivor, drain=True)
        assert survivor.proc.returncode == 0
        router.stop(drain=True)
        for url in (router.url, victim.url, survivor.url):
            with pytest.raises((URLError, ConnectionError, OSError)):
                urlopen(url + "/healthz", timeout=2)
    finally:
        router.stop(drain=False)
        for h in handles:
            launcher.terminate(h, drain=False, timeout_s=5)


def test_loadz_schema_stable_and_statz_unchanged(model_dir):
    """/loadz serves exactly the documented schema (the router contract)
    and /statz keeps its original shape — the human view and the
    machine view must not drift into each other."""
    srv = InferenceServer(create_predictor(Config(model_dir)), port=0,
                          buckets=(1, 2)).start()
    try:
        lz = json.loads(urlopen(srv.url + "/loadz").read())
        assert set(lz) == {"schema", "kind", "ready", "draining",
                           "queue_depth", "queue_capacity", "load",
                           "mean_fill", "slot_occupancy", "compiles"}
        assert lz["schema"] == 1 and lz["kind"] == "predict"
        assert lz["ready"] is True and lz["draining"] is False
        assert set(lz["compiles"]) == {"expected", "unexpected",
                                       "jit_misses"}
        assert lz["compiles"]["expected"] == 2
        sz = json.loads(urlopen(srv.url + "/statz").read())
        for key in ("requests", "batches", "latency", "compiles",
                    "queue_depth", "buckets", "replicas"):
            assert key in sz, key
        hz = json.loads(urlopen(srv.url + "/histz").read())
        assert set(hz) == {"histograms"}
        for snap in hz["histograms"].values():
            assert {"bounds", "buckets", "sum", "count"} <= set(snap)
    finally:
        srv.stop(drain=False)


def test_generation_server_loadz_schema():
    """The generation server speaks the same /loadz schema with the
    slot-occupancy field populated instead of mean_fill."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny_config
    from paddle_tpu.serving import GenerationServer

    paddle.seed(3)
    cfg = gpt_tiny_config()
    cfg.attention_window = 16
    srv = GenerationServer(GPTForCausalLM(cfg), port=0, slots=2,
                           cache_len=16, prefill_buckets=(4, 8))
    try:
        lz = srv.loadz()
        assert lz["schema"] == 1 and lz["kind"] == "generate"
        assert lz["ready"] is False  # never warmed
        assert lz["slot_occupancy"] == 0.0 and lz["mean_fill"] is None
        assert lz["compiles"]["expected"] == 3  # 2 prefill buckets + 1
    finally:
        srv.stop(drain=False)

"""Online serving subsystem: dynamic batcher, replica pool, HTTP frontend.

Covers the production contracts: bucket padding is numerically inert
(batched == unbatched goldens), deadlines expire WITHOUT dispatch, the
compile count stays bounded at the bucket-ladder length across mixed
traffic, Predictor clones share one executable cache, a full queue
rejects (429) instead of growing, and drain completes in-flight work.
"""
import json
import threading
import time
from urllib.error import HTTPError
from urllib.request import Request, urlopen

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.static as static
from paddle_tpu import profiler
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.serving import (
    DeadlineExceededError,
    DynamicBatcher,
    InferenceServer,
    QueueFullError,
    ReplicaPool,
    ServingClosedError,
    parse_buckets,
    predictor_input_specs,
)

FEED = "x"
IN_DIM = 6
OUT_DIM = 3


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    """A tiny fc inference model saved once for the whole module."""
    d = str(tmp_path_factory.mktemp("serving") / "model")
    static.enable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    try:
        x = static.data(FEED, [None, IN_DIM], "float32")
        h = static.nn.fc(x, 8, name="s_fc1")
        y = static.nn.fc(h, OUT_DIM, name="s_fc2")
        exe = static.Executor()
        exe.run_startup()
        static.save_inference_model(d, [FEED], [y], exe)
    finally:
        static.disable_static()
        static.reset_default_programs()
    return d


@pytest.fixture()
def predictor(model_dir):
    return create_predictor(Config(model_dir))


def _jit_misses():
    return profiler.counters().get("executor::jit_cache_miss", 0)


def _rand(rows, seed=0):
    return np.random.RandomState(seed).randn(rows, IN_DIM).astype("float32")


# -- bucket ladder -----------------------------------------------------------

def test_parse_buckets():
    assert parse_buckets("1,2,4,8") == (1, 2, 4, 8)
    assert parse_buckets((2, 16)) == (2, 16)
    from paddle_tpu.errors import InvalidArgumentError

    for bad in ("", "0,2", "4,2", "2,2", "a,b"):
        with pytest.raises(InvalidArgumentError):
            parse_buckets(bad)


def test_submit_validation(predictor):
    b = DynamicBatcher([FEED], buckets=(1, 2, 4), queue_capacity=4)
    from paddle_tpu.errors import InvalidArgumentError

    with pytest.raises(InvalidArgumentError):
        b.submit({"wrong": _rand(1)})
    with pytest.raises(InvalidArgumentError):
        b.submit({FEED: np.float32(3.0)})  # scalar: no batch axis
    with pytest.raises(InvalidArgumentError):
        b.submit({FEED: _rand(5)})  # 5 rows > largest bucket 4
    b.close(drain=False)
    with pytest.raises(ServingClosedError):
        b.submit({FEED: _rand(1)})


# -- padding goldens ---------------------------------------------------------

def test_batched_results_match_unbatched(predictor, model_dir):
    """Bucket padding must be numerically inert: every batched result is
    identical to a direct unbatched Predictor.run on the same rows."""
    ref_pred = create_predictor(Config(model_dir))  # separate cache
    batcher = DynamicBatcher([FEED], buckets=(1, 2, 4, 8),
                             queue_capacity=64, batch_timeout_ms=1.0)
    pool = ReplicaPool(predictor, batcher, replicas=2).warmup()
    pool.start()
    try:
        cases = [(_rand(r, seed=r), None) for r in (1, 2, 3, 5, 8, 1, 3)]
        handles = [batcher.submit({FEED: a}) for a, _ in cases]
        for (a, _), h in zip(cases, handles):
            out = h.wait(timeout=30)
            assert len(out) == 1 and out[0].shape == (a.shape[0], OUT_DIM)
            ref = np.asarray(ref_pred.run([a])[0])
            np.testing.assert_allclose(out[0], ref, rtol=1e-5, atol=1e-6)
    finally:
        pool.stop(drain=False)


# -- deadline expiry ---------------------------------------------------------

def test_deadline_expiry_never_dispatches():
    b = DynamicBatcher([FEED], buckets=(1, 2), queue_capacity=8,
                       batch_timeout_ms=0.0)
    from paddle_tpu import monitor

    batches_before = monitor.counter("serving/batches_total").value
    req = b.submit({FEED: _rand(1)}, deadline_ms=1.0)
    time.sleep(0.02)
    # a worker arriving after the deadline finds only the expired request
    assert b.next_batch(timeout=0.01) is None
    with pytest.raises(DeadlineExceededError):
        req.wait(timeout=1)
    assert monitor.counter("serving/batches_total").value == batches_before
    assert monitor.counter("serving/deadline_expired_total").value >= 1
    b.close(drain=False)


def test_live_request_still_dispatchable():
    b = DynamicBatcher([FEED], buckets=(1, 2), queue_capacity=8,
                       batch_timeout_ms=0.0)
    req = b.submit({FEED: _rand(2)}, deadline_ms=10_000)
    batch = b.next_batch(timeout=0.5)
    assert batch is not None and batch.rows == 2 and batch.bucket == 2
    b.complete(batch, [np.zeros((2, OUT_DIM), "float32")])
    assert req.wait(timeout=1)[0].shape == (2, OUT_DIM)
    b.close(drain=False)


# -- bounded compiles --------------------------------------------------------

def test_compile_count_bounded_across_mixed_traffic(predictor):
    """100 mixed-size requests may cost at most len(buckets) compiles —
    the tentpole invariant, asserted via the profiler counters."""
    buckets = (1, 2, 4, 8)
    batcher = DynamicBatcher([FEED], buckets=buckets, queue_capacity=128,
                             batch_timeout_ms=0.5)
    pool = ReplicaPool(predictor, batcher, replicas=2)
    before = _jit_misses()
    pool.warmup()
    assert _jit_misses() - before == len(buckets)
    pool.start()
    try:
        rng = np.random.RandomState(42)
        handles = []
        for i in range(100):
            rows = int(rng.randint(1, 9))
            handles.append(batcher.submit(
                {FEED: rng.randn(rows, IN_DIM).astype("float32")}))
        for h in handles:
            h.wait(timeout=60)
        assert _jit_misses() - before == len(buckets)
        assert pool.extra_compiles() == 0
    finally:
        pool.stop(drain=False)


def test_clone_shares_compiled_cache(predictor):
    """Predictor.clone(): same Executor (compile counter stays flat when
    the clone runs an already-compiled shape), per-clone IO handles."""
    a = _rand(4)
    ref = np.asarray(predictor.run([a])[0])
    before = _jit_misses()
    clone = predictor.clone()
    assert clone._exe is predictor._exe
    assert clone._inputs is not predictor._inputs
    out = np.asarray(clone.run([a])[0])
    assert _jit_misses() == before  # zero extra compiles
    np.testing.assert_allclose(out, ref, rtol=1e-6)
    # clone IO is independent: staging on the clone leaves the parent
    clone.get_input_handle(FEED).copy_from_cpu(_rand(2))
    assert predictor.get_input_handle(FEED)._data.shape == (4, IN_DIM)


# -- backpressure / drain ----------------------------------------------------

def test_feature_shape_mismatch_rejected_at_admission(predictor):
    """A request that couldn't concatenate must be rejected at submit()
    (the pool arms spec validation on its batcher), so it can never
    poison the innocent requests co-assembled with it."""
    from paddle_tpu.errors import InvalidArgumentError

    batcher = DynamicBatcher([FEED], buckets=(1, 2, 4), queue_capacity=8,
                             batch_timeout_ms=0.5)
    assert batcher.input_specs is None
    pool = ReplicaPool(predictor, batcher, replicas=1)
    assert batcher.input_specs is not None  # pool armed validation
    with pytest.raises(InvalidArgumentError):
        batcher.submit({FEED: np.zeros((1, IN_DIM + 2), "float32")})
    # good requests still flow end to end
    pool.warmup()
    pool.start()
    try:
        out = batcher.predict({FEED: _rand(2)}, timeout=30)
        assert out[0].shape == (2, OUT_DIM)
    finally:
        pool.stop(drain=False)


def test_assembly_failure_spares_the_worker(predictor):
    """With validation unarmed (bare batcher), incompatible feature
    shapes that meet in one batch must fail THOSE requests and leave the
    worker alive for the next batch."""
    b = DynamicBatcher([FEED], buckets=(1, 2, 4), queue_capacity=8,
                       batch_timeout_ms=50.0)
    good = b.submit({FEED: _rand(1)})
    bad = b.submit({FEED: np.zeros((1, IN_DIM + 3), "float32")})
    assert b.next_batch(timeout=0.5) is None  # assembly failed, no batch
    with pytest.raises(ValueError):
        good.wait(timeout=1)
    with pytest.raises(ValueError):
        bad.wait(timeout=1)
    # the batcher still works afterwards
    ok = b.submit({FEED: _rand(2)})
    batch = b.next_batch(timeout=0.5)
    assert batch is not None and batch.rows == 2
    b.complete(batch, [np.zeros((2, OUT_DIM), "float32")])
    assert ok.wait(timeout=1)[0].shape == (2, OUT_DIM)
    b.close(drain=False)


def test_queue_full_rejects():
    b = DynamicBatcher([FEED], buckets=(1, 2), queue_capacity=3)
    from paddle_tpu import monitor

    for _ in range(3):
        b.submit({FEED: _rand(1)})
    with pytest.raises(QueueFullError):
        b.submit({FEED: _rand(1)})
    assert monitor.counter("serving/rejected_total").value >= 1
    b.close(drain=False)


def test_close_without_drain_fails_queued():
    b = DynamicBatcher([FEED], buckets=(1, 2), queue_capacity=8)
    req = b.submit({FEED: _rand(1)})
    b.close(drain=False)
    with pytest.raises(ServingClosedError):
        req.wait(timeout=1)


def test_drain_completes_in_flight_work(predictor):
    """stop(drain=True) on a PAUSED pool must still flush everything
    already queued before the workers exit."""
    batcher = DynamicBatcher([FEED], buckets=(1, 2, 4), queue_capacity=32,
                             batch_timeout_ms=0.5)
    pool = ReplicaPool(predictor, batcher, replicas=2).warmup()
    pool.start()
    pool.pause()
    handles = [batcher.submit({FEED: _rand(r, seed=r)})
               for r in (1, 2, 3, 1, 2)]
    pool.stop(drain=True)  # resumes, closes, flushes, joins
    for h, rows in zip(handles, (1, 2, 3, 1, 2)):
        assert h.wait(timeout=1)[0].shape == (rows, OUT_DIM)
    assert pool.alive == 0
    assert batcher.next_batch(timeout=0.01) is None  # closed + drained


# -- predictor tensor hardening ---------------------------------------------

def test_copy_from_cpu_non_contiguous_and_big_endian(predictor, model_dir):
    h = predictor.get_input_handle(FEED)
    base = np.arange(4 * IN_DIM * 2, dtype=">f4").reshape(4, IN_DIM * 2)
    view = base[:, ::2]  # non-contiguous AND non-native-endian
    h.copy_from_cpu(view)
    staged = h._data
    assert staged.flags["C_CONTIGUOUS"] and staged.dtype.isnative
    np.testing.assert_array_equal(staged, np.ascontiguousarray(
        view).astype("<f4"))
    # and the run path accepts it end to end
    out = predictor.run()
    assert np.asarray(out[0]).shape == (4, OUT_DIM)


# -- HTTP frontend -----------------------------------------------------------

def _post(url, payload):
    body = json.dumps(payload).encode()
    try:
        r = urlopen(Request(url + "/predict", data=body))
        return r.status, json.loads(r.read())
    except HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def test_http_server_end_to_end(predictor, model_dir):
    ref_pred = create_predictor(Config(model_dir))
    srv = InferenceServer(predictor, port=0, replicas=2, buckets=(1, 2, 4),
                          queue_capacity=16, batch_timeout_ms=1.0)
    try:
        srv.start(warmup=False)
        # readiness gates on warmup-complete
        with pytest.raises(HTTPError) as ei:
            urlopen(srv.url + "/healthz")
        assert ei.value.code == 503
        status, out = _post(srv.url, {"inputs": _rand(1).tolist()})
        assert status == 503
        srv.warmup()
        hz = json.loads(urlopen(srv.url + "/healthz").read())
        assert hz["ready"] and hz["buckets"] == [1, 2, 4]

        a = _rand(3, seed=9)
        status, out = _post(srv.url, {"inputs": {FEED: a.tolist()}})
        assert status == 200 and out["rows"] == 3
        got = np.asarray(next(iter(out["outputs"].values())), "float32")
        np.testing.assert_allclose(
            got, np.asarray(ref_pred.run([a])[0]), rtol=1e-4, atol=1e-5)

        # malformed requests are 400, not 500 (or a dropped socket)
        for bad in ({}, {"inputs": {"nope": [[1.0]]}},
                    {"inputs": {FEED: [["a"] * IN_DIM]}},
                    [1, 2, 3],  # valid JSON, not an object
                    {"inputs": {FEED: [[1.0] * (IN_DIM + 1)]}},  # shape
                    {"inputs": _rand(1).tolist(), "deadline_ms": "abc"}):
            status, _ = _post(srv.url, bad)
            assert status == 400, bad

        sz = json.loads(urlopen(srv.url + "/statz").read())
        assert sz["requests"]["completed"] >= 1
        assert sz["compiles"]["unexpected"] == 0
        assert "mfu_avg" in sz["utilization"]
        prom = urlopen(srv.url + "/metrics").read().decode()
        assert "serving_requests_total" in prom
    finally:
        srv.stop(drain=False)


def test_http_429_and_deadline(predictor):
    srv = InferenceServer(predictor, port=0, replicas=1, buckets=(1, 2),
                          queue_capacity=2, batch_timeout_ms=0.5)
    try:
        srv.start()
        srv.pool.pause()
        parked = [srv.batcher.submit({FEED: _rand(1)}) for _ in range(2)]
        status, out = _post(srv.url, {"inputs": _rand(1).tolist()})
        assert status == 429, out
        # deadline expiry surfaces as 504 through HTTP
        results = []
        t = threading.Thread(target=lambda: results.append(_post(
            srv.url, {"inputs": _rand(1).tolist(), "deadline_ms": 1.0})))
        # one parked slot must be free for the deadline request
        srv.batcher._q.pop()
        t.start()
        time.sleep(0.05)
        srv.pool.resume()
        t.join(timeout=30)
        assert results and results[0][0] == 504, results
        for req in parked[:1]:
            req.wait(timeout=30)
        sz = json.loads(urlopen(srv.url + "/statz").read())
        assert sz["requests"]["rejected_429"] >= 1
    finally:
        srv.stop(drain=False)


def test_model_serve_roundtrip():
    paddle.seed(11)
    import paddle_tpu.nn as nn

    net = nn.Sequential(nn.Linear(IN_DIM, 8), nn.ReLU(),
                        nn.Linear(8, OUT_DIM))
    model = paddle.Model(net)
    srv = model.serve(input_spec=[paddle.jit.InputSpec([None, IN_DIM])],
                      port=0, replicas=2, buckets=(1, 2, 4))
    try:
        a = _rand(2, seed=5)
        status, out = _post(srv.url, {"inputs": a.tolist()})
        assert status == 200
        got = np.asarray(next(iter(out["outputs"].values())), "float32")
        net.eval()
        ref = net(paddle.to_tensor(a)).numpy()
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    finally:
        srv.stop(drain=True)
        assert srv.pool.alive == 0
    with pytest.raises(OSError):  # the listener went with the workers
        urlopen(srv.url + "/healthz", timeout=2)


# -- monitor integration -----------------------------------------------------

def test_histogram_quantile():
    from paddle_tpu import monitor

    h = monitor.histogram("t_serving_q", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 0.5, 5.0, 5.0, 50.0, 50.0, 500.0, 500.0):
        h.observe(v)
    assert monitor.histogram_quantile(h, 0.0) == 0.0
    assert 0 < monitor.histogram_quantile(h, 0.25) <= 1.0
    assert 1.0 < monitor.histogram_quantile(h, 0.5) <= 10.0
    assert monitor.histogram_quantile(h, 0.99) == 100.0  # +Inf clamps
    empty = monitor.histogram("t_serving_q_empty")
    # no observations -> no quantile (None), not a fabricated 0ms
    assert monitor.histogram_quantile(empty, 0.5) is None
    with pytest.raises(ValueError):
        monitor.histogram_quantile(h, 1.5)


def test_serving_metrics_and_flight_events(predictor):
    from paddle_tpu import monitor

    batcher = DynamicBatcher([FEED], buckets=(1, 2), queue_capacity=8,
                             batch_timeout_ms=0.0)
    pool = ReplicaPool(predictor, batcher, replicas=1).warmup()
    pool.start()
    try:
        batcher.predict({FEED: _rand(1)}, timeout=30)
        snap = monitor.registry_snapshot()
        assert snap["serving/requests_total"]["value"] >= 1
        assert snap["serving/batches_total"]["value"] >= 1
        assert snap["serving/e2e_ms"]["count"] >= 1
        assert snap["serving/dispatch_ms"]["count"] >= 1
        kinds = {e.get("kind") for e in
                 monitor.flight_recorder.get_recorder().events()}
        assert "serving_batch" in kinds and "serving_warmup" in kinds
        # serving histograms ride the standard prometheus exporter
        assert "serving_e2e_ms_bucket" in monitor.prometheus_text()
    finally:
        pool.stop(drain=False)

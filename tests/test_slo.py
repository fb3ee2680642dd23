"""Fleet SLO plane: labeled metric families + burn-rate engine + /fleetz.

Pins the observability contracts: labeled children aggregate into their
parent exactly (so pre-label dashboards and merge goldens never move),
the per-family cardinality bound collapses the overflow into one
``other`` series with a flight event, label-aware snapshot merge equals
a single pooled histogram bucket-for-bucket, burn rates match
hand-computed goldens under an injected clock, alert transitions fire
exactly one ``slo_burn`` flight event, the autoscaler treats confirmed
burn as up-pressure, and a 2-process fleet round-trips snapshots through
the router's /fleetz to the same numbers.
"""
import json
import time
from urllib.request import Request, urlopen

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.static as static
from paddle_tpu import monitor
from paddle_tpu.errors import InvalidArgumentError
from paddle_tpu.monitor import slo as slo_mod
from paddle_tpu.monitor import flight_recorder as _flight
from paddle_tpu.monitor.registry import OVERFLOW_LABEL_VALUE

FEED = "x"
IN_DIM = 6


@pytest.fixture(autouse=True)
def _clean():
    monitor.reset_registry(unregister=True)
    slo_mod.reset_engine()
    yield
    slo_mod.reset_engine()
    monitor.reset_registry(unregister=True)


# -- labeled metric families --------------------------------------------------


def test_labeled_children_aggregate_into_parent():
    c = monitor.counter("t_slo/req_total")
    c.labels(kind="predict").inc(3)
    c.labels(kind="generate").inc(2)
    c.inc()  # bare increments still land on the parent alone
    assert c.value == 6
    assert c.labels(kind="predict").value == 3
    h = monitor.histogram("t_slo/lat_ms", buckets=(1.0, 10.0))
    h.labels(kind="predict", tenant="a").observe(0.5)
    h.labels(kind="predict", tenant="b").observe(5.0)
    assert h.count == 2 and h.sum == 5.5
    assert h.labels(kind="predict", tenant="a").count == 1
    # gauges do NOT propagate: a set is not a sum
    g = monitor.gauge("t_slo/depth")
    g.set(7)
    g.labels(kind="predict").set(3)
    assert g.value == 7


def test_label_keyset_fixed_and_child_restrictions():
    c = monitor.counter("t_slo/keys_total")
    c.labels(kind="predict").inc()
    with pytest.raises(ValueError):
        c.labels(tenant="a")  # key set fixed by the first labels() call
    with pytest.raises(ValueError):
        c.labels()  # empty label set
    with pytest.raises(ValueError):
        c.labels(kind="predict").labels(kind="generate")  # child of child


def test_cardinality_bound_collapses_to_other_with_flight_event():
    paddle.set_flags({"metrics_max_series": 3})
    rec = _flight.get_recorder()
    try:
        c = monitor.counter("t_slo/card_total")
        for i in range(3):
            c.labels(tenant=f"t{i}").inc()
        before = sum(1 for e in rec.snapshot(reason="test")["events"]
                     if e["kind"] == "metric_series_overflow")
        c.labels(tenant="t3").inc()
        c.labels(tenant="t4").inc(2)
        # both overflow sets share ONE collapsed child
        other = c.labels(tenant=OVERFLOW_LABEL_VALUE)
        assert other.value == 3
        assert c.value == 6  # parent still aggregates everything
        sels = set(c.series())
        assert 'tenant="other"' in sels and len(sels) == 4
        events = [e for e in rec.snapshot(reason="test")["events"]
                  if e["kind"] == "metric_series_overflow"
                  and e.get("metric") == "t_slo/card_total"]
        assert len(events) - before == 1  # once per family, not per set
    finally:
        paddle.set_flags({"metrics_max_series": 64})


def test_prometheus_text_emits_labeled_series():
    c = monitor.counter("t_slo/exp_total")
    c.labels(kind="predict", tenant="a b").inc(2)
    h = monitor.histogram("t_slo/exp_ms", buckets=(1.0, 10.0))
    h.labels(kind="predict").observe(0.5)
    text = monitor.prometheus_text()
    assert 't_slo_exp_total{kind="predict",tenant="a b"} 2' in text
    assert ('t_slo_exp_ms_bucket{kind="predict",le="1.0"} 1'
            in text)
    assert 't_slo_exp_ms_count{kind="predict"} 1' in text
    # the parent aggregate keeps its bare line
    assert "t_slo_exp_total 2" in text


def test_label_aware_merge_matches_pooled_golden():
    """Merging per-backend labeled snapshots must equal one pooled
    histogram — parent AND per-series — bucket for bucket."""
    bounds = (1.0, 10.0, 100.0)
    obs = {"a": [0.5, 5.0, 50.0, 500.0], "b": [5.0, 5.0, 50.0]}
    snaps = []
    for split in (  # two "backends" observing disjoint halves
            {"a": [0.5, 5.0], "b": [5.0]},
            {"a": [50.0, 500.0], "b": [5.0, 50.0]}):
        monitor.reset_registry(unregister=True)
        h = monitor.histogram("t_slo/merge_ms", buckets=bounds)
        for tenant, vals in split.items():
            for v in vals:
                h.labels(tenant=tenant).observe(v)
        snaps.append(h.snapshot())
    monitor.reset_registry(unregister=True)
    golden = monitor.histogram("t_slo/merge_golden", buckets=bounds)
    for tenant, vals in obs.items():
        for v in vals:
            golden.labels(tenant=tenant).observe(v)
    merged = monitor.merge_histogram_snapshots(snaps, name="m")
    assert (merged.snapshot()["buckets"]
            == golden.snapshot()["buckets"])  # elementwise bucket sums
    assert merged.count == golden.count and merged.sum == golden.sum
    for q in (0.5, 0.99):
        assert (monitor.histogram_quantile(merged, q)
                == monitor.histogram_quantile(golden, q))
    for tenant in obs:
        sel = monitor.format_labels({"tenant": tenant})
        mc, gc = merged.series()[sel], golden.series()[sel]
        assert mc.snapshot()["buckets"] == gc.snapshot()["buckets"]
        assert mc.count == gc.count
        assert (monitor.histogram_quantile(mc, 0.99)
                == monitor.histogram_quantile(gc, 0.99))


# -- SLO engine ---------------------------------------------------------------


def test_parse_selector_and_objective():
    name, labels = slo_mod.parse_selector(
        'serving/e2e_ms{kind=predict,tenant="a"}')
    assert name == "serving/e2e_ms"
    assert labels == {"kind": "predict", "tenant": "a"}
    assert slo_mod.parse_selector("serving/e2e_ms") == (
        "serving/e2e_ms", {})
    s = slo_mod.parse_objective(
        "p99|serving/e2e_ms{kind=predict}|threshold_ms=250"
        "|target=0.99|window_s=600")
    assert s.mode == "latency" and s.threshold_ms == 250.0
    assert s.target == 0.99 and s.window_s == 600.0
    assert s.fast_window_s == 60.0  # max(60, 600/12)
    e = slo_mod.parse_objective(
        "err|serving/errors_total|error_ratio=serving/requests_total"
        "|target=0.999")
    assert e.mode == "error" and e.total_metric == "serving/requests_total"
    with pytest.raises(InvalidArgumentError):
        slo_mod.parse_objective("noselector")
    with pytest.raises(InvalidArgumentError):
        slo_mod.parse_objective("x|m|bogus_field=1")


def test_slo_validation():
    with pytest.raises(InvalidArgumentError):
        slo_mod.SLO("x", "m")  # neither mode
    with pytest.raises(InvalidArgumentError):
        slo_mod.SLO("x", "m", threshold_ms=1, error_ratio="n")  # both
    with pytest.raises(InvalidArgumentError):
        slo_mod.SLO("x", "m", threshold_ms=1, target=1.0)


def test_latency_burn_rate_golden():
    """Hand-computed burn: target 0.9 (budget 0.1), threshold on a
    bucket bound. Window 1: 4 requests, 1 bad -> bad fraction 0.25,
    burn 2.5x. Window 2: 2 requests, both good -> fast burn 0, slow
    burn (1 bad of 6) / 0.1."""
    h = monitor.histogram("t_slo/burn_ms", buckets=(10.0, 100.0))
    eng = slo_mod.SLOEngine(clock=lambda: 0.0)
    eng.add(slo_mod.SLO("g", "t_slo/burn_ms", threshold_ms=10.0,
                        target=0.9, window_s=1200.0))
    tr = eng._tracked["g"]
    eng.sample(now=0.0)
    for v in (1.0, 5.0, 5.0, 50.0):  # 3 good, 1 bad
        h.observe(v)
    eng.sample(now=100.0)
    assert eng._burn(tr, 100.0, 100.0) == pytest.approx(0.25 / 0.1)
    assert eng.max_confirmed_burn() == pytest.approx(2.5)
    for v in (1.0, 1.0):  # 2 good
        h.observe(v)
    eng.sample(now=200.0)
    assert eng._burn(tr, 100.0, 200.0) == pytest.approx(0.0)
    assert eng._burn(tr, 1200.0, 200.0) == pytest.approx(
        (1.0 / 6.0) / 0.1)
    # confirmed burn = min(fast, slow) = 0
    assert eng.max_confirmed_burn() == pytest.approx(0.0)


def test_error_mode_burn_rate_golden():
    bad = monitor.counter("t_slo/err_total")
    total = monitor.counter("t_slo/all_total")
    eng = slo_mod.SLOEngine(clock=lambda: 0.0)
    eng.add(slo_mod.SLO("e", "t_slo/err_total",
                        error_ratio="t_slo/all_total",
                        target=0.99, window_s=1200.0))
    tr = eng._tracked["e"]
    eng.sample(now=0.0)
    total.inc(100)
    bad.inc(2)  # 2% errors against a 1% budget -> burn 2.0
    eng.sample(now=60.0)
    assert eng._burn(tr, 60.0, 60.0) == pytest.approx(0.02 / 0.01)


def test_alert_transition_fires_one_flight_event():
    paddle.set_flags({"slo_burn_alert": 2.0})
    rec = _flight.get_recorder()
    try:
        h = monitor.histogram("t_slo/alert_ms", buckets=(10.0, 100.0))
        eng = slo_mod.SLOEngine(clock=lambda: 0.0)
        eng.add(slo_mod.SLO("a", "t_slo/alert_ms", threshold_ms=10.0,
                            target=0.9, window_s=600.0))
        before = sum(1 for e in rec.snapshot(reason="t")["events"]
                     if e["kind"] == "slo_burn")
        alerts0 = monitor.counter("slo/alerts_total").value
        eng.sample(now=0.0)
        for v in (50.0, 50.0, 1.0, 50.0):  # 75% bad / 10% budget
            h.observe(v)
        for t in (10.0, 20.0, 30.0):  # stays alerting: ONE transition
            eng.sample(now=t)
        events = [e for e in rec.snapshot(reason="t")["events"]
                  if e["kind"] == "slo_burn"]
        assert len(events) - before == 1
        assert events[-1]["slo"] == "a"
        assert events[-1]["fast_burn"] >= 2.0
        assert monitor.counter("slo/alerts_total").value == alerts0 + 1
        payload = eng.sloz_payload(now=30.0)
        row = payload["slos"][0]
        assert row["alerting"] is True
        assert row["burn"]["fast"] >= 2.0
    finally:
        paddle.set_flags({"slo_burn_alert": 14.4})


def test_install_from_flags_and_current_burn():
    paddle.set_flags({
        "slo_objectives":
            "p99|t_slo/flag_ms{kind=predict}|threshold_ms=10"
            "|target=0.9|window_s=600;"
            "err|t_slo/e_total|error_ratio=t_slo/t_total|target=0.99"})
    try:
        installed = slo_mod.install_from_flags(start_sampler=False)
        assert [s.name for s in installed] == ["p99", "err"]
        assert [s.name for s in slo_mod.engine().objectives()] == [
            "p99", "err"]
        assert slo_mod.current_burn() == 0.0  # no samples yet
        # re-install is idempotent (entrypoints may call twice)
        slo_mod.install_from_flags(start_sampler=False)
        assert len(slo_mod.engine().objectives()) == 2
    finally:
        paddle.set_flags({"slo_objectives": ""})


def test_scaler_treats_confirmed_burn_as_up_pressure():
    from paddle_tpu.serving.scaler import AutoScaler, FleetSignals

    class _StubRouter:
        def backend_states(self):
            return []

    sc = AutoScaler(_StubRouter(), launcher=None, min_backends=1,
                    max_backends=4, up_queue_depth=8.0,
                    down_queue_depth=0.0, window=2, cooldown_s=0.0,
                    interval_s=60.0, clock=lambda: 0.0)
    try:
        calm = dict(time=0.0, backends_total=2, backends_healthy=2,
                    mean_queue_depth=0.5, max_queue_depth=1,
                    total_inflight=1)
        assert sc.decide(FleetSignals(**calm)) is None
        # queues shallow but both SLO windows confirm a burn past the
        # alert threshold: up after the hysteresis window
        burning = dict(calm, slo_burn=sc.burn_alert)
        assert sc.decide(FleetSignals(**burning)) is None  # streak 1->2
        assert sc.decide(FleetSignals(**burning)) == "up"
    finally:
        sc.stop(drain=False)


# -- 2-process fleet round-trip ----------------------------------------------


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("slo_fleet") / "model")
    static.enable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    try:
        x = static.data(FEED, [None, IN_DIM], "float32")
        y = static.nn.fc(static.nn.fc(x, 8, name="slo_fc1"), 3,
                         name="slo_fc2")
        exe = static.Executor()
        exe.run_startup()
        static.save_inference_model(d, [FEED], [y], exe)
    finally:
        static.disable_static()
        static.reset_default_programs()
    return d


def _get(url):
    with urlopen(url, timeout=10) as r:
        ctype = r.headers.get("Content-Type", "")
        return r.status, ctype, r.read()


def test_fleetz_round_trip_two_real_processes(model_dir):
    """Two real backend PROCESSES: /metricz?format=snapshot on each,
    router-merged /fleetz p50/p99 equal to merging the same two
    snapshots by hand — the fleet view is exactly the pooled histogram,
    labeled series included."""
    from paddle_tpu.serving import Router
    from paddle_tpu.serving.scaler import launch_process

    backends = []
    router = None
    try:
        for _ in range(2):
            backends.append(launch_process(
                "paddle_tpu.serving.backend",
                ["--model-dir", model_dir, "--port", "0",
                 "--buckets", "1,2", "--batch-timeout-ms", "1"],
                startup_timeout_s=180.0))
        router = Router(backends=[b.url for b in backends],
                        probe_interval_s=0.2).start()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and router.healthy_count < 2:
            time.sleep(0.05)
        assert router.healthy_count == 2
        rng = np.random.RandomState(0)
        for i in range(8):
            body = json.dumps({
                "inputs": rng.randn((i % 2) + 1, IN_DIM).tolist(),
                "tenant": "t%d" % (i % 2)}).encode()
            req = Request(router.url + "/predict", data=body,
                          headers={"Content-Type": "application/json"})
            with urlopen(req, timeout=30) as r:
                assert r.status == 200
        # hand-merged golden from the backends' own snapshot endpoints
        snaps = []
        for b in backends:
            status, ctype, raw = _get(b.url +
                                      "/metricz?format=snapshot")
            assert status == 200 and "json" in ctype
            snaps.append(json.loads(raw)["metrics"])
        name = "serving/e2e_ms"
        golden = monitor.merge_histogram_snapshots(
            [s[name] for s in snaps], name=name)
        assert golden.count == 8
        # prometheus text mode carries the labeled series fleet-wide
        # (P2C may send every request to one backend: check them all)
        texts = []
        for b in backends:
            status, ctype, raw = _get(b.url + "/metricz")
            assert status == 200 and ctype.startswith("text/plain")
            texts.append(raw)
        assert any(b'serving_e2e_ms_count{' in t for t in texts)
        # wait for a probe pass to pick up the post-traffic snapshots
        deadline = time.monotonic() + 10
        fz = None
        while time.monotonic() < deadline:
            status, _, raw = _get(router.url + "/fleetz")
            assert status == 200
            fz = json.loads(raw)
            row = fz["fleet"].get("predict", {}).get(name)
            if row and row["count"] == golden.count:
                break
            time.sleep(0.1)
        row = fz["fleet"]["predict"][name]
        assert fz["backends_scraped"] == 2
        assert row["count"] == golden.count
        assert row["p50_ms"] == round(
            monitor.histogram_quantile(golden, 0.5), 3)
        assert row["p99_ms"] == round(
            monitor.histogram_quantile(golden, 0.99), 3)
        assert row["backends"] == 2
        # labeled series ride along and also match their pooled golden
        for sel, child in golden.series().items():
            assert row["series"][sel]["count"] == child.count
        # /sloz answers on the router too (empty doc without objectives)
        status, _, raw = _get(router.url + "/sloz")
        assert status == 200 and "slos" in json.loads(raw)
    finally:
        if router is not None:
            router.stop(drain=False)
        for b in backends:
            if b.proc is not None:
                b.proc.kill()
                b.proc.wait(10)


def test_sloz_pages_on_the_wedged_backend_process_only(model_dir):
    """Two backend PROCESSES given one latency objective through
    ``FLAGS_slo_objectives`` in their environment; one holds every
    request past the threshold (a long batch window: slow but
    answering, so /healthz stays green). Its own sampler thread drives
    both window burns past the alert on ``/sloz``; the healthy one
    never pages."""
    from concurrent.futures import ThreadPoolExecutor

    from paddle_tpu.serving.scaler import launch_process

    env = {"FLAGS_slo_objectives":
           "predict-fast|serving/e2e_ms{kind=predict}"
           "|threshold_ms=200|target=0.99|window_s=120",
           "FLAGS_slo_sample_interval_s": "0.2"}
    with ThreadPoolExecutor(2) as pool:
        boots = [pool.submit(
            launch_process, "paddle_tpu.serving.backend",
            ["--model-dir", model_dir, "--port", "0", "--buckets", "1,2,4",
             "--batch-timeout-ms", window_ms],
            env=env, startup_timeout_s=120.0) for window_ms in ("1", "500")]
    try:
        healthy, wedged = (b.result() for b in boots)
        body = json.dumps({"inputs": np.zeros((1, IN_DIM)).tolist()}).encode()
        for b in (healthy, wedged):
            for _ in range(4):
                req = Request(b.url + "/predict", data=body,
                              headers={"Content-Type": "application/json"})
                with urlopen(req, timeout=30) as r:
                    assert r.status == 200
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            wz = json.loads(_get(wedged.url + "/sloz")[2])["slos"][0]
            hz = json.loads(_get(healthy.url + "/sloz")[2])["slos"][0]
            if wz["alerting"] and hz["samples"] >= 2:
                break
            time.sleep(0.1)
        assert wz["name"] == "predict-fast" and wz["alerting"], wz
        assert wz["burn"]["fast"] >= wz["alert_burn"]
        assert wz["burn"]["slow"] >= wz["alert_burn"]
        assert hz["samples"] >= 2 and not hz["alerting"], hz
        assert (hz["burn"]["fast"] or 0.0) < hz["alert_burn"]
    finally:
        for b in boots:
            if b.exception() is None:
                b.result().proc.kill()
                b.result().proc.wait(10)

"""Distributed request tracing: context, propagation, tail sampling.

Pins the tracing contracts end to end: W3C-style ``traceparent``
round-trips and rejects garbage, spans nest under a contextvar-held
current span and cross thread hops through stored contexts, the
tail-sampled store keeps every errored/deadline/retried trace plus the
slowest-K per window while dropping the fast-path bulk, a router retry
keeps ONE trace_id across distinct per-attempt spans (including the
orphaned-attempt record on the read-timeout 504 path), a co-batched
dispatch span lands in every member trace exactly once with links naming
all members, and the executor/engine tag dispatch spans with their cache
disposition and cost-model FLOPs.
"""
import json
import threading
import time
from urllib.error import HTTPError
from urllib.request import Request, urlopen

import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu.flags import set_flags
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.monitor import tracing
from paddle_tpu.serving import (
    DynamicBatcher,
    InferenceServer,
    ReplicaPool,
    Router,
)
from paddle_tpu.serving.router import (
    BackendTimeoutError,
    BackendUnavailableError,
)

FEED = "x"
IN_DIM = 6
OUT_DIM = 3


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tracing") / "model")
    static.enable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    try:
        x = static.data(FEED, [None, IN_DIM], "float32")
        h = static.nn.fc(x, 8, name="tr_fc1")
        y = static.nn.fc(h, OUT_DIM, name="tr_fc2")
        exe = static.Executor()
        exe.run_startup()
        static.save_inference_model(d, [FEED], [y], exe)
    finally:
        static.disable_static()
        static.reset_default_programs()
    return d


def _rand(rows, seed=0):
    return np.random.RandomState(seed).randn(rows, IN_DIM).astype("float32")


# -- traceparent wire format --------------------------------------------------

def test_traceparent_round_trip():
    ctx = tracing.SpanContext(tracing.new_trace_id(),
                              tracing.new_span_id())
    parsed = tracing.parse_traceparent(tracing.format_traceparent(ctx))
    assert parsed.trace_id == ctx.trace_id
    assert parsed.span_id == ctx.span_id


def test_traceparent_rejects_garbage():
    tid, sid = "ab" * 16, "cd" * 8
    for bad in (
        None, "", 42, "not-a-header", f"00-{tid}-{sid}",  # 3 parts
        f"00-{tid[:10]}-{sid}-01",                        # short trace
        f"00-{tid}-{sid[:8]}-01",                         # short span
        f"00-{'0' * 32}-{sid}-01",                        # zero trace
        f"00-{tid}-{'0' * 16}-01",                        # zero span
        f"ff-{tid}-{sid}-01",                             # version ff
        f"FF-{tid}-{sid}-01",                             # uppercase ff
        f"zz-{tid}-{sid}-01", f"00-{'g' * 32}-{sid}-01",  # non-hex
        f"00-{tid}-{sid}-zz",                             # non-hex flags
        f"00-{tid}-{sid}-0",                              # short flags
    ):
        assert tracing.parse_traceparent(bad) is None, bad


def test_ids_are_wire_valid_and_unique():
    tids = {tracing.new_trace_id() for _ in range(200)}
    sids = {tracing.new_span_id() for _ in range(200)}
    assert len(tids) == 200 and len(sids) == 200
    assert all(len(t) == 32 and int(t, 16) for t in tids)
    assert all(len(s) == 16 and int(s, 16) for s in sids)


# -- span nesting and context -------------------------------------------------

def test_span_nesting_and_parentage():
    with tracing.start_trace("root", kind="test") as root:
        assert tracing.current_context().trace_id == root.trace_id
        with tracing.start_span("child") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
            with tracing.start_span("grandchild") as gc:
                assert gc.parent_id == child.span_id
        assert tracing.current_context().span_id == root.span_id
    assert tracing.current_context() is None
    p = tracing.store().get(root.trace_id)
    assert p is not None
    assert [s["name"] for s in p["spans"]] == \
        ["grandchild", "child", "root"]
    assert p["spans"][2]["root"] is True


def test_span_outside_trace_is_free_noop():
    before = tracing.store().stats()
    with tracing.start_span("ambient") as sp:
        assert not sp  # NULL span: gate optional work on truthiness
        tracing.annotate(ignored=1)
    assert tracing.store().stats() == before


def test_trace_disabled_flag():
    set_flags({"trace_enabled": False})
    try:
        with tracing.start_trace("off") as sp:
            assert not sp
            assert tracing.current_context() is None
        assert tracing.store().stats()["finished"] == 0
    finally:
        set_flags({"trace_enabled": True})


def test_annotate_and_note_status():
    with tracing.start_trace("root") as root:
        tracing.annotate(bucket=4, none_dropped=None)
        tracing.note_status(504)
    p = tracing.store().get(root.trace_id)
    s = p["spans"][0]
    assert s["attrs"]["bucket"] == 4
    assert "none_dropped" not in s["attrs"]
    assert s["attrs"]["status"] == 504
    assert "504" in s["error"]
    assert "error" in p["kept"]  # >=500 => errored => always retained


def test_remote_parent_preserves_trace_id():
    remote = tracing.SpanContext(tracing.new_trace_id(),
                                 tracing.new_span_id())
    with tracing.start_trace("local_root", parent=remote) as root:
        assert root.trace_id == remote.trace_id
        assert root.parent_id == remote.span_id


def test_record_interval_retroactive():
    with tracing.start_trace("root") as root:
        t0 = time.monotonic() - 0.05
        tracing.record_interval("queue_wait", root.context, t0,
                               rows=3)
    p = tracing.store().get(root.trace_id)
    qw = [s for s in p["spans"] if s["name"] == "queue_wait"][0]
    assert qw["parent_id"] == root.span_id
    assert 40 < qw["dur_ms"] < 500
    assert qw["attrs"]["rows"] == 3


def test_record_fanin_links_each_member_exactly_once():
    ctxs = []
    roots = []
    for i in range(3):
        with tracing.start_trace(f"req{i}") as r:
            tracing.flag_current_trace("test")  # force retention
            ctxs.append(r.context)
            roots.append(r)
    span = tracing.begin_span("dispatch", bucket=4)
    # duplicates and Nones must not double-link or crash
    n = tracing.record_fanin(span, ctxs + [ctxs[0], None])
    assert n == 3
    for i, root in enumerate(roots):
        p = tracing.store().get(root.trace_id)
        copies = [s for s in p["spans"] if s["name"] == "dispatch"]
        assert len(copies) == 1, (i, p["spans"])
        d = copies[0]
        assert d["parent_id"] == ctxs[i].span_id
        links = d["links"]
        assert len(links) == 3
        assert {(k["trace_id"], k["span_id"]) for k in links} == \
            {(c.trace_id, c.span_id) for c in ctxs}


# -- tail-sampled store -------------------------------------------------------

def test_tail_sampling_keeps_flags_and_slowest_drops_bulk():
    st = tracing.TraceStore()

    def finish(name, dur_ms, flag=None, error=None):
        sp = tracing.Span(name, tracing.new_trace_id(), root=True)
        sp.duration_ms = dur_ms
        if error:
            sp.set_error(error)
        st.add_span(sp)
        if flag:
            st.flag_trace(sp.trace_id, flag)
        st.finish(sp)
        return sp.trace_id

    set_flags({"trace_sample_slowest_k": 2})
    try:
        slow1 = finish("a", 100.0)
        slow2 = finish("b", 50.0)
        # the first K seed the window; later faster entrants are dropped
        fast = [finish(f"f{i}", 1.0) for i in range(10)]
        dead = finish("deadline", 0.5, flag="deadline")
        err = finish("err", 0.5, error="boom")
        retried = finish("retried", 0.5, flag="retry")
        slower = finish("c", 200.0)  # outcompetes slow2
    finally:
        set_flags({"trace_sample_slowest_k": 5})
    assert st.get(slow1) is not None
    assert st.get(slower) is not None
    assert st.get(slow2) is None  # evicted: slowness was its only claim
    assert all(st.get(t) is None for t in fast)
    assert st.get(dead)["kept"] == ["deadline"]
    assert st.get(err)["kept"] == ["error"]
    assert st.get(retried)["kept"] == ["retry"]
    s = st.stats()
    assert s["dropped"] == 10 and s["finished"] == 16


def test_tail_sampling_window_forgets_old_champions():
    st = tracing.TraceStore()
    set_flags({"trace_sample_window_s": 0.05,
               "trace_sample_slowest_k": 1})
    try:
        sp = tracing.Span("old", tracing.new_trace_id(), root=True)
        sp.duration_ms = 1000.0
        st.add_span(sp)
        st.finish(sp)
        time.sleep(0.06)  # new window: the old champion is forgotten
        sp2 = tracing.Span("new", tracing.new_trace_id(), root=True)
        sp2.duration_ms = 1.0  # would lose to 1000ms in the same window
        st.add_span(sp2)
        st.finish(sp2)
        assert st.get(sp2.trace_id) is not None
    finally:
        set_flags({"trace_sample_window_s": 30.0,
                   "trace_sample_slowest_k": 5})


def test_store_capacity_fifo_eviction():
    st = tracing.TraceStore()
    set_flags({"trace_store_capacity": 4})
    try:
        tids = []
        for i in range(8):
            sp = tracing.Span(f"t{i}", tracing.new_trace_id(), root=True)
            st.add_span(sp)
            st.flag_trace(sp.trace_id, "test")
            st.finish(sp.end())
            tids.append(sp.trace_id)
        assert all(st.get(t) is None for t in tids[:4])
        assert all(st.get(t) is not None for t in tids[4:])
        assert len(st.summaries()) == 4
    finally:
        set_flags({"trace_store_capacity": 256})


def test_second_finish_merges_instead_of_overwriting():
    """Router + backend co-hosted in one process: one distributed trace
    finishes once per local root — the second finish must merge the two
    subtrees, and the parentless (outermost) root names the trace."""
    st = tracing.TraceStore()
    tid = tracing.new_trace_id()
    backend_root = tracing.Span("serving::predict", tid,
                                parent_id=tracing.new_span_id(),
                                root=True)
    child = tracing.Span("serving::dispatch", tid,
                         parent_id=backend_root.span_id)
    st.add_span(child.end())
    st.add_span(backend_root.end())
    st.flag_trace(tid, "test")
    st.finish(backend_root)
    router_root = tracing.Span("serving::router", tid, root=True)
    router_root.duration_ms = 12.0
    st.add_span(router_root)
    st.finish(router_root)
    p = st.get(tid)
    names = sorted(s["name"] for s in p["spans"])
    assert names == ["serving::dispatch", "serving::predict",
                     "serving::router"]
    assert len({s["span_id"] for s in p["spans"]}) == 3  # deduped
    assert p["root"] == "serving::router"
    assert p["duration_ms"] == 12.0


def test_errored_outer_root_merge_promotes_to_always_kept():
    """Co-hosted: the inner root is retained on slowness alone, then the
    OUTER root finishes errored into the merge path — the trace must
    gain the 'error' reason, or the slowest-K competition can evict the
    exact trace the incident needs (kept==['slow'] is evictable)."""
    st = tracing.TraceStore()
    set_flags({"trace_sample_slowest_k": 1})
    try:
        tid = tracing.new_trace_id()
        inner = tracing.Span("serving::predict", tid,
                             parent_id=tracing.new_span_id(), root=True)
        inner.duration_ms = 10.0
        st.add_span(inner)
        p = st.finish(inner)
        assert p is not None and p["kept"] == ["slow"]
        outer = tracing.Span("serving::router", tid, root=True)
        outer.duration_ms = 11.0
        outer.set_error("backend died mid-stream")
        st.add_span(outer)
        st.finish(outer)
        assert "error" in st.get(tid)["kept"]
        # a faster-but-slower-window entrant must NOT evict it now
        bulk = tracing.Span("bulk", tracing.new_trace_id(), root=True)
        bulk.duration_ms = 50.0
        st.add_span(bulk)
        st.finish(bulk)
        assert st.get(tid) is not None, (
            "errored trace evicted by the slowest-K race")
    finally:
        set_flags({"trace_sample_slowest_k": 5})


def test_dropped_inner_root_subtree_survives_for_outer_root():
    """Co-hosted router+backend: the inner (backend) root may lose the
    slowest-K race while the outer (router) root later wins it — the
    inner subtree must still be in the retained payload."""
    st = tracing.TraceStore()
    set_flags({"trace_sample_slowest_k": 1})
    try:
        # seed the window so the inner root LOSES the race
        champ = tracing.Span("champ", tracing.new_trace_id(), root=True)
        champ.duration_ms = 100.0
        st.add_span(champ)
        st.finish(champ)
        tid = tracing.new_trace_id()
        inner = tracing.Span("serving::predict", tid,
                             parent_id=tracing.new_span_id(), root=True)
        inner.duration_ms = 1.0
        stage = tracing.Span("serving::dispatch", tid,
                             parent_id=inner.span_id)
        st.add_span(stage.end())
        st.add_span(inner)
        assert st.finish(inner) is None  # dropped: lost the race
        outer = tracing.Span("serving::router", tid, root=True)
        outer.duration_ms = 500.0  # outcompetes the champion
        st.add_span(outer)
        p = st.finish(outer)
        assert p is not None
        names = {s["name"] for s in p["spans"]}
        assert {"serving::predict", "serving::dispatch",
                "serving::router"} <= names, names
        assert p["root"] == "serving::router"
    finally:
        set_flags({"trace_sample_slowest_k": 5})


def test_dropped_then_retained_counts_one_request():
    """Co-hosted drop-then-retain: the inner root's drop decision and
    the outer root's retention are ONE request — stats must not count
    it as both a finished-dropped and a finished-retained trace."""
    st = tracing.TraceStore()
    set_flags({"trace_sample_slowest_k": 1})
    try:
        champ = tracing.Span("champ", tracing.new_trace_id(), root=True)
        champ.duration_ms = 100.0
        st.add_span(champ)
        st.finish(champ)
        tid = tracing.new_trace_id()
        inner = tracing.Span("serving::predict", tid,
                             parent_id=tracing.new_span_id(), root=True)
        inner.duration_ms = 1.0
        st.add_span(inner)
        assert st.finish(inner) is None  # dropped, spans put back
        outer = tracing.Span("serving::router", tid, root=True)
        outer.duration_ms = 500.0  # outcompetes the champion
        st.add_span(outer)
        assert st.finish(outer) is not None
        stats = st.stats()
        assert stats["finished"] == 2, stats  # champ + this request
        assert stats["retained"] == 2, stats
        assert stats["dropped"] == 0, stats
    finally:
        set_flags({"trace_sample_slowest_k": 5})


def test_active_gc_evicts_lingerers_before_live_traces():
    """A long-lived in-flight trace's early spans must survive GC
    pressure from put-back lingerers (dropped inner roots waiting for
    an outer root that never comes)."""
    st = tracing.TraceStore()
    set_flags({"trace_store_capacity": 16})  # active limit = 64
    try:
        live_tid = tracing.new_trace_id()
        early = tracing.Span("serving::queue_wait", live_tid,
                             parent_id=tracing.new_span_id())
        st.add_span(early.end())
        # flood: fast inner roots (remote parent) that lose retention
        # and are put back as lingerers, far past the active-table limit
        for _ in range(300):
            tid = tracing.new_trace_id()
            r = tracing.Span("serving::predict", tid,
                             parent_id=tracing.new_span_id(), root=True)
            r.duration_ms = 0.01
            st.add_span(r)
            st.finish(r)
        assert st.active_count() <= 64 + 1
        root = tracing.Span("serving::generate", live_tid, root=True)
        root.duration_ms = 10_000.0  # a p99 outlier: retained
        st.add_span(root)
        p = st.finish(root)
        assert p is not None
        names = {s["name"] for s in p["spans"]}
        assert "serving::queue_wait" in names, names
    finally:
        set_flags({"trace_store_capacity": 256})


def test_flag_after_retention_merges_reasons():
    st = tracing.TraceStore()
    sp = tracing.Span("r", tracing.new_trace_id(), root=True)
    sp.set_error("x")
    st.add_span(sp.end())
    st.finish(sp)
    st.flag_trace(sp.trace_id, "timeout")
    kept = st.get(sp.trace_id)["kept"]
    assert {"error", "timeout"} <= set(kept)


# -- serving integration ------------------------------------------------------

def _predict_traced(batcher, rows, seed=0, flag=None):
    with tracing.start_trace("serving::predict") as root:
        if flag:
            tracing.flag_current_trace(flag)
        batcher.predict({FEED: _rand(rows, seed)}, timeout=30)
    return root.trace_id


def test_batcher_spans_and_executor_attrs(model_dir):
    pred = create_predictor(Config(model_dir))
    batcher = DynamicBatcher([FEED], buckets=(1, 2, 4),
                             batch_timeout_ms=1.0)
    pool = ReplicaPool(pred, batcher, replicas=1)
    pool.warmup()
    pool.start()
    try:
        tid = _predict_traced(batcher, rows=3, flag="test")
    finally:
        pool.stop(drain=False)
    p = tracing.store().get(tid)
    names = {s["name"] for s in p["spans"]}
    assert {"serving::predict", "serving::queue_wait",
            "serving::assemble", "serving::dispatch"} <= names
    asm = [s for s in p["spans"] if s["name"] == "serving::assemble"][0]
    assert asm["attrs"]["bucket"] == 4
    assert asm["attrs"]["rows"] == 3
    assert asm["attrs"]["padded_rows"] == 1  # the padding-waste story
    disp = [s for s in p["spans"] if s["name"] == "serving::dispatch"][0]
    # the executor tagged the dispatch span through annotate(): cache
    # disposition + cost-model FLOPs, no handle threading
    assert disp["attrs"]["plan_cache"] in ("hit", "miss")
    assert disp["attrs"]["jit_cache"] in ("hit", "miss")
    assert disp["attrs"]["flops"] > 0
    assert disp["links"] == [{"trace_id": tid,
                              "span_id": p["spans"][-1]["span_id"]}] \
        or any(k["trace_id"] == tid for k in disp["links"])


def test_cobatched_dispatch_links_all_members_exactly_once(model_dir):
    """One dispatch serves N co-batched requests: its span must land in
    every member trace exactly once, carrying links that name all
    members exactly once."""
    pred = create_predictor(Config(model_dir))
    batcher = DynamicBatcher([FEED], buckets=(1, 2, 4),
                             batch_timeout_ms=200.0)
    pool = ReplicaPool(pred, batcher, replicas=1)
    pool.warmup()
    batcher.pause()  # queue the members so ONE batch picks them all
    pool.start()
    tids, threads = [], []
    lock = threading.Lock()

    def client(seed):
        tid = _predict_traced(batcher, rows=1, seed=seed, flag="test")
        with lock:
            tids.append(tid)

    try:
        for i in range(3):
            t = threading.Thread(target=client, args=(i,))
            t.start()
            threads.append(t)
        deadline = time.monotonic() + 5
        while batcher.queue_depth() < 3:
            assert time.monotonic() < deadline, "requests never queued"
            time.sleep(0.005)
        batcher.resume()
        for t in threads:
            t.join(timeout=30)
    finally:
        batcher.resume()
        pool.stop(drain=False)
    assert len(tids) == 3
    link_sets = []
    for tid in tids:
        p = tracing.store().get(tid)
        copies = [s for s in p["spans"]
                  if s["name"] == "serving::dispatch"]
        assert len(copies) == 1, (tid, [s["name"] for s in p["spans"]])
        d = copies[0]
        assert d["attrs"]["requests"] == 3
        assert d["trace_id"] == tid
        links = {(k["trace_id"], k["span_id"]) for k in d["links"]}
        assert len(d["links"]) == len(links) == 3
        assert {k[0] for k in links} == set(tids)
        link_sets.append(links)
    assert link_sets[0] == link_sets[1] == link_sets[2]


def test_deadline_expiry_flags_trace_with_errored_queue_wait(model_dir):
    pred = create_predictor(Config(model_dir))
    batcher = DynamicBatcher([FEED], buckets=(1, 2),
                             batch_timeout_ms=1.0)
    pool = ReplicaPool(pred, batcher, replicas=1)
    pool.warmup()
    batcher.pause()  # nothing picks: the deadline must expire in queue
    pool.start()
    try:
        with tracing.start_trace("serving::predict") as root:
            req = batcher.submit({FEED: _rand(1)}, deadline_ms=5)
        time.sleep(0.05)
        batcher.resume()
        from paddle_tpu.serving import DeadlineExceededError

        with pytest.raises(DeadlineExceededError):
            req.wait(10)
    finally:
        batcher.resume()
        pool.stop(drain=False)
    p = tracing.store().get(root.trace_id)
    assert p is not None and "deadline" in p["kept"]
    qw = [s for s in p["spans"] if s["name"] == "serving::queue_wait"][0]
    assert "deadline" in qw["error"]


# -- HTTP frontend ------------------------------------------------------------

@pytest.fixture()
def server(model_dir):
    srv = InferenceServer(create_predictor(Config(model_dir)),
                          buckets=(1, 2, 4)).start()
    yield srv
    srv.stop(drain=False)


def _http_json(url, payload=None, headers=None):
    data = json.dumps(payload).encode() if payload is not None else None
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    try:
        r = urlopen(Request(url, data=data, headers=hdrs), timeout=15)
        return r.status, json.loads(r.read())
    except HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def test_http_traceparent_extraction_and_tracez(server):
    remote = tracing.SpanContext(tracing.new_trace_id(),
                                 tracing.new_span_id())
    header = {tracing.TRACEPARENT_HEADER:
              tracing.format_traceparent(remote)}
    status, _ = _http_json(server.url + "/predict",
                           {"inputs": _rand(2).tolist()}, header)
    assert status == 200
    deadline = time.monotonic() + 5
    p = None
    while p is None and time.monotonic() < deadline:
        p = tracing.store().get(remote.trace_id)
        time.sleep(0.01)
    assert p is not None, "client trace_id must be preserved + retained"
    root = [s for s in p["spans"] if s["name"] == "serving::predict"][0]
    assert root["parent_id"] == remote.span_id
    assert root["attrs"]["rows"] == 2
    # /tracez list + fetch + chrome view + 404
    status, listing = _http_json(server.url + "/tracez")
    assert status == 200
    assert any(r["trace_id"] == remote.trace_id
               for r in listing["retained"])
    status, one = _http_json(
        server.url + f"/tracez?id={remote.trace_id}")
    assert status == 200 and one["trace_id"] == remote.trace_id
    status, chrome = _http_json(
        server.url + f"/tracez?id={remote.trace_id}&format=chrome")
    assert status == 200
    xs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} >= {"serving::predict",
                                       "serving::dispatch"}
    status, missing = _http_json(server.url + "/tracez?id=" + "0" * 32)
    assert status == 404 and "error" in missing
    # a garbage traceparent must not break the request (fresh trace)
    status, _ = _http_json(server.url + "/predict",
                           {"inputs": _rand(1).tolist()},
                           {tracing.TRACEPARENT_HEADER: "garbage"})
    assert status == 200


def test_statz_slowest_table(server):
    for i in range(3):
        status, _ = _http_json(server.url + "/predict",
                               {"inputs": _rand(i + 1, seed=i).tolist()})
        assert status == 200
    deadline = time.monotonic() + 5
    rows = []
    while not rows and time.monotonic() < deadline:
        _, sz = _http_json(server.url + "/statz")
        rows = sz.get("slowest") or []
        time.sleep(0.01)
    assert rows, "statz slowest must surface retained serving traces"
    top = rows[0]
    assert top["trace_id"] and top["duration_ms"] > 0
    assert top["root"].startswith("serving::")
    assert "queue_wait" in top["stages"] or "dispatch" in top["stages"]
    assert rows == sorted(rows, key=lambda r: -r["duration_ms"])


# -- router -------------------------------------------------------------------

class _StubHTTP:
    """Minimal scriptable backend for router-policy tracing tests."""

    def __init__(self, status=200, delay_s=0.0):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        stub = self
        self.status = status
        self.delay_s = delay_s
        self.traceparents = []

        class _H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_GET(self):
                body = json.dumps({
                    "schema": 1, "kind": "predict", "ready": True,
                    "draining": False, "queue_depth": 0,
                    "queue_capacity": 8, "load": 0.0,
                    "mean_fill": None, "slot_occupancy": None,
                    "compiles": {}, "histograms": {}}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                if n:
                    self.rfile.read(n)
                stub.traceparents.append(
                    self.headers.get(tracing.TRACEPARENT_HEADER))
                if stub.delay_s:
                    time.sleep(stub.delay_s)
                body = b'{"ok": true}'
                self.send_response(stub.status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

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


def test_router_retry_preserves_trace_id_across_attempts():
    """The satellite contract: a retried request keeps ONE trace_id with
    DISTINCT per-attempt child spans — the dead backend's attempt is
    errored, the survivor's carries the 200."""
    dead, live = _StubHTTP(), _StubHTTP()
    router = Router(backends=[dead.url, live.url],
                    probe_interval_s=30).start()
    try:
        dead_url = dead.url
        # steer the p2c pick onto the dying backend (ties break on URL,
        # which is port-order luck otherwise)
        states = {s.url: s for s in router.backend_states()}
        states[live.url].queue_depth = 3
        dead.stop()  # in rotation, but the port is now closed
        with tracing.start_trace("serving::router") as root:
            b, conn, resp = router.dispatch("predict", "/predict", b"{}")
            resp.read()
            router.finish(b, time.monotonic(), resp.status,
                          conn=conn, resp=resp)
            assert resp.status == 200
            states = {s.url: s for s in router.backend_states()}
            assert not states[dead_url].in_rotation
    finally:
        router.stop(drain=False)
        live.stop()
    p = tracing.store().get(root.trace_id)
    assert p is not None and "retry" in p["kept"]
    attempts = [s for s in p["spans"] if s["name"] == "serving::attempt"]
    assert len(attempts) >= 2
    assert {s["trace_id"] for s in attempts} == {root.trace_id}
    assert len({s["span_id"] for s in attempts}) == len(attempts)
    failed = [s for s in attempts if s.get("error")]
    ok = [s for s in attempts if s["attrs"].get("status") == 200]
    assert failed and failed[0]["attrs"]["backend"] == dead_url
    assert ok and ok[0]["attrs"]["backend"] == live.url
    assert failed[0]["parent_id"] == root.span_id
    assert ok[0]["parent_id"] == root.span_id
    # the winning attempt's traceparent reached the live backend
    assert live.traceparents and live.traceparents[-1]
    carried = tracing.parse_traceparent(live.traceparents[-1])
    assert carried.trace_id == root.trace_id
    assert carried.span_id == ok[0]["span_id"]


def test_one_trace_id_spans_router_and_a_backend_process(model_dir):
    """A real backend PROCESS behind the in-process router: the backend's
    ``/tracez`` holds the router's trace_id, its ``serving::predict``
    root hangs under the router's ``serving::attempt`` span, and the
    dispatch span carries the cache disposition, the cost-model FLOPs
    and a link to the member trace."""
    from paddle_tpu.serving.scaler import launch_process

    backend = launch_process(
        "paddle_tpu.serving.backend",
        ["--model-dir", model_dir, "--port", "0", "--buckets", "1,2",
         "--batch-timeout-ms", "1"], startup_timeout_s=120.0)
    router = Router(backends=[backend.url], probe_interval_s=30.0).start()
    try:
        # the first finished trace of a sampling window is always kept,
        # on both sides of the hop
        status, _ = _http_json(router.url + "/predict",
                               {"inputs": _rand(2).tolist()})
        assert status == 200
        # a root finishes after its reply is on the wire: wait for it
        deadline = time.monotonic() + 5
        mine = []
        while not mine and time.monotonic() < deadline:
            mine = [t for t in tracing.store().summaries()
                    if t["root"] == "serving::router"]
            time.sleep(0.01)
        assert mine
        tid = mine[0]["trace_id"]
        spans = tracing.store().get(tid)["spans"]
        root = [s for s in spans if s["name"] == "serving::router"][0]
        attempts = [s for s in spans if s["name"] == "serving::attempt"]
        assert attempts and attempts[0]["parent_id"] == root["span_id"]
        assert attempts[0]["attrs"]["status"] == 200
        status = None
        while status != 200 and time.monotonic() < deadline:
            status, theirs = _http_json(backend.url + f"/tracez?id={tid}")
            time.sleep(0.01)
        assert status == 200 and theirs["trace_id"] == tid
        by_name = {s["name"]: s for s in theirs["spans"]}
        assert {"serving::predict", "serving::queue_wait",
                "serving::assemble", "serving::dispatch"} <= set(by_name)
        assert by_name["serving::predict"]["parent_id"] \
            == attempts[0]["span_id"]
        disp = by_name["serving::dispatch"]
        assert disp["attrs"]["plan_cache"] in ("hit", "miss")
        assert disp["attrs"]["jit_cache"] in ("hit", "miss")
        assert disp["attrs"]["flops"] > 0
        assert any(l["trace_id"] == tid for l in disp["links"])
    finally:
        router.stop(drain=False)
        backend.proc.kill()
        backend.proc.wait(10)


def test_router_timeout_records_orphaned_attempt_span():
    """The satellite fix: a read-timeout 504 must leave a per-attempt
    record naming the backend that swallowed the request."""
    slow = _StubHTTP(delay_s=2.0)
    router = Router(backends=[slow.url], probe_interval_s=30,
                    request_timeout_s=0.2).start()
    try:
        with tracing.start_trace("serving::router") as root:
            with pytest.raises(BackendTimeoutError):
                router.dispatch("predict", "/predict", b"{}")
    finally:
        router.stop(drain=False)
        slow.stop()
    p = tracing.store().get(root.trace_id)
    assert p is not None
    assert "timeout" in p["kept"]
    att = [s for s in p["spans"] if s["name"] == "serving::attempt"]
    assert len(att) == 1, "the orphaned attempt must be recorded"
    assert att[0]["attrs"]["backend"] == slow.url
    assert "timeout" in att[0]["error"]


# -- training + export --------------------------------------------------------

def test_training_monitor_step_trace_cites_flight_events():
    from paddle_tpu import monitor
    from paddle_tpu.monitor import flight_recorder as fr

    mon = monitor.TrainingMonitor("trace_test", interval=0)
    with mon.step(examples=4):
        ctx = tracing.current_context()
        assert ctx is not None
        tracing.flag_current_trace("test")
        fr.record_event("test_step_event", detail=1)
    ev = [e for e in fr.get_recorder().events()
          if e["kind"] == "test_step_event"][0]
    assert ev["trace_id"] == ctx.trace_id
    p = tracing.store().get(ctx.trace_id)
    assert p["spans"][-1]["name"] == "train::trace_test::step"
    assert p["spans"][-1]["attrs"]["step"] == 1
    mon.close()


def test_training_monitor_aborted_step_trace_is_errored():
    from paddle_tpu import monitor

    mon = monitor.TrainingMonitor("trace_abort", interval=0)
    ctx = [None]
    with pytest.raises(RuntimeError):
        with mon.step():
            ctx[0] = tracing.current_context()
            raise RuntimeError("boom")
    p = tracing.store().get(ctx[0].trace_id)
    assert p is not None and "error" in p["kept"]
    assert p["spans"][-1]["error"] == "step aborted"
    mon.close()


def test_export_merged_chrome_trace_embeds_retained(tmp_path):
    from paddle_tpu.monitor.export import export_merged_chrome_trace

    with tracing.start_trace("serving::export_probe") as root:
        tracing.flag_current_trace("test")
        with tracing.start_span("serving::dispatch", flops=9.0):
            pass
    path = str(tmp_path / "merged.json")
    export_merged_chrome_trace(path)
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    mine = [e for e in events
            if e.get("args", {}).get("trace_id") == root.trace_id]
    assert {e["name"] for e in mine} == {"serving::export_probe",
                                         "serving::dispatch"}
    # and trace_summary --trace-id narrows the merged file to the trace
    import sys as _sys
    import os as _os

    _sys.path.insert(0, _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        "tools"))
    import trace_summary

    kept = trace_summary.filter_trace_id(events, root.trace_id[:10])
    assert len(kept) == 2
    assert trace_summary.filter_trace_id(events, "f" * 32) == []


def test_debug_server_tracez_endpoint():
    from paddle_tpu.monitor.debug_server import DebugServer

    with tracing.start_trace("serving::dbg_probe") as root:
        tracing.flag_current_trace("test")
    srv = DebugServer(port=0).start()
    try:
        status, listing = _http_json(srv.url + "/tracez")
        assert status == 200
        assert any(r["trace_id"] == root.trace_id
                   for r in listing["retained"])
        status, one = _http_json(srv.url + f"/tracez?id={root.trace_id}")
        assert status == 200 and one["trace_id"] == root.trace_id
        status, _ = _http_json(srv.url + "/tracez?id=" + "1" * 32)
        assert status == 404
    finally:
        srv.stop()

"""Shared compiled-callable runtime (paddle_tpu/runtime/compiled.py).

The ONE policy every dispatch site shares: cache hit/miss/LRU-eviction
semantics (bounded by FLAGS_compiled_cache_capacity — the single knob),
the double-checked one-time AOT compile (a concurrent cold-signature
race pays exactly one XLA compile), CostRecord capture keyed by the
store's cache_key (the identity /tracez, the flight recorder, and the
/costz ledger all cite), and the donation-safe demote-to-jit fallback.
Plus parity: Executor and TrainStepFn ride the same store class, so the
same-key-same-executable semantics hold at both sites.
"""
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.static as static
from paddle_tpu import ops, profiler
from paddle_tpu.flags import flag, set_flags
from paddle_tpu.monitor import cost_model, flight_recorder as fr, tracing
from paddle_tpu.runtime.compiled import CompiledStore, any_deleted


@pytest.fixture(autouse=True)
def _fresh():
    profiler.reset_counters()
    yield
    static.disable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    profiler.reset_counters()


def _make_store(**kw):
    kw.setdefault("cost_label", "rt_test")
    return CompiledStore("rt_test", **kw)


def _jitted(k=1.0):
    return jax.jit(lambda x: x + k)


# -- cache semantics ---------------------------------------------------------


def test_hit_miss_counters_and_lru_refresh():
    store = _make_store(hit_counter="rt_test::hit",
                        miss_counter="rt_test::miss")
    e1, d1 = store.get_or_build("a", lambda: (_jitted(), None))
    e2, d2 = store.get_or_build("a", lambda: (_jitted(), None))
    assert (d1, d2) == ("miss", "hit")
    assert e1 is e2  # same entry object: same executable semantics
    c = profiler.counters()
    assert c["rt_test::miss"] == 1 and c["rt_test::hit"] == 1


def test_eviction_bounded_by_flag_and_counted():
    """ONE knob (FLAGS_compiled_cache_capacity) bounds every store, and
    an eviction is counted — silent recompile churn must be visible."""
    store = _make_store()
    assert store.capacity == flag("compiled_cache_capacity")
    set_flags({"compiled_cache_capacity": 2})
    try:
        for i in range(5):
            store.get_or_build(i, lambda: (_jitted(), None))
        assert len(store) <= 2
        assert profiler.counters()["rt_test::cache_evict"] == 3
        # the evicted signature is a MISS again (recompile on return)
        _, disposition = store.get_or_build(0, lambda: (_jitted(), None))
        assert disposition == "miss"
    finally:
        set_flags({"compiled_cache_capacity": 128})


def test_explicit_capacity_override_wins():
    store = _make_store(capacity=1)
    store.get_or_build("a", lambda: (_jitted(), None))
    store.get_or_build("b", lambda: (_jitted(), None))
    assert len(store) == 1


def test_entry_meta_round_trips():
    store = _make_store()
    entry, _ = store.get_or_build(
        "sig", lambda: (_jitted(), ("donate", "hold")))
    assert entry.meta == ("donate", "hold")
    assert entry.cache_key.startswith("rt_test#")


# -- AOT compile + cost capture ----------------------------------------------


def test_dispatch_aot_captures_cost_record_under_cache_key():
    """The CostRecord ledger, the flight recorder, and the trace span all
    cite the SAME cache_key identity (satellite: one identity)."""
    store = _make_store()
    entry, _ = store.get_or_build("sig", lambda: (_jitted(), None))
    x = jnp.ones((8, 8), jnp.float32)
    with tracing.start_trace("rt::dispatch") as scope:
        tracing.flag_current_trace("test")
        out = store.dispatch(entry, x)
    np.testing.assert_allclose(np.asarray(out), np.ones((8, 8)) + 1)
    assert entry.attempted
    rec = cost_model.latest_record("rt_test")
    assert rec is not None
    assert rec.key == entry.cache_key
    assert rec.meta["cache_key"] == entry.cache_key
    assert rec.runs == 1
    compiles = [e for e in fr.get_recorder().events()
                if e["kind"] == "runtime_compile"
                and e.get("label") == "rt_test"]
    assert compiles and compiles[-1]["cache_key"] == entry.cache_key
    payload = tracing.store().get(scope.trace_id)
    root = [s for s in payload["spans"] if s["name"] == "rt::dispatch"][0]
    assert root["attrs"]["cache_key"] == entry.cache_key


def test_concurrent_cold_signature_pays_one_compile():
    """N threads racing one cold signature: ONE build, ONE lower+compile
    (the double-checked per-entry lock), and every thread's result is
    correct."""
    store = _make_store()
    real = jax.jit(lambda x: x * 2)
    lowers = []
    builds = []

    class CountingJit:
        def lower(self, *args):
            lowers.append(1)
            return real.lower(*args)

        def __call__(self, *args):
            return real(*args)

    def build():
        builds.append(1)
        return CountingJit(), None

    barrier = threading.Barrier(8)
    results = [None] * 8

    def worker(i):
        barrier.wait()
        entry, _ = store.get_or_build("cold", build)
        results[i] = store.dispatch(entry, jnp.asarray([float(i)]))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(builds) == 1
    assert len(lowers) == 1
    for i, r in enumerate(results):
        np.testing.assert_allclose(np.asarray(r), [2.0 * i])


# -- demote-to-jit -----------------------------------------------------------


class _RaisingAot:
    """What a jax ``Compiled`` does when called with avals it was not
    built for: raises TypeError."""

    def __init__(self, exc=TypeError):
        self.exc = exc

    def __call__(self, *args):
        raise self.exc("aval drift")


def test_demotion_falls_back_to_jit_and_drops_record():
    store = _make_store()
    entry, _ = store.get_or_build("sig", lambda: (_jitted(), None))
    x = jnp.ones((4,), jnp.float32)
    store.dispatch(entry, x)  # AOT-compile + capture
    assert entry.record is not None
    entry.aot = _RaisingAot()  # simulate aval/layout drift
    out = store.dispatch(entry, x)
    np.testing.assert_allclose(np.asarray(out), np.full(4, 2.0))
    # demoted: jit path forever after, stale record dropped (the MFU
    # ledger must not credit pre-drift numbers against jit's recompile)
    assert entry.aot is None and entry.record is None
    assert profiler.counters()["rt_test::aot_demote"] == 1
    demotes = [e for e in fr.get_recorder().events()
               if e["kind"] == "runtime_demote"]
    assert demotes and demotes[-1]["cache_key"] == entry.cache_key


def test_no_retry_after_donation_consumed():
    """A failed AOT dispatch whose donated buffers are already consumed
    must RAISE, never retry (the retry would read dead buffers)."""
    store = _make_store()
    entry, _ = store.get_or_build("sig", lambda: (_jitted(), None))
    entry.attempted = True
    entry.aot = _RaisingAot()

    class _Dead:
        def is_deleted(self):
            return True

    with pytest.raises(TypeError, match="aval drift"):
        store.dispatch(entry, jnp.ones((4,)), donated=[_Dead()])
    assert isinstance(entry.aot, _RaisingAot)  # NOT demoted: error surfaced


def test_device_error_is_not_drift_and_is_not_retried():
    """Only aval/sharding drift demotes. A run-time failure of the
    executable (RuntimeError: out of memory, a device fault) surfaces —
    retrying it through jax.jit would recompile the whole program just to
    fail the same way."""
    store = _make_store()
    entry, _ = store.get_or_build("sig", lambda: (_jitted(), None))
    entry.attempted = True
    entry.aot = _RaisingAot(RuntimeError)
    with pytest.raises(RuntimeError, match="aval drift"):
        store.dispatch(entry, jnp.ones((4,)))
    assert isinstance(entry.aot, _RaisingAot)
    assert "rt_test::aot_demote" not in profiler.counters()


def test_compile_error_surfaces_with_its_message():
    """A lowering/compile failure (on the chip: Mosaic refusing a kernel)
    must reach the caller with its text — never a silent jit fallback —
    and the entry stays cold so the next dispatch reports it again."""
    store = _make_store()

    class Refusing:
        def lower(self, *args):
            raise ValueError("Mosaic: block shape refused")

        def __call__(self, *args):
            raise AssertionError("the jit path must not run")

    entry, _ = store.get_or_build("sig", lambda: (Refusing(), None))
    for _ in range(2):
        with pytest.raises(ValueError, match="block shape refused"):
            store.dispatch(entry, jnp.ones((4,)))
    assert not entry.attempted and entry.aot is None


def test_donation_check_is_lazy_callable():
    """`donated` may be a zero-arg callable: evaluated only on failure
    (the happy path must not pay a pytree flatten per step)."""
    store = _make_store()
    entry, _ = store.get_or_build("sig", lambda: (_jitted(), None))
    calls = []

    def donated():
        calls.append(1)
        return []

    store.dispatch(entry, jnp.ones((4,)), donated=donated)
    assert calls == []  # success: never evaluated
    entry.aot = _RaisingAot()
    store.dispatch(entry, jnp.ones((4,)), donated=donated)
    assert calls == [1]  # failure path consulted it


def test_any_deleted_tolerates_foreign_objects():
    assert any_deleted([object(), 3, None]) is False


# -- executor / train-step parity --------------------------------------------


def _executor_program():
    static.enable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    x = static.data("x", [4, 8], "float32")
    w = static.nn.create_parameter([8, 1], "float32")
    loss = ops.mean(ops.matmul(x, w))
    exe = static.Executor()
    exe.run_startup()
    return exe, loss


def test_executor_rides_the_shared_store():
    exe, loss = _executor_program()
    X = np.zeros((4, 8), np.float32)
    exe.run(feed={"x": X}, fetch_list=[loss])
    exe.run(feed={"x": X}, fetch_list=[loss])
    c = profiler.counters()
    assert c["executor::jit_cache_miss"] == 1
    assert c["executor::jit_cache_hit"] == 1
    entries = list(exe._cache.values())
    assert len(entries) == 1
    assert entries[0].cache_key.startswith("executor#")
    # same identity in the cost ledger
    rec = cost_model.latest_record("executor")
    assert rec.key == entries[0].cache_key


def test_train_step_rides_the_shared_store_same_key_same_executable():
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as popt
    from paddle_tpu.framework import jit as fjit

    paddle.seed(0)
    net = nn.Linear(8, 4)
    opt = popt.SGD(learning_rate=0.1, parameters=net.parameters())
    step = fjit.train_step(net, opt,
                           lambda m, x, y: F.mse_loss(m(x), y).mean())
    rng = np.random.RandomState(0)
    X, Y = rng.randn(4, 8).astype("f4"), rng.randn(4, 4).astype("f4")
    step(X, Y)
    step(X, Y)  # same batch signature -> same entry, zero extra compiles
    c = profiler.counters()
    assert c["train_step::exec_cache_miss"] == 1
    assert c["train_step::exec_cache_hit"] == 1
    rec = cost_model.latest_record("train_step")
    entry = next(iter(step._exec.entries().values()))
    assert rec.key == entry.cache_key
    assert rec.runs == 2
    # a NEW batch signature is a miss (one more executable, same policy)
    step(rng.randn(2, 8).astype("f4"), rng.randn(2, 4).astype("f4"))
    assert profiler.counters()["train_step::exec_cache_miss"] == 2
    assert len(step._exec) == 2
    # both sites obey the ONE capacity knob
    assert step._exec.capacity == flag("compiled_cache_capacity")
    exe, _ = _executor_program()
    assert exe._cache_limit == flag("compiled_cache_capacity")


def test_executor_cache_view_mutation_invalidates_for_real():
    """The legacy ``exe._cache`` surface is a LIVE view: ``clear()`` /
    ``del`` must invalidate entries in the real store so the next run
    recompiles (the historical force-a-recompile workflow), not mutate
    a throwaway snapshot."""
    exe, loss = _executor_program()
    X = np.zeros((4, 8), np.float32)
    exe.run(feed={"x": X}, fetch_list=[loss])
    assert len(exe._cache) == 1
    exe._cache.clear()
    assert len(exe._cache) == 0
    profiler.reset_counters()
    exe.run(feed={"x": X}, fetch_list=[loss])
    assert profiler.counters()["executor::jit_cache_miss"] == 1
    # del / pop invalidate one signature the same way
    sig = next(iter(exe._cache))
    del exe._cache[sig]
    with pytest.raises(KeyError):
        exe._cache[sig]
    assert exe._cache.pop(sig, None) is None
    profiler.reset_counters()
    exe.run(feed={"x": X}, fetch_list=[loss])
    assert profiler.counters()["executor::jit_cache_miss"] == 1


def test_train_step_cache_keys_distinct_per_instance_no_id():
    """Cache keys derive from a deterministic per-instance counter, not
    ``id(self)`` — so the same logical program keys identically across
    runs, while two instances with IDENTICAL batch avals still get
    distinct keys (their CostRecords must not collide in the global
    ledger)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as popt
    from paddle_tpu.framework import jit as fjit

    def build():
        net = nn.Linear(8, 4)
        opt = popt.SGD(learning_rate=0.1, parameters=net.parameters())
        return fjit.train_step(
            net, opt, lambda m, x, y: F.mse_loss(m(x), y).mean())

    paddle.seed(0)
    s1, s2 = build(), build()
    assert isinstance(s1._instance, int) and s2._instance == s1._instance + 1
    rng = np.random.RandomState(0)
    X, Y = rng.randn(4, 8).astype("f4"), rng.randn(4, 4).astype("f4")
    s1(X, Y)
    s2(X, Y)  # same avals, different instance
    k1 = next(iter(s1._exec.entries().values())).cache_key
    k2 = next(iter(s2._exec.entries().values())).cache_key
    assert k1 != k2
    # both records live side by side in the ledger (no last-writer-wins)
    keys = {r.key for r in cost_model.cost_records().values()}
    assert {k1, k2} <= keys


def test_train_step_donation_after_demotion_is_safe():
    """Demotion retry with the step's donated state: the runtime retries
    ONLY when the state buffers survived — a consumed pytree raises."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as popt
    from paddle_tpu.framework import jit as fjit

    paddle.seed(0)
    net = nn.Linear(6, 2)
    opt = popt.SGD(learning_rate=0.1, parameters=net.parameters())
    step = fjit.train_step(net, opt,
                           lambda m, x, y: F.mse_loss(m(x), y).mean())
    rng = np.random.RandomState(0)
    X, Y = rng.randn(3, 6).astype("f4"), rng.randn(3, 2).astype("f4")
    l0 = float(np.asarray(step(X, Y)["loss"]))
    # wedge the AOT executable: the next dispatch must demote + retry
    # through jax.jit and KEEP TRAINING (state donation did not fire
    # before the failure, so the retry is legal)
    entry = next(iter(step._exec.entries().values()))
    entry.aot = _RaisingAot()
    entry.record = None
    l1 = float(np.asarray(step(X, Y)["loss"]))
    assert np.isfinite(l1) and l1 < l0 + 1.0
    assert entry.aot is None  # demoted for good
    for _ in range(3):  # donated jit steps keep the state pytree alive
        step(X, Y)
    assert np.isfinite(float(np.asarray(step(X, Y)["loss"])))

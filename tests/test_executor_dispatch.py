"""Zero-copy executor dispatch: cached RunPlans, buffer donation, lazy
fetches, and the persistent compile cache.

Covers the steady-state contract of static/executor.py: a cache-hit
``run()`` performs NO op traversal (the per-program RunPlan holds the
one-time analysis), written persistables are donated to the compiled step
(in-place updates, scope ownership transfer), ``return_numpy=True``
fetches materialize lazily, and both cache levels stay LRU-bounded.
"""
import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu import ops, profiler
from paddle_tpu.flags import flag, set_flags
from paddle_tpu.static import executor as executor_mod


@pytest.fixture(autouse=True)
def _fresh():
    static.reset_default_programs()
    static.global_scope().clear()
    profiler.reset_counters()
    yield
    static.disable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    profiler.reset_counters()


def _build_train_step(lr=0.05, seed=0):
    """Small regression train step; returns (exe, loss, X, Y)."""
    static.enable_static()
    x = static.data("x", [4, 8], "float32")
    y = static.data("y", [4, 1], "float32")
    w = static.nn.create_parameter([8, 1], "float32")
    pred = ops.matmul(x, w)
    loss = ops.mean(ops.square(ops.subtract(pred, y)))
    opt = static.optimizer.Adam(learning_rate=lr)
    opt.minimize(loss)
    exe = static.Executor()
    exe.run_startup()
    rng = np.random.RandomState(seed)
    return (exe, loss, rng.randn(4, 8).astype("float32"),
            rng.randn(4, 1).astype("float32"))


# -- run-plan cache ----------------------------------------------------------


def test_plan_cache_hit_counter_and_no_op_rewalk(monkeypatch):
    """After N identical runs the plan-cache hit counter is N-1, and the
    steady-state path never walks the program's ops again."""
    exe, loss, X, Y = _build_train_step()
    N = 6
    first = exe.run(feed={"x": X, "y": Y}, fetch_list=[loss])[0]

    walks = []
    real_walk = executor_mod._walk_ops

    def counting_walk(*a, **kw):
        walks.append(a)
        return real_walk(*a, **kw)

    monkeypatch.setattr(executor_mod, "_walk_ops", counting_walk)
    for _ in range(N - 1):
        last = exe.run(feed={"x": X, "y": Y}, fetch_list=[loss])[0]

    assert walks == []  # cache hits do zero op traversal
    c = profiler.counters()
    assert c["executor::plan_cache_hit"] == N - 1
    assert c["executor::plan_cache_miss"] == 1
    assert c["executor::jit_cache_hit"] == N - 1
    assert float(last) < float(first)  # the step itself still trains


def test_plan_cache_keyed_by_program_version():
    """Appending an op bumps the program version: the stale plan is not
    reused and the new op's effect is visible."""
    static.enable_static()
    x = static.data("x", [3], "float32")
    y = ops.add(x, ops.full([3], 1.0))
    exe = static.Executor()
    X = np.array([1.0, 2.0, 3.0], np.float32)
    np.testing.assert_allclose(
        exe.run(feed={"x": X}, fetch_list=[y])[0], [2.0, 3.0, 4.0])
    z = ops.multiply(y, ops.full([3], 10.0))
    np.testing.assert_allclose(
        exe.run(feed={"x": X}, fetch_list=[z])[0], [20.0, 30.0, 40.0])
    assert len(exe._plans) == 2  # one plan per program version


def test_plan_cache_lru_eviction():
    static.enable_static()
    exe = static.Executor()
    exe._plan_cache_limit = 2
    for i in range(5):
        static.reset_default_programs()
        x = static.data("x", [2], "float32")
        y = ops.add(x, ops.full([2], float(i)))
        exe.run(feed={"x": np.zeros(2, np.float32)}, fetch_list=[y])
    assert len(exe._plans) <= 2
    assert len(exe._cache) <= exe._cache_limit


# -- buffer donation ---------------------------------------------------------


def test_donation_updates_params_in_place():
    """Written persistables are donated: after a run the pre-step arrays
    are dead (XLA reused their buffers) and the scope owns fresh ones —
    and training stays numerically correct across donated steps."""
    assert flag("executor_buffer_donation") is True
    exe, loss, X, Y = _build_train_step()
    scope = static.global_scope()
    pname = next(n for n in scope.var_names() if n.startswith("param"))
    before = scope.get(pname)

    l0 = float(exe.run(feed={"x": X, "y": Y}, fetch_list=[loss])[0])
    assert before.is_deleted()  # buffer handed to XLA, not copied
    after = scope.get(pname)
    assert after is not before and not after.is_deleted()
    assert profiler.counters()["executor::donated_buffers"] > 0

    # donated scope state is never read after the call: repeated steps
    # keep training (stale-buffer reuse would raise or corrupt numerics)
    for _ in range(10):
        l1 = float(exe.run(feed={"x": X, "y": Y}, fetch_list=[loss])[0])
    assert l1 < l0


def test_donation_opt_out_flag():
    set_flags({"executor_buffer_donation": False})
    try:
        exe, loss, X, Y = _build_train_step()
        scope = static.global_scope()
        pname = next(n for n in scope.var_names() if n.startswith("param"))
        before = scope.get(pname)
        exe.run(feed={"x": X, "y": Y}, fetch_list=[loss])
        assert not before.is_deleted()  # pre-step array stays alive
        assert "executor::donated_buffers" not in profiler.counters()
    finally:
        set_flags({"executor_buffer_donation": True})


def test_donation_flag_toggle_respected_with_warm_cache():
    """Toggling executor_buffer_donation must not silently reuse a jit
    entry compiled with the other donation mode (the flag is part of the
    compile key)."""
    exe, loss, X, Y = _build_train_step()
    exe.run(feed={"x": X, "y": Y}, fetch_list=[loss])  # donating entry
    scope = static.global_scope()
    pname = next(n for n in scope.var_names() if n.startswith("param"))
    set_flags({"executor_buffer_donation": False})
    try:
        before = scope.get(pname)
        exe.run(feed={"x": X, "y": Y}, fetch_list=[loss])
        assert not before.is_deleted()  # non-donating entry was used
    finally:
        set_flags({"executor_buffer_donation": True})
    before = scope.get(pname)
    exe.run(feed={"x": X, "y": Y}, fetch_list=[loss])
    assert before.is_deleted()  # donating entry again


def test_check_nan_inf_writeback_precedes_raise():
    """When the NaN scan raises, the scope must hold the valid post-step
    arrays — never the dead donated inputs."""
    from paddle_tpu.errors import FatalError

    exe, loss, X, Y = _build_train_step()
    scope = static.global_scope()
    pname = next(n for n in scope.var_names() if n.startswith("param"))
    set_flags({"check_nan_inf": True})
    try:
        bad = np.full_like(X, np.nan)
        with pytest.raises(FatalError):
            exe.run(feed={"x": bad, "y": Y}, fetch_list=[loss])
        assert not scope.get(pname).is_deleted()
    finally:
        set_flags({"check_nan_inf": False})
    # the executor remains usable on the same (donated) entry: a dead
    # scope array here would raise 'Array has been deleted'
    out = exe.run(feed={"x": np.zeros_like(X), "y": Y}, fetch_list=[loss])
    assert out[0].shape == ()


def test_fetched_written_persistable_survives_next_run():
    """Fetching a donated persistable must return a value the NEXT run's
    donation cannot destroy or silently overwrite."""
    exe, loss, X, Y = _build_train_step()
    scope = static.global_scope()
    pname = next(n for n in scope.var_names() if n.startswith("param"))

    out = exe.run(feed={"x": X, "y": Y}, fetch_list=[pname])
    v1 = out[0]  # materialized host view
    snap = v1.copy()
    exe.run(feed={"x": X, "y": Y}, fetch_list=[pname])  # donates again
    np.testing.assert_array_equal(v1, snap)  # not overwritten in place

    out2 = exe.run(feed={"x": X, "y": Y}, fetch_list=[pname])
    exe.run(feed={"x": X, "y": Y}, fetch_list=[loss])
    assert np.isfinite(out2[0]).all()  # late materialization still valid


def test_lazy_fetch_list_c_level_paths_materialize():
    import jax

    exe, loss, X, Y = _build_train_step()
    res = exe.run(feed={"x": X, "y": Y}, fetch_list=[loss, loss])
    assert not isinstance(list.__getitem__(res, 0), np.ndarray)
    v = res.pop()
    assert isinstance(v, np.ndarray)
    combined = res + [np.zeros(1)]
    assert all(isinstance(a, np.ndarray) for a in combined)
    assert not any(isinstance(a, jax.Array) for a in res.copy())
    res2 = exe.run(feed={"x": X, "y": Y}, fetch_list=[loss, loss])
    # reversed() reads backing storage directly — must not leak handles
    assert all(isinstance(a, np.ndarray) for a in reversed(res2))


def test_read_only_persistables_not_donated():
    """A program that only READS a parameter must keep it alive."""
    static.enable_static()
    x = static.data("x", [4, 8], "float32")
    w = static.nn.create_parameter([8, 1], "float32")
    pred = ops.matmul(x, w)
    exe = static.Executor()
    exe.run_startup()
    scope = static.global_scope()
    pname = next(n for n in scope.var_names() if n.startswith("param"))
    before = scope.get(pname)
    exe.run(feed={"x": np.zeros((4, 8), np.float32)}, fetch_list=[pred])
    assert not before.is_deleted()
    assert scope.get(pname) is before


# -- lazy fetches ------------------------------------------------------------


def test_return_numpy_fetches_are_lazy():
    import jax

    exe, loss, X, Y = _build_train_step()
    res = exe.run(feed={"x": X, "y": Y}, fetch_list=[loss])
    assert isinstance(res, list)  # drop-in list surface
    raw = list.__getitem__(res, 0)
    assert isinstance(raw, jax.Array)  # no host sync yet
    val = res[0]
    assert isinstance(val, np.ndarray)  # materialized on access
    assert isinstance(list.__getitem__(res, 0), np.ndarray)  # cached
    # iteration and negative indexing materialize too
    assert all(isinstance(v, np.ndarray) for v in res)
    assert isinstance(res[-1], np.ndarray)


def test_return_numpy_false_returns_lazy_tensors():
    from paddle_tpu.framework.tensor import Tensor

    exe, loss, X, Y = _build_train_step()
    res = exe.run(feed={"x": X, "y": Y}, fetch_list=[loss],
                  return_numpy=False)
    assert isinstance(res[0], Tensor)
    assert np.asarray(res[0]).shape == ()  # __array__ is the sync point

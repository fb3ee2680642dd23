"""Test harness config.

Tests run on a virtual 8-device CPU mesh (SURVEY.md §4: the reference tests
multi-device via localhost subprocesses; JAX lets us do it in-process with
xla_force_host_platform_device_count). Must set env before jax imports.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests use the virtual CPU mesh
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "true")
# Persistent XLA compilation cache: the suite's wall time is dominated by
# compiles, so cached modules survive across runs (and across xdist
# workers and the SUBPROCESS worlds, which inherit the environment). The
# directory is the package's own choice (runtime/compile_cache.py);
# this only lowers the size of compile worth keeping.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")

from paddle_tpu.runtime import compile_cache  # noqa: E402

compile_cache.apply()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def child_env():
    """``child_env(**extra)``: this process's environment for a child
    that must import THIS checkout's ``paddle_tpu``, plus ``extra``."""
    def make(**extra):
        return dict(os.environ, PYTHONPATH=os.pathsep.join(
            [REPO, os.environ.get("PYTHONPATH", "")]), **extra)

    return make


@pytest.fixture(autouse=True)
def _seed_rng():
    import paddle_tpu

    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield


@pytest.fixture(autouse=True)
def _dygraph_mode_restored():
    """Static mode is a process-global switch: a test that enables it and
    returns (or fails) before ``disable_static()`` must not hand it to the
    next test, whose eager ops would then build a program instead."""
    yield
    from paddle_tpu import static

    static.disable_static()


@pytest.fixture(autouse=True)
def _reset_telemetry():
    """Telemetry state is process-global (profiler counters, monitor
    registry): zero it after every test so bump_counter/metric state
    cannot leak across test files and order-couple assertions."""
    yield
    from paddle_tpu import monitor, profiler, serving
    from paddle_tpu.distributed import chaos, checkpoint

    # serving first: live servers/pools/batchers own daemon threads that
    # keep bumping metrics — shut the subsystem down BEFORE zeroing, so
    # no thread leaks (or stray counter bump) crosses into the next test
    serving.shutdown_all()
    # drain the checkpoint writer: an in-flight async save must not keep
    # writing (and bumping counters) into the next test's tmp dirs
    checkpoint.wait_pending(raise_errors=False)
    chaos.reset()
    profiler.reset_counters()
    monitor.reset_registry(unregister=True)
    monitor.cost_model.reset_cost_records()
    from paddle_tpu.analysis import memory as _memplan

    _memplan.reset_accuracy_records()
    monitor.tracing.reset_store()
    monitor.opprof.reset_profiles()
    monitor.cluster.stop_publisher()
    monitor.goodput.reset_ledger()
    monitor.flight_recorder.reset_recorder()
    monitor.flight_recorder.stop_watchdog()

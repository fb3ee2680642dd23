"""Kernel autotuner: schedule spaces, the tuner harness, the persistent
cache, and the runtime coupling.

The tuner itself is certified with a DETERMINISTIC fake timer — the
selection pipeline (candidate enumeration, pre-compile pruning,
best-of-N, cache write, resolve swap-in) runs with zero real compiles
and scripted timings, so every assertion is exact. One test tunes the
two real kernels under the wall clock and reads the file back in a
fresh process.
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu  # noqa: F401 (bootstrap flags/monitor)
from paddle_tpu import profiler, tuning
from paddle_tpu.flags import set_flags
from paddle_tpu.tuning.cache import TuningCache

# reach the kernel modules (package re-exports shadow the names)
from paddle_tpu.ops.pallas import layernorm_residual as _  # noqa: F401
from paddle_tpu.ops.pallas import conv_bn_relu as _  # noqa: F401

lnr = sys.modules["paddle_tpu.ops.pallas.layernorm_residual"]
ou = sys.modules["paddle_tpu.ops.pallas.optimizer_update"]
im = sys.modules["paddle_tpu.ops.pallas.int8_matmul"]
fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
cbr = sys.modules["paddle_tpu.ops.pallas.conv_bn_relu"]


@pytest.fixture(autouse=True)
def _clean_tuning():
    """Every test starts from an empty in-memory cache and mode=cached,
    and leaves no tuned entries behind for the rest of the suite."""
    tuning.reset_tuning_cache()
    set_flags({"kernel_autotune": "cached"})
    yield
    tuning.reset_tuning_cache()
    set_flags({"kernel_autotune": "cached"})


def _counter(name):
    return profiler.counters().get(name, 0)


# -- a synthetic space the fake-timer tests drive -----------------------------


def _register_fake_space(bench_calls, version=1):
    """A 2-axis space whose bench builder records every candidate it is
    asked to build — the pruning proof."""

    def bench(info):
        def builder(params):
            bench_calls.append(dict(params))
            return lambda: None  # the fake timer never runs real work

        return builder

    return tuning.register_schedule(tuning.ScheduleSpace(
        "fake_kernel",
        version=version,
        params={"block": (8, 16, 32), "unroll": (1, 2)},
        default=lambda info: {"block": 16, "unroll": 1},
        supported=lambda info, c: c["block"] <= info["n"],
        bench=bench,
    ))


# -- selection / pruning ------------------------------------------------------


def test_best_candidate_selection_with_fake_timer():
    calls = []
    _register_fake_space(calls)
    # scripted timings: block=8 slowest, block=32/unroll=2 fastest
    times = {(8, 1): 50.0, (8, 2): 40.0, (16, 1): 30.0, (16, 2): 25.0,
             (32, 1): 20.0, (32, 2): 10.0}
    seq = []

    def timer(run):
        run()
        key = (calls[-1]["block"], calls[-1]["unroll"])
        seq.append(key)
        return times[key] * 1e-6

    tuner = tuning.KernelTuner(measure_n=3, timer=timer)
    res = tuner.tune("fake_kernel", n=1000)
    assert res.params == {"block": 32, "unroll": 2}
    assert res.best_us == pytest.approx(10.0)
    assert res.default_us == pytest.approx(30.0)  # default point measured
    assert res.speedup == pytest.approx(3.0)
    assert res.measured == 6 and res.pruned == 0
    # the winner is immediately resolvable
    assert tuning.resolve("fake_kernel", n=1000) == {"block": 32,
                                                     "unroll": 2}
    assert _counter("autotune::cache_hit") >= 1


def test_invalid_candidates_pruned_before_compile():
    calls = []
    _register_fake_space(calls)
    before = _counter("autotune::pruned")
    tuner = tuning.KernelTuner(
        measure_n=1, timer=lambda run: (run(), 1e-6)[1])
    res = tuner.tune("fake_kernel", n=10)  # only block=8 admissible
    # the bench builder (the compile) ran ONLY for valid candidates
    assert all(c["block"] <= 10 for c in calls), calls
    assert res.pruned == 4  # block in (16, 32) x unroll in (1, 2)
    assert res.measured == 2
    assert _counter("autotune::pruned") == before + 4


def test_no_valid_candidate_raises_precondition():
    calls = []
    _register_fake_space(calls)
    tuner = tuning.KernelTuner(measure_n=1, timer=lambda run: 1e-6)
    from paddle_tpu.errors import PreconditionNotMetError

    with pytest.raises(PreconditionNotMetError, match="no valid candidate"):
        tuner.tune("fake_kernel", n=1)
    assert calls == []  # nothing compiled


# -- flag semantics -----------------------------------------------------------


def test_mode_off_returns_defaults_with_zero_tuner_work():
    calls = []
    _register_fake_space(calls)
    tuning.KernelTuner(measure_n=1, timer=lambda run: (run(), 1e-6)[1]) \
        .tune("fake_kernel", n=1000)
    set_flags({"kernel_autotune": "off"})
    before = profiler.counters()
    assert tuning.resolve("fake_kernel", n=1000) == {"block": 16,
                                                     "unroll": 1}
    after = profiler.counters()
    for k in ("autotune::cache_hit", "autotune::cache_miss",
              "autotune::enqueued"):
        assert after.get(k, 0) == before.get(k, 0), k


def test_mode_cached_never_searches(monkeypatch):
    _register_fake_space([])
    enq = []
    monkeypatch.setattr("paddle_tpu.tuning.tuner.enqueue_search",
                        lambda *a: enq.append(a))
    set_flags({"kernel_autotune": "cached"})
    assert tuning.resolve("fake_kernel", n=64) == {"block": 16, "unroll": 1}
    assert enq == []
    assert _counter("autotune::cache_miss") >= 1


def test_mode_search_enqueues_miss_and_dedupes(monkeypatch):
    _register_fake_space([])
    enq = []
    monkeypatch.setattr("paddle_tpu.tuning.tuner.enqueue_search",
                        lambda kernel, info: enq.append((kernel,
                                                         dict(info))))
    set_flags({"kernel_autotune": "search"})
    for _ in range(3):
        p = tuning.resolve("fake_kernel", n=64)
        assert p == {"block": 16, "unroll": 1}  # defaults until the swap
    assert len(enq) == 3  # resolve enqueues every miss; the real
    #                       enqueue_search dedupes by (kernel, bucket)


def test_background_enqueue_dedupes_and_drains():
    calls = []
    _register_fake_space(calls)
    from paddle_tpu.tuning import tuner as tuner_mod

    import time as _time

    def slow_timer(run):
        run()
        _time.sleep(0.05)  # keep the first search in flight while the
        #                    duplicate enqueues arrive (dedupe window)
        return 1e-6

    tuner_mod._default_tuner[0] = tuning.KernelTuner(
        measure_n=1, timer=slow_timer)
    before = _counter("autotune::search")
    try:
        for _ in range(5):
            tuning.enqueue_search("fake_kernel", {"n": 128})
        assert tuning.drain_background(timeout=10.0)
        entry = tuning.tuning_cache().lookup(
            tuning.schedule_space("fake_kernel"), {"n": 128})
        assert entry is not None
        # deduped: ONE search despite 5 enqueues of the same bucket
        assert _counter("autotune::search") == before + 1
    finally:
        tuner_mod._default_tuner[0] = None


# -- cache round-trip / rejection ---------------------------------------------


def test_cache_round_trip_across_instances(tmp_path):
    _register_fake_space([])
    space = tuning.schedule_space("fake_kernel")
    path = str(tmp_path / "kernel_tuning_cache.json")
    c1 = TuningCache(path)
    c1.put(space, {"n": 256}, {"block": 32, "unroll": 2},
           best_us=10.0, default_us=25.0)
    assert os.path.exists(path)
    # a FRESH instance (fresh process stand-in) reads the same winner
    c2 = TuningCache(path)
    entry = c2.lookup(space, {"n": 256})
    assert entry is not None
    assert entry["params"] == {"block": 32, "unroll": 2}
    assert entry["best_us"] == 10.0
    # and the file is valid versioned JSON
    with open(path) as f:
        raw = json.load(f)
    assert raw["schema"] == tuning.CACHE_SCHEMA_VERSION


def test_truncated_cache_degrades_to_defaults(tmp_path):
    _register_fake_space([])
    space = tuning.schedule_space("fake_kernel")
    path = str(tmp_path / "kernel_tuning_cache.json")
    with open(path, "w") as f:
        f.write('{"schema": 1, "entries": {"trunc')  # torn write
    before = _counter("autotune::cache_reject")
    c = TuningCache(path)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert c.lookup(space, {"n": 256}) is None
    assert any("tuning cache rejected" in str(x.message) for x in w)
    assert _counter("autotune::cache_reject") == before + 1
    # the reject is ONE-time, not per lookup
    assert c.lookup(space, {"n": 512}) is None
    assert _counter("autotune::cache_reject") == before + 1


_RESOLVE_IN_A_FRESH_PROCESS = """
import json, sys
import paddle_tpu
from paddle_tpu import profiler, tuning

path, infos = sys.argv[1], json.loads(sys.argv[2])
tuning.reset_tuning_cache(path)
params = {k: tuning.resolve(k, **info) for k, info in infos.items()}
c = profiler.counters()
print(json.dumps({"params": params, "pending": tuning.pending_searches(),
                  **{k: c.get("autotune::" + k, 0) for k in
                     ("search", "enqueued", "cache_hit", "cache_reject")}}))
"""


def test_real_kernels_tune_then_resolve_in_a_fresh_process(tmp_path,
                                                           child_env):
    """The search over the two real kernels under the wall clock
    (interpreted off the chip): the inadmissible candidate is pruned
    before any compile, the winners land in the versioned file, a FRESH
    process in search mode resolves them with no search at all, and a
    torn file there degrades to the defaults with one reject."""
    infos = {"layernorm_residual": dict(rows=128, h=256, dtype="float32"),
             "conv_bn_relu": dict(m=256, k=64, c=128, dtype="float32")}
    path = str(tmp_path / tuning.CACHE_FILE_NAME)
    set_flags({"kernel_autotune": "search"})
    tuning.reset_tuning_cache(path)
    tuner = tuning.KernelTuner(measure_n=1)
    res = tuner.tune("layernorm_residual",
                     candidates=[{"block_r": 8}, {"block_r": 32},
                                 {"block_r": 4096}],
                     **infos["layernorm_residual"])
    assert res.pruned == 1 and res.default_us is not None
    winners = {"layernorm_residual": res.params,
               "conv_bn_relu": tuner.tune(
                   "conv_bn_relu",
                   candidates=[{"tile_m": 64}, {"tile_m": 128}],
                   **infos["conv_bn_relu"]).params}
    with open(path) as f:
        raw = json.load(f)
    assert raw["schema"] == tuning.CACHE_SCHEMA_VERSION
    assert len(raw["entries"]) == 2
    assert _counter("autotune::search") == 2

    def fresh_process():
        out = subprocess.run(
            [sys.executable, "-c", _RESOLVE_IN_A_FRESH_PROCESS, path,
             json.dumps(infos)],
            env=child_env(FLAGS_kernel_autotune="search"),
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-3000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    got = fresh_process()
    assert got["params"] == winners
    assert got["search"] == got["enqueued"] == got["pending"] == 0
    assert got["cache_hit"] >= 2

    with open(path, "w") as f:
        f.write('{"schema": 1, "entries": {"torn')
    got = fresh_process()
    assert got["params"] == {
        k: tuning.schedule_space(k).default_params(info)
        for k, info in infos.items()}
    assert got["cache_reject"] == 1


def test_wrong_schema_version_degrades_to_defaults(tmp_path):
    _register_fake_space([])
    space = tuning.schedule_space("fake_kernel")
    path = str(tmp_path / "kernel_tuning_cache.json")
    with open(path, "w") as f:
        json.dump({"schema": 999, "entries": {}}, f)
    before = _counter("autotune::cache_reject")
    c = TuningCache(path)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert c.lookup(space, {"n": 256}) is None
    assert any("wrong schema" in str(x.message) for x in w)
    assert _counter("autotune::cache_reject") == before + 1


def test_malformed_entries_dropped_good_ones_kept(tmp_path):
    _register_fake_space([])
    space = tuning.schedule_space("fake_kernel")
    path = str(tmp_path / "kernel_tuning_cache.json")
    c1 = TuningCache(path)
    c1.put(space, {"n": 256}, {"block": 32, "unroll": 2})
    with open(path) as f:
        raw = json.load(f)
    raw["entries"]["bogus|key"] = {"params": "not-a-dict"}
    raw["entries"]["bogus2|key"] = 17
    with open(path, "w") as f:
        json.dump(raw, f)
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        c2 = TuningCache(path)
        assert c2.lookup(space, {"n": 256})["params"] == {
            "block": 32, "unroll": 2}
    assert len(c2) == 1  # the two malformed entries are gone


def test_stale_space_version_rejected(tmp_path):
    calls = []
    _register_fake_space(calls, version=1)
    space_v1 = tuning.schedule_space("fake_kernel")
    path = str(tmp_path / "kernel_tuning_cache.json")
    c = TuningCache(path)
    c.put(space_v1, {"n": 256}, {"block": 32, "unroll": 2})
    # the schedule space changes shape -> persisted entry is stale
    _register_fake_space(calls, version=2)
    space_v2 = tuning.schedule_space("fake_kernel")
    before = _counter("autotune::cache_reject")
    c2 = TuningCache(path)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert c2.lookup(space_v2, {"n": 256}) is None
        # repeated lookups of the same stale key count/warn ONCE — the
        # counter is a corruption signal, not a dispatch-rate meter
        assert c2.lookup(space_v2, {"n": 256}) is None
    assert _counter("autotune::cache_reject") == before + 1
    assert sum("stale space_version" in str(x.message) for x in w) == 1


def test_foreign_device_entries_do_not_apply(tmp_path):
    """A cache tuned on other silicon travels without poisoning this
    host: its entries key under the foreign device_kind and simply
    never hit."""
    _register_fake_space([])
    space = tuning.schedule_space("fake_kernel")
    path = str(tmp_path / "kernel_tuning_cache.json")
    c1 = TuningCache(path)
    c1.put(space, {"n": 256}, {"block": 32, "unroll": 2},
           device_kind="TPU v4")
    c2 = TuningCache(path)
    # same shape, THIS device kind (cpu under the test backend): miss
    assert c2.lookup(space, {"n": 256}) is None
    # the foreign entry is still there, keyed to its own device
    assert c2.lookup(space, {"n": 256}, device_kind="TPU v4") is not None


def test_per_device_kind_isolation_through_resolve():
    _register_fake_space([])
    space = tuning.schedule_space("fake_kernel")
    tuning.tuning_cache().put(space, {"n": 256},
                              {"block": 8, "unroll": 2},
                              device_kind="TPU v5e")
    # resolve keys on the DETECTED device kind (cpu here): defaults
    assert tuning.resolve("fake_kernel", n=256) == {"block": 16,
                                                    "unroll": 1}


def test_inadmissible_cached_params_degrade_to_defaults():
    """Buckets are coarser than shapes: a tuned point that does not
    admit this exact shape falls back to defaults, counted."""
    _register_fake_space([])
    space = tuning.schedule_space("fake_kernel")
    # n=200 buckets to 256; tune an entry only valid for n >= 32
    tuning.tuning_cache().put(space, {"n": 200}, {"block": 32,
                                                  "unroll": 1})
    assert tuning.resolve("fake_kernel", n=200) == {"block": 32,
                                                    "unroll": 1}
    before = _counter("autotune::cache_reject")
    # an entry in the 256 bucket (n=129..256) tuned with block=256:
    # resolving n=130 hits the bucket but fails the exact-shape
    # predicate (block <= n) -> defaults + one reject
    tuning.tuning_cache().put(space, {"n": 200}, {"block": 256,
                                                  "unroll": 1})
    assert tuning.resolve("fake_kernel", n=130) == {"block": 16,
                                                    "unroll": 1}
    assert _counter("autotune::cache_reject") == before + 1


# -- byte-identical defaults for the real kernels -----------------------------


def test_migrated_kernel_defaults_are_byte_identical():
    """Satellite contract: 'untuned' == the historical hardcoded
    geometry for every migrated kernel — the schedule plumbing changes
    nothing until a tuned entry lands."""
    # layernorm_residual: the _block_rows policy
    for rows, h in [(1024, 2048), (1024, 4096), (4, 256), (37, 256)]:
        assert tuning.resolve("layernorm_residual", rows=rows, h=h,
                              dtype="float32")["block_r"] \
            == lnr._block_rows(rows, h)
        assert lnr._schedule_block_rows(rows, h, "float32") \
            == lnr._block_rows(rows, h)
    # optimizer_update: min(rows, 2048)
    for rows in (8, 512, 2048, 65536):
        assert tuning.resolve("optimizer_update", rows=rows,
                              dtype="float32")["block_r"] \
            == min(rows, 2048)
    # int8_matmul: min(dim, 256) tiles
    p = tuning.resolve("int8_matmul", m=512, k=384, n=1024, dtype="int8")
    assert (p["tile_m"], p["tile_n"]) == (256, 256)
    assert im._schedule_tiles(64, 128, 128) == (64, 128)
    # flash_attention: 256/256 blocks, no unroll
    p = tuning.resolve("flash_attention", b=4, h=12, lq=512, lk=512,
                       d=64, dtype="float32")
    assert (p["block_q"], p["block_k"], p["unroll"]) == (256, 256, 1)
    # conv_bn_relu: min(dim, 256) tiles
    p = tuning.resolve("conv_bn_relu", m=4096, k=1152, c=256,
                       dtype="float32")
    assert (p["tile_m"], p["tile_n"]) == (256, 256)


def test_numerics_neutral_under_non_default_schedules():
    """A tuned (non-default) schedule changes WHERE the work tiles, not
    what it computes: interpret-mode kernels at odd block sizes match
    the jnp references (int8 bit-equal, floats to tolerance)."""
    rng = np.random.RandomState(0)
    # layernorm_residual at a deliberately small row block
    x = jnp.asarray(rng.randn(37, 256).astype("f4"))
    r = jnp.asarray(rng.randn(37, 256).astype("f4"))
    w = jnp.asarray(rng.randn(256).astype("f4"))
    b = jnp.asarray(rng.randn(256).astype("f4"))
    ref = lnr._reference(x, r, w, b, 1e-5)
    for block_r in (8, 16, 64):
        y, _, _ = lnr._pallas_fwd(x, r, w, b, 1e-5, interpret=True,
                                  block_r=block_r)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(y),
                                   rtol=1e-5, atol=1e-5)
    # optimizer_update across block sizes
    p = jnp.asarray(rng.randn(700, 130).astype("f4"))
    g = jnp.asarray(rng.randn(700, 130).astype("f4"))
    v = jnp.asarray(rng.randn(700, 130).astype("f4"))
    ref_p, ref_v = ou._jnp_update(p, g, v, 0.1, 0.9, 0.01, False)
    for block_r in (64, 512, 4096):
        out_p, out_v = ou._pallas_update(p, g, v, 0.1, 0.9, 0.01, False,
                                         interpret=True, block_r=block_r)
        np.testing.assert_allclose(np.asarray(ref_p), np.asarray(out_p),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ref_v), np.asarray(out_v),
                                   rtol=1e-6, atol=1e-6)
    # int8_matmul: integer math — bit-equal at EVERY tile geometry
    xi = jnp.asarray(rng.randint(-128, 128, (70, 200)), jnp.int8)
    wi = jnp.asarray(rng.randint(-128, 128, (200, 150)), jnp.int8)
    ref_i = np.asarray(im._jnp_matmul(xi, wi))
    for tiles in ((32, 128), (64, 256), (256, 128)):
        out = np.asarray(im._pallas_matmul(xi, wi, interpret=True,
                                           tiles=tiles))
        np.testing.assert_array_equal(ref_i, out)
    # conv_bn_relu eval pass across tile geometries
    p2 = jnp.asarray(rng.randn(100, 48).astype("f4"))
    w2 = jnp.asarray(rng.randn(48, 24).astype("f4"))
    scale = jnp.asarray(rng.rand(24).astype("f4") + 0.5)
    shift = jnp.asarray(rng.randn(24).astype("f4"))
    ref_c = np.maximum(
        np.asarray(jnp.dot(p2, w2,
                           preferred_element_type=jnp.float32))
        * np.asarray(scale) + np.asarray(shift), 0.0)
    for tiles in ((8, 128), (64, 256)):
        out = np.asarray(cbr._mm_affine_relu(p2, w2, scale, shift,
                                             interpret=True, tiles=tiles))
        np.testing.assert_allclose(ref_c, out, rtol=1e-5, atol=1e-5)


def test_resolved_schedule_actually_applies():
    """A cached winner changes the geometry the kernel runs (observable
    via the bwd partial-sum shape, which is per-row-tile)."""
    space = tuning.schedule_space("layernorm_residual")
    tuning.tuning_cache().put(space, {"rows": 64, "h": 128,
                                      "dtype": "float32"}, {"block_r": 8})
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 128).astype("f4"))
    r = jnp.asarray(rng.randn(64, 128).astype("f4"))
    w = jnp.asarray(rng.randn(128).astype("f4"))
    b = jnp.asarray(rng.randn(128).astype("f4"))
    assert lnr._schedule_block_rows(64, 128, "float32") == 8
    y, _, _ = lnr._pallas_fwd(x, r, w, b, 1e-5, interpret=True)
    np.testing.assert_allclose(
        np.asarray(lnr._reference(x, r, w, b, 1e-5)), np.asarray(y),
        rtol=1e-5, atol=1e-5)


# -- runtime coupling ---------------------------------------------------------


def test_schedule_token_tracks_mode_and_generation():
    t0 = tuning.schedule_token()
    set_flags({"kernel_autotune": "off"})
    assert tuning.schedule_token() == ("sched-off",)
    set_flags({"kernel_autotune": "cached"})
    assert tuning.schedule_token() == t0
    _register_fake_space([])
    tuning.tuning_cache().put(tuning.schedule_space("fake_kernel"),
                              {"n": 64}, {"block": 8, "unroll": 1})
    assert tuning.schedule_token() != t0


def test_compiled_store_recompiles_on_schedule_swap():
    """The stale-trace hazard: an entry whose trace resolved a schedule
    must NOT serve after a tuned swap-in of that schedule — the store
    rebuilds it once (<label>::schedule_refresh) and the NEW trace
    bakes the tuned params in. Entries that resolve no schedule are
    immune (no fleet-wide recompile waves)."""
    import jax
    import jax.numpy as jnp_

    from paddle_tpu.runtime.compiled import CompiledStore

    _register_fake_space([])
    store = CompiledStore("tunetest")
    builds = []

    def build():
        builds.append(1)

        def fn(x):
            # the traced program bakes the resolved schedule in
            p = tuning.resolve("fake_kernel", n=64)
            return x * p["block"]

        return jax.jit(fn), None

    def run(entry):
        return int(np.asarray(store.dispatch(entry, jnp_.ones(()))))

    # an entry that resolves NOTHING must never schedule-refresh
    plain_entry, _ = store.get_or_build("plain", lambda: (
        jax.jit(lambda x: x + 1), None))
    store.dispatch(plain_entry, jnp_.ones(()))

    entry, how = store.get_or_build("sig", build)
    assert how == "miss" and len(builds) == 1
    assert run(entry) == 16  # the default point
    entry, how = store.get_or_build("sig", build)
    assert how == "hit" and len(builds) == 1
    key0 = entry.cache_key
    # a tuned winner lands -> ONLY the resolving signature rebuilds
    tuning.tuning_cache().put(tuning.schedule_space("fake_kernel"),
                              {"n": 64}, {"block": 8, "unroll": 1})
    entry, how = store.get_or_build("sig", build)
    assert how == "miss" and len(builds) == 2
    assert run(entry) == 8  # the refreshed trace uses the tuned point
    assert entry.cache_key != key0  # new cost identity
    assert profiler.counters().get("tunetest::schedule_refresh") == 1
    # steady again; the non-resolving signature never refreshed
    _, how = store.get_or_build("sig", build)
    assert how == "hit" and len(builds) == 2
    _, how = store.get_or_build("plain", lambda: (None, None))
    assert how == "hit"
    assert profiler.counters().get("tunetest::schedule_refresh") == 1


def test_tuned_table_lists_this_devices_entries():
    _register_fake_space([])
    space = tuning.schedule_space("fake_kernel")
    tuning.tuning_cache().put(space, {"n": 64}, {"block": 8, "unroll": 2},
                              best_us=10.0, default_us=30.0)
    tuning.tuning_cache().put(space, {"n": 64}, {"block": 32, "unroll": 1},
                              device_kind="TPU v4")
    rows = tuning.tuned_table()
    assert len(rows) == 1
    assert rows[0]["kernel"] == "fake_kernel"
    assert rows[0]["params"] == {"block": 8, "unroll": 2}
    assert rows[0]["speedup"] == pytest.approx(3.0)
    assert tuning.tuned_table(device_kind="TPU v4")[0]["params"] == {
        "block": 32, "unroll": 1}

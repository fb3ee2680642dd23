#!/usr/bin/env python
"""CI smoke for the kernel autotuner (`make autotune-smoke`).

Asserts the four contracts the tuning subsystem rests on, end to end
on the CPU backend (pallas interpret mode drives the real search
pipeline; timings are real wall clock, selection logic is identical to
TPU):

1. **Fused-vs-jnp parity** — the layernorm_residual and conv+bn+relu
   pallas kernels match their unfused jnp references, INCLUDING under
   the non-default schedules the tuner may pick.
2. **Offline search works** — tuning the two kernels measures the
   default point, prunes invalid candidates before any compile, and
   records a winner in the versioned JSON cache file handed to
   ``tuning.reset_tuning_cache(path)``.
3. **Warm cache = zero search** — a FRESH process pointed at the same
   cache file resolves the tuned schedules with autotune::search == 0
   and autotune::cache_hit > 0 (the steady-state-pays-nothing
   contract), and the resolved params equal the parent's winners.
4. **Corruption degrades, never crashes** — a truncated cache file in
   a fresh process still resolves (defaults), with the
   autotune::cache_reject counter bumped exactly once.

Exit 0 on success.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

LN_INFO = dict(rows=128, h=256, dtype="float32")
CBR_INFO = dict(m=256, k=64, c=128, dtype="float32")


def _parity():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas as _pk  # noqa: F401 (bind modules)

    lnr = sys.modules["paddle_tpu.ops.pallas.layernorm_residual"]
    cbr = sys.modules["paddle_tpu.ops.pallas.conv_bn_relu"]

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(37, 256).astype("f4"))
    r = jnp.asarray(rng.randn(37, 256).astype("f4"))
    w = jnp.asarray(rng.randn(256).astype("f4"))
    b = jnp.asarray(rng.randn(256).astype("f4"))
    ref = lnr._reference(x, r, w, b, 1e-5)
    for block_r in (8, 32, 256):  # schedules the tuner may pick
        y, _, _ = lnr._pallas_fwd(x, r, w, b, 1e-5, interpret=True,
                                  block_r=block_r)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(y),
                                   rtol=1e-5, atol=1e-5)

    # a pointwise conv: the only kind the fused path takes
    xc = jnp.asarray(rng.randn(2, 24, 10, 10).astype("f4"))
    wc = jnp.asarray(rng.randn(8, 24, 1, 1).astype("f4") * 0.2)
    gamma = jnp.asarray(rng.rand(8).astype("f4") + 0.5)
    beta = jnp.asarray(rng.randn(8).astype("f4") * 0.1)
    mean = jnp.asarray(rng.randn(8).astype("f4") * 0.1)
    var = jnp.asarray(rng.rand(8).astype("f4") + 0.5)
    for training in (True, False):
        kw = dict(stride=1, padding=0, training=training, momentum=0.9,
                  eps=1e-5, data_format="NCHW")
        ry, rm, rv = cbr._reference(xc, wc, gamma, beta, mean, var, **kw)
        fy, fm, fv = cbr._fused(xc, wc, gamma, beta, mean, var,
                                interpret=True, force=True, **kw)
        np.testing.assert_allclose(np.asarray(ry), np.asarray(fy),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(rm), np.asarray(fm),
                                   rtol=1e-4, atol=1e-5)
        # backward through the fused kernels vs autodiff of the chain
        gr = jax.grad(lambda *a: (cbr._reference(*a, mean, var, **kw)[0]
                                  ** 2).sum(), argnums=(0, 1, 2, 3))(
            xc, wc, gamma, beta)
        gf = jax.grad(lambda *a: (cbr._fused(*a, mean, var,
                                             interpret=True, force=True,
                                             **kw)[0] ** 2).sum(),
                      argnums=(0, 1, 2, 3))(xc, wc, gamma, beta)
        for a, b_ in zip(gr, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-3, atol=2e-4)
    print("parity OK (layernorm + conv_bn_relu, pallas == jnp, "
          "default AND tuned schedules, fwd + bwd)")


def _tune_and_persist(cache_dir):
    from paddle_tpu import profiler, tuning
    from paddle_tpu.flags import set_flags

    path = os.path.join(cache_dir, tuning.CACHE_FILE_NAME)
    set_flags({"kernel_autotune": "search"})
    tuning.reset_tuning_cache(path)
    tuner = tuning.KernelTuner(measure_n=2)
    winners = {}
    res = tuner.tune("layernorm_residual",
                     candidates=[{"block_r": 8}, {"block_r": 32},
                                 {"block_r": 4096}],  # last one prunes
                     **LN_INFO)
    assert res.pruned == 1, res  # VMEM predicate fired BEFORE compile
    assert res.default_us is not None  # the baseline was measured
    winners["layernorm_residual"] = res.params
    res = tuner.tune("conv_bn_relu",
                     candidates=[{"tile_m": 64}, {"tile_m": 128}],
                     **CBR_INFO)
    winners["conv_bn_relu"] = res.params
    assert os.path.exists(path), "tuning cache file not written"
    with open(path) as f:
        raw = json.load(f)
    assert raw["schema"] == tuning.CACHE_SCHEMA_VERSION
    assert len(raw["entries"]) == 2
    # the winners resolve immediately in THIS process too
    assert tuning.resolve("layernorm_residual", **LN_INFO) \
        == winners["layernorm_residual"]
    c = profiler.counters()
    assert c.get("autotune::search", 0) == 2, c
    print(f"offline search OK: 2 kernels tuned, winners {winners}, "
          f"cache at {path}")
    return winners


_CHILD = r"""
import json, os, sys
sys.path.insert(0, {root!r})
import paddle_tpu
from paddle_tpu import profiler, tuning

tuning.reset_tuning_cache({path!r})
ln = tuning.resolve("layernorm_residual", **{ln_info!r})
cbr = tuning.resolve("conv_bn_relu", **{cbr_info!r})
c = profiler.counters()
print(json.dumps({{
    "layernorm_residual": ln,
    "conv_bn_relu": cbr,
    "search": c.get("autotune::search", 0),
    "enqueued": c.get("autotune::enqueued", 0),
    "cache_hit": c.get("autotune::cache_hit", 0),
    "cache_reject": c.get("autotune::cache_reject", 0),
    "pending": tuning.pending_searches(),
}}))
"""


def _fresh_process(cache_dir, extra_env=None):
    from paddle_tpu import tuning

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               FLAGS_kernel_autotune="search")
    env.update(extra_env or {})
    code = _CHILD.format(
        root=root, ln_info=LN_INFO, cbr_info=CBR_INFO,
        path=os.path.join(cache_dir, tuning.CACHE_FILE_NAME))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, (out.stdout, out.stderr)
    return json.loads(out.stdout.strip().splitlines()[-1])


def _warm_cache_zero_search(cache_dir, winners):
    got = _fresh_process(cache_dir)
    # the tuned winners crossed the process boundary...
    assert got["layernorm_residual"] == winners["layernorm_residual"], got
    assert got["conv_bn_relu"] == winners["conv_bn_relu"], got
    # ...and steady state paid ZERO search (mode=search, but every
    # resolve was a cache hit: nothing to enqueue, nothing to measure)
    assert got["search"] == 0, got
    assert got["enqueued"] == 0 and got["pending"] == 0, got
    assert got["cache_hit"] >= 2, got
    print("warm-cache round trip OK: fresh process resolved both tuned "
          "schedules with zero re-search")


def _corrupt_cache_degrades(cache_dir):
    from paddle_tpu import tuning

    path = os.path.join(cache_dir, tuning.CACHE_FILE_NAME)
    with open(path, "w") as f:
        f.write('{"schema": 1, "entries": {"torn')
    got = _fresh_process(cache_dir)
    # defaults, one file-level reject, no crash (exit 0 got us here)
    ln_default = tuning.schedule_space("layernorm_residual") \
        .default_params(LN_INFO)
    assert got["layernorm_residual"] == ln_default, got
    assert got["cache_reject"] == 1, got
    print("corrupt-cache OK: truncated file degraded to defaults with "
          "one cache_reject, no crash")


def main():
    _parity()
    cache_dir = tempfile.mkdtemp(prefix="ptpu_autotune_smoke_")
    try:
        winners = _tune_and_persist(cache_dir)
        _warm_cache_zero_search(cache_dir, winners)
        _corrupt_cache_degrades(cache_dir)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    print("autotune smoke OK")


if __name__ == "__main__":
    main()

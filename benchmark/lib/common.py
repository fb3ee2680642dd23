"""What every part of the benchmark shares: where the checkout is, the
compile cache, the look for a chip, loading a module by file path, and
quantiles. Nothing here imports the program."""
from __future__ import annotations

import importlib.util
import json
import os
import statistics
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class NoChip(SystemExit):
    """Raised (exit code 3) when JAX finds no TPU or too few chips."""


def setup_env():
    """Before jax is imported: the persistent compile cache at
    JAX_COMPILATION_CACHE_DIR if set, else a fixed path in the checkout
    (the path is part of the cache key); cache every program that took
    a tenth of a second to compile; the compiler's logs off."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)


def device_info(require_chips=None):
    """The device as JAX reports it. With ``require_chips`` a platform
    other than tpu, or fewer chips, ends the process with code 3 and no
    result line: nothing falls back to the CPU."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chips is not None:
        if info["platform"] != "tpu" or len(devs) < require_chips:
            print(f"benchmark: needs {require_chips} TPU chip(s), jax "
                  f"reports {info}", file=sys.stderr, flush=True)
            raise NoChip(3)
    return info


def memory_peak_bytes(devices):
    """Peak on the fullest chip. ``peak_bytes_in_use`` counts live
    arrays only on this runtime (PERF.md), a running program's scratch
    shows under ``peak_bytes_reserved``: the larger of the two."""
    peak = 0
    for d in devices:
        s = d.memory_stats() or {}
        peak = max(peak, s.get("peak_bytes_in_use", 0),
                   s.get("peak_bytes_reserved", 0))
    return int(peak)


def assign_weights(model, weights):
    """Hand the benchmark's weights to the program's model by parameter
    name; a name or a shape that differs is an error, not a skip."""
    named = dict(model.named_parameters())
    if set(named) != set(weights):
        raise RuntimeError("parameter names differ from the benchmark's: "
                           f"{sorted(set(named) ^ set(weights))[:8]}")
    for name, p in named.items():
        if tuple(p._array.shape) != tuple(weights[name].shape):
            raise RuntimeError(f"{name}: {p._array.shape} vs "
                               f"{weights[name].shape}")
        p._array = weights[name]


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name=None):
    """Import a python file by path (configs and metrics are found by
    the names in BENCHMARK.json, not by an import table)."""
    name = name or "bench_" + os.path.relpath(path, ROOT).replace(
        os.sep, "_").replace("-", "_").replace(".py", "")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def pctl(values, q):
    """q-th percentile (0-100), linear interpolation, of a non-empty
    list; the plain definition, no numpy needed in the load generator."""
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def spread(values):
    """Distance between the first and third quartile as a share of the
    median (statistics.quantiles, n=4): the contract's spread."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def seed_key(seed, tag=0):
    """A jax PRNG key from any whole-number seed (the driver's are above
    2**31) and a small tag."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed % 2147483629)
    return jax.random.fold_in(jax.random.fold_in(key, seed // 2147483629),
                              tag)


def host_rng(seed, tag=0):
    import numpy as np

    return np.random.default_rng([int(seed), int(tag)])

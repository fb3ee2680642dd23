"""The compile cache is placed from outside (runtime/compile_cache.py):
JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache — the same
string in every process, since the path is part of the cache key."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PROBE = ("import paddle_tpu, jax; "
          "from paddle_tpu.runtime import compile_cache; "
          "print(jax.config.jax_compilation_cache_dir); "
          "print(compile_cache.cache_dir())")


def _probe(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    return subprocess.Popen([sys.executable, "-c", _PROBE], env=env,
                            cwd="/", stdout=subprocess.PIPE, text=True)


def test_cache_dir_follows_the_environment_or_the_checkout(tmp_path):
    outside = str(tmp_path / "placed_from_outside")
    before = set(os.listdir(ROOT))
    procs = [_probe(outside), _probe(None), _probe(None)]
    outs = [p.communicate(timeout=120)[0].split() for p in procs]
    assert all(p.returncode == 0 for p in procs)
    # set: jax holds the variable's value, the resolver agrees, and
    # nothing was created under the checkout (nor the directory itself)
    assert outs[0] == [outside, outside]
    assert not os.path.exists(outside)
    assert set(os.listdir(ROOT)) - before <= {".jax_cache"}
    # unset: <checkout>/.jax_cache, identical in two processes
    assert outs[1] == outs[2] == [os.path.join(ROOT, ".jax_cache")] * 2


def test_this_process_uses_the_resolved_dir():
    import jax

    from paddle_tpu.runtime import compile_cache

    assert jax.config.jax_compilation_cache_dir == compile_cache.cache_dir()

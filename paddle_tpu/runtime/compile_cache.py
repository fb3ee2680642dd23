"""Where XLA's persistent compilation cache lives.

One rule for every entry point (benchmark, examples, serving backends,
``chip_smoke.py``, the tests): if ``JAX_COMPILATION_CACHE_DIR`` is set,
jax reads it itself and this module sets nothing; otherwise the cache is
``<checkout>/.jax_cache``, derived from the package's own location. The
path is part of the cache key, so it must be the same string in every
process — never a temp dir, a pid or a timestamp.

``paddle_tpu/__init__.py`` calls :func:`apply` at import, which is before
the first compile of any program that uses the package.
"""
from __future__ import annotations

import os

import jax

__all__ = ["apply", "cache_dir"]

_ENV = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> str:
    """The directory the compile cache resolves to in this process."""
    env = os.environ.get(_ENV)
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def apply() -> str:
    """Point jax's persistent compilation cache at :func:`cache_dir` and
    return it. With the environment variable set this touches no jax
    config (jax already holds the variable's value). Creates nothing:
    jax makes the directory at its first cache write."""
    d = cache_dir()
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", d)
    return d

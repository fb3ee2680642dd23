"""RNG state.

Reference parity: paddle/fluid/framework/generator.h + pybind/generator_py.cc
(global generator with seed control). TPU-native design: state is a JAX PRNG
key. Eager ops split the global key statefully; functionalized/jitted train
steps swap the key for a traced one so randomness threads through the
compiled step as data (see framework/jit.py).
"""
from __future__ import annotations

import contextlib
import os

import jax

_PRNG_IMPL = None


def prng_impl() -> str:
    """PRNG implementation for all framework keys.

    TPU default is ``rbg`` (XLA's counter-based hardware RNG): dropout-heavy
    steps (BERT pretraining has 25+ dropout sites) are ~25% faster end to
    end than with threefry, measured on v5e. CPU keeps ``threefry2x32`` so
    test vectors stay stable. Override with PADDLE_TPU_PRNG=threefry2x32
    (e.g. for bit-exact cross-platform reproducibility studies).
    """
    global _PRNG_IMPL
    if _PRNG_IMPL is None:
        env = os.environ.get("PADDLE_TPU_PRNG", "")
        if env:
            _PRNG_IMPL = env
        else:
            # accelerators get rbg; only plain CPU keeps threefry
            _PRNG_IMPL = ("threefry2x32" if jax.default_backend() == "cpu"
                          else "rbg")
    return _PRNG_IMPL


class Generator:
    """Stateful wrapper over a jax PRNG key.

    Key creation is lazy: the impl (and thus the backend query) resolves on
    first RNG use, not at `import paddle_tpu` — user code gets a chance to
    call jax.config.update("jax_platforms", ...) / set PADDLE_TPU_PRNG
    after import.
    """

    def __init__(self, seed: int = 0):
        self._key = None
        self._seed = seed

    def _ensure(self):
        if self._key is None:
            # never a tracer: the first RNG use may come from inside a
            # trace (a program lowered before any eager draw), and a key
            # made there would leak into this global state. Same value.
            with jax.ensure_compile_time_eval():
                self._key = jax.random.key(self._seed, impl=prng_impl())

    def manual_seed(self, seed: int):
        self._seed = seed
        self._key = None
        return self

    def initial_seed(self) -> int:
        return self._seed

    def split(self):
        """Return a fresh subkey, advancing internal state."""
        self._ensure()
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- functionalization hooks (used by jit/train-step capture) ----------
    def get_state(self):
        self._ensure()
        return self._key

    def set_state(self, key):
        self._key = key


_default_generator = Generator(0)


def default_generator() -> Generator:
    return _default_generator


def seed(value: int):
    """Set the global RNG seed (paddle.seed)."""
    _default_generator.manual_seed(int(value))
    return _default_generator


def split_key():
    return _default_generator.split()


@contextlib.contextmanager
def fork_rng(seed_value: int | None = None):
    """Temporarily fork RNG state (deterministic scope)."""
    saved = _default_generator.get_state()
    if seed_value is not None:
        _default_generator.manual_seed(seed_value)
    try:
        yield
    finally:
        _default_generator.set_state(saved)

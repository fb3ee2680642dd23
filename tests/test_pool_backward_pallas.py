"""Pallas fused max-pool backward vs XLA select_and_scatter.

Reference semantics: operators/math/pooling.cu MaxPool2dGradFunctor —
gradient routed to the FIRST max position in each window (ties included).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops.pallas.pool_backward import max_pool2d_backward


def test_platform_gate_shared_across_pallas_kernels():
    """Both pallas dispatch gates consume the ONE shared platform
    predicate (ops/pallas/_platform.py) so they cannot drift."""
    import importlib

    from paddle_tpu.ops.pallas import _platform
    from paddle_tpu.ops.pallas import pool_backward as pb

    # the package re-exports the flash_attention FUNCTION; get the module
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    assert pb.can_emit_mosaic is _platform.can_emit_mosaic
    assert fa.can_emit_mosaic is _platform.can_emit_mosaic
    # on the CPU test backend both gates reject the pallas path
    if jax.devices()[0].platform == "cpu":
        assert _platform.on_tpu_platform() is False
        assert _platform.can_emit_mosaic() is False
        assert pb.max_pool_backward_supported(
            (2, 3, 8, 8), jnp.float32, (2, 2), (2, 2), (0, 0), (0, 0),
            "NCHW") is False


def _xla_pool_vjp(x, dy, ks, st, p):
    window = (1, 1) + ks
    strides = (1, 1) + st
    pads = ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]))

    def pool(x):
        return lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pads)

    y, vjp = jax.vjp(pool, x)
    (dx,) = vjp(dy.astype(y.dtype))
    return np.asarray(y), np.asarray(dx)


GEOMS = [
    # (shape, kernel, stride, padding) — stem shape last (scaled down)
    ((2, 3, 8, 8), (2, 2), (2, 2), (0, 0)),
    ((2, 2, 9, 9), (3, 3), (2, 2), (1, 1)),
    ((1, 4, 12, 16), (3, 3), (1, 1), (1, 1)),
    ((2, 2, 14, 14), (3, 3), (2, 2), (1, 1)),
    ((1, 1, 8, 8), (3, 2), (2, 3), (1, 0)),
]


@pytest.mark.parametrize("shape,ks,st,p", GEOMS)
def test_matches_xla_select_and_scatter(shape, ks, st, p):
    rng = np.random.RandomState(0)
    x = rng.randn(*shape).astype(np.float32)
    oh = (shape[2] + 2 * p[0] - ks[0]) // st[0] + 1
    ow = (shape[3] + 2 * p[1] - ks[1]) // st[1] + 1
    dy = rng.randn(shape[0], shape[1], oh, ow).astype(np.float32)
    y, want = _xla_pool_vjp(x, dy, ks, st, p)
    got = np.asarray(max_pool2d_backward(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(dy),
        kernel=ks, stride=st, padding=p, interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_tie_handling_first_max_wins():
    """Constant inputs make every window an all-tie: the whole gradient
    must land on the FIRST tap of each window, exactly like
    select_and_scatter's ge-select."""
    x = np.zeros((1, 1, 8, 8), np.float32)
    ks, st, p = (2, 2), (2, 2), (0, 0)
    dy = np.ones((1, 1, 4, 4), np.float32)
    y, want = _xla_pool_vjp(jnp.asarray(x), jnp.asarray(dy), ks, st, p)
    got = np.asarray(max_pool2d_backward(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(dy),
        kernel=ks, stride=st, padding=p, interpret=True))
    np.testing.assert_array_equal(got, want)
    # and the winner is the top-left corner of each window
    assert got[0, 0, 0, 0] == 1.0 and got[0, 0, 0, 1] == 0.0


def test_bf16_stem_geometry():
    """bf16 carrier (the AMP path) at a scaled stem geometry."""
    rng = np.random.RandomState(1)
    x = rng.randn(2, 4, 28, 28).astype(np.float32)
    ks, st, p = (3, 3), (2, 2), (1, 1)
    xb = jnp.asarray(x, jnp.bfloat16)
    y, want = _xla_pool_vjp(xb, jnp.ones((2, 4, 14, 14)), ks, st, p)
    got = np.asarray(max_pool2d_backward(
        xb, jnp.asarray(y), jnp.ones((2, 4, 14, 14), jnp.bfloat16),
        kernel=ks, stride=st, padding=p, interpret=True).astype(jnp.float32))
    np.testing.assert_allclose(
        got, np.asarray(want, np.float32), rtol=1e-2, atol=1e-2)


def test_full_model_path_unaffected_on_cpu():
    """On CPU the dispatch gate keeps the XLA path; training through
    F.max_pool2d stays correct."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    x = paddle.to_tensor(
        np.random.RandomState(2).randn(2, 3, 8, 8).astype(np.float32),
        stop_gradient=False)
    out = F.max_pool2d(x, kernel_size=3, stride=2, padding=1)
    out.sum().backward()
    assert x.grad is not None and np.isfinite(x.grad.numpy()).all()

"""Max-pool gradient through the tape against a numpy reference.

Reference semantics: operators/math/pooling.cu MaxPool2dGradFunctor —
gradient routed to the FIRST max position in each window (ties included).
The pooling itself is ``lax.reduce_window`` (ops/kernels.py pool2d), so
this also checks its pad arithmetic: symmetric padding and ceil mode.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F


def _out_hw(shape, ks, st, p, ceil_mode=False):
    out = []
    for dim, k, s, pp in zip(shape[2:], ks, st, p):
        span = dim + 2 * pp - k
        out.append((-(-span // s) if ceil_mode else span // s) + 1)
    return tuple(out)


def _numpy_pool_vjp(x, dy, ks, st, p):
    """dx of max pooling, window by window; argmax takes the first max."""
    n, c, h, w = x.shape
    out = dy.shape[2:]
    # far side padded to where the last window ends (ceil mode overhangs)
    xp = np.full((n, c, (out[0] - 1) * st[0] + ks[0] + p[0],
                  (out[1] - 1) * st[1] + ks[1] + p[1]), -np.inf, x.dtype)
    xp[:, :, p[0]:p[0] + h, p[1]:p[1] + w] = x
    dxp = np.zeros_like(xp)
    for i in range(out[0]):
        for j in range(out[1]):
            hs, ws = i * st[0], j * st[1]
            win = xp[:, :, hs:hs + ks[0], ws:ws + ks[1]].reshape(n, c, -1)
            first = np.zeros_like(win)
            np.put_along_axis(first, win.argmax(-1)[..., None], 1.0, -1)
            dxp[:, :, hs:hs + ks[0], ws:ws + ks[1]] += (
                first.reshape(n, c, *ks) * dy[:, :, i, j, None, None])
    return dxp[:, :, p[0]:p[0] + h, p[1]:p[1] + w]


def _tape_grad(x, dy, ks, st, p, ceil_mode=False):
    xt = paddle.to_tensor(x, stop_gradient=False)
    out = F.max_pool2d(xt, kernel_size=ks, stride=st, padding=p,
                       ceil_mode=ceil_mode)
    (out * paddle.to_tensor(dy)).sum().backward()
    return xt.grad.numpy()


GEOMS = [
    # (shape, kernel, stride, padding, ceil_mode); kernel 2 / stride 2
    # unpadded is tests/test_ops_math.py TestPool2D.test_grad
    ((2, 2, 9, 9), (3, 3), (2, 2), (1, 1), False),
    ((1, 4, 12, 16), (3, 3), (1, 1), (1, 1), False),
    ((2, 2, 14, 14), (3, 3), (2, 2), (1, 1), False),  # ResNet stem, scaled
    ((1, 1, 8, 8), (3, 2), (2, 3), (1, 0), False),
    ((1, 2, 8, 10), (3, 3), (2, 2), (0, 1), True),
]


@pytest.mark.parametrize("shape,ks,st,p,ceil_mode", GEOMS)
def test_max_pool_grad_matches_numpy(shape, ks, st, p, ceil_mode):
    rng = np.random.RandomState(0)
    x = rng.randn(*shape).astype(np.float32)
    dy = rng.randn(*shape[:2], *_out_hw(shape, ks, st, p, ceil_mode)).astype(
        np.float32)
    want = _numpy_pool_vjp(x, dy, ks, st, p)
    got = _tape_grad(x, dy, ks, st, p, ceil_mode)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_tie_handling_first_max_wins():
    """Constant inputs make every window an all-tie: the whole gradient
    must land on the FIRST tap of each window (Paddle's documented
    subgradient; XLA's select_and_scatter ge-select gives the same)."""
    x = np.zeros((1, 1, 8, 8), np.float32)
    ks, st, p = (2, 2), (2, 2), (0, 0)
    dy = np.ones((1, 1, 4, 4), np.float32)
    want = _numpy_pool_vjp(x, dy, ks, st, p)
    got = _tape_grad(x, dy, ks, st, p)
    np.testing.assert_array_equal(got, want)
    # and the winner is the top-left corner of each window
    assert got[0, 0, 0, 0] == 1.0 and got[0, 0, 0, 1] == 0.0


def test_full_model_path_trains_through_max_pool():
    """Training through F.max_pool2d: every output's gradient reaches
    exactly one input."""
    x = paddle.to_tensor(
        np.random.RandomState(2).randn(2, 3, 8, 8).astype(np.float32),
        stop_gradient=False)
    out = F.max_pool2d(x, kernel_size=3, stride=2, padding=1)
    out.sum().backward()
    g = x.grad.numpy()
    assert np.isfinite(g).all() and g.sum() == out.numpy().size

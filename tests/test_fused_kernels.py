"""Fused pallas kernels (optimizer update, layernorm+residual) and the
overlapped device prefetcher.

The pallas paths are gated to TPU, so the CPU suite certifies them two
ways: interpret-mode pallas vs the jnp reference (the kernels' math is
right, including the masked row tails), and flag-on vs flag-off parity
through the REAL call sites (Momentum, the post-norm transformer) — the
jnp fallback computes the identical primitive sequence, so enabling the
flags must never change numerics anywhere.
"""
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as popt
from paddle_tpu.flags import set_flags
from paddle_tpu.framework.tensor import to_tensor

# the package re-exports shadow the submodule names; reach the modules
from paddle_tpu.ops.pallas import optimizer_update as _  # noqa: F401
from paddle_tpu.ops.pallas import layernorm_residual as _  # noqa: F401
from paddle_tpu.ops.pallas import conv_bn_relu as _  # noqa: F401

ou = sys.modules["paddle_tpu.ops.pallas.optimizer_update"]
lnr = sys.modules["paddle_tpu.ops.pallas.layernorm_residual"]
cbr = sys.modules["paddle_tpu.ops.pallas.conv_bn_relu"]


@pytest.fixture
def _flags_restored():
    yield
    set_flags({"use_fused_optimizer": True, "use_fused_layernorm": True,
               "use_fused_conv_bn": True, "io_prefetch_overlap": True})


# -- shared platform gate -----------------------------------------------------


def test_platform_gate_shared_across_pallas_kernels():
    """Every pallas dispatch gate consumes the ONE shared platform
    predicate (ops/pallas/_platform.py) so they cannot drift."""
    import importlib

    from paddle_tpu.ops.pallas import _platform

    # the package re-exports the kernel FUNCTIONS; get the modules
    for name in ("flash_attention", "int8_matmul", "layernorm_residual",
                 "optimizer_update", "conv_bn_relu"):
        mod = importlib.import_module("paddle_tpu.ops.pallas." + name)
        assert mod.can_emit_mosaic is _platform.can_emit_mosaic, name
    # on the CPU test backend the gate rejects the pallas path
    if jax.devices()[0].platform == "cpu":
        assert _platform.on_tpu_platform() is False
        assert _platform.can_emit_mosaic() is False


# -- fused momentum update ----------------------------------------------------


@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("wd", [0.0, 0.01])
def test_momentum_kernel_interpret_parity(nesterov, wd):
    """Pallas (interpret) == jnp reference, including a size that needs
    lane padding (1000*130 is no multiple of 8*128)."""
    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.randn(1000, 130).astype("f4"))
    g = jnp.asarray(rng.randn(1000, 130).astype("f4"))
    v = jnp.asarray(rng.randn(1000, 130).astype("f4"))
    ref_p, ref_v = ou._jnp_update(p, g, v, 0.1, 0.9, wd, nesterov)
    out_p, out_v = ou._pallas_update(p, g, v, 0.1, 0.9, wd, nesterov,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(ref_p), np.asarray(out_p),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ref_v), np.asarray(out_v),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("wd", [0.0, 0.01])
@pytest.mark.parametrize("shape", [(32, 16, 3, 3), (64, 3, 7, 7)])
def test_momentum_refused_shape_is_the_jnp_update(shape, nesterov, wd,
                                                  monkeypatch):
    """With the platform gate open a weight with a spatial extent never
    reaches the kernel (on this backend its call could not even
    compile): the public function returns ``_jnp_update``'s result bit
    for bit, and a vector of the same size is still admitted."""
    monkeypatch.setattr(ou, "can_emit_mosaic", lambda: True)
    rng = np.random.RandomState(0)
    p, g, v = (jnp.asarray(rng.randn(*shape).astype("f4"))
               for _ in range(3))
    assert not ou._supported(p, g, v)
    assert ou._supported(*(a.reshape(-1) for a in (p, g, v)))
    ref = jax.jit(lambda p, g, v, lr: ou._jnp_update(
        p, g, v, lr, 0.9, wd, nesterov))(p, g, v, 0.1)
    out = jax.jit(lambda p, g, v, lr: ou.fused_momentum_update(
        p, g, v, lr, momentum=0.9, weight_decay=wd,
        use_nesterov=nesterov))(p, g, v, 0.1)
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_momentum_counters_say_which_leaves_took_the_kernel(monkeypatch):
    """One trace of a ResNet-18 Momentum step with the gate open: every
    convolution with a spatial extent (the stem and sixteen 3x3) and the
    ten batch-norm leaves of width 64 (under 128 elements) go to XLA;
    the thirty wider batch-norm leaves, the three pointwise projections
    and the classifier's two leaves take the kernel. With the flag off
    nothing is counted: the optimizer never asks."""
    from paddle_tpu import profiler
    from paddle_tpu.framework import jit as fjit
    from paddle_tpu.models import resnet18

    monkeypatch.setattr(ou, "can_emit_mosaic", lambda: True)

    def traced():
        paddle.seed(0)
        m = resnet18(num_classes=1000)
        step = fjit.train_step(
            m, popt.Momentum(learning_rate=0.01, momentum=0.9,
                             parameters=m.parameters()),
            lambda mm, x, y: F.cross_entropy(mm(x), y).mean())
        before = profiler.counters()
        jax.eval_shape(step.pure, step.state,
                       (jnp.zeros((2, 3, 32, 32), "float32"),
                        jnp.zeros((2,), "int32")),
                       jnp.float32(0.01), step._rng)
        return {k[len("optimizer::momentum_"):]: v - before.get(k, 0)
                for k, v in profiler.counters().items()
                if k.startswith("optimizer::momentum_")
                and v != before.get(k, 0)}

    assert traced() == {"kernel": 35, "xla": 27}
    set_flags({"use_fused_optimizer": False})
    try:
        assert traced() == {}
    finally:
        set_flags({"use_fused_optimizer": True})


def _momentum_net_steps(steps=4, **mom_kw):
    paddle.seed(7)
    net = nn.Linear(16, 4)
    opt = popt.Momentum(learning_rate=0.05, momentum=0.9,
                        parameters=net.parameters(), **mom_kw)
    rng = np.random.RandomState(1)
    X = to_tensor(rng.randn(8, 16).astype("f4"))
    Y = to_tensor(rng.randn(8, 4).astype("f4"))
    for _ in range(steps):
        loss = F.mse_loss(net(X), Y).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    return [np.asarray(p) for p in net.parameters()]


@pytest.mark.parametrize("mom_kw", [
    {}, {"weight_decay": 0.01}, {"use_nesterov": True},
    {"weight_decay": 0.02, "use_nesterov": True},
])
def test_momentum_fused_flag_is_numerically_free(mom_kw, _flags_restored):
    """Flag on vs off: bit-compatible through the real optimizer (the
    fused jnp fallback is the same expression in the same order)."""
    set_flags({"use_fused_optimizer": True})
    fused = _momentum_net_steps(**mom_kw)
    set_flags({"use_fused_optimizer": False})
    unfused = _momentum_net_steps(**mom_kw)
    for a, b in zip(fused, unfused):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_momentum_fused_with_grad_clip_keeps_decay_before_clip(
        _flags_restored):
    """grad_clip must see the DECAYED grad: the fused-wd fold is
    disabled under clipping and parity still holds."""
    kw = {"weight_decay": 0.05,
          "grad_clip": popt.ClipGradByGlobalNorm(0.5)}
    set_flags({"use_fused_optimizer": True})
    fused = _momentum_net_steps(**kw)
    set_flags({"use_fused_optimizer": False})
    unfused = _momentum_net_steps(**kw)
    for a, b in zip(fused, unfused):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_momentum_fused_inside_compiled_train_step(_flags_restored):
    """The fused update traces into TrainStepFn: same loss trajectory
    with the flag on and off (the resnet50 cell's configuration)."""
    from paddle_tpu.framework import jit as fjit

    def run():
        paddle.seed(3)
        net = nn.Linear(12, 3)
        opt = popt.Momentum(learning_rate=0.1, momentum=0.9,
                            weight_decay=0.01,
                            parameters=net.parameters())
        step = fjit.train_step(
            net, opt, lambda m, x, y: F.mse_loss(m(x), y).mean())
        rng = np.random.RandomState(0)
        X = rng.randn(8, 12).astype("f4")
        Y = rng.randn(8, 3).astype("f4")
        return [float(np.asarray(step(X, Y)["loss"])) for _ in range(5)]

    set_flags({"use_fused_optimizer": True})
    fused = run()
    set_flags({"use_fused_optimizer": False})
    unfused = run()
    np.testing.assert_allclose(fused, unfused, rtol=1e-6)
    assert fused[-1] < fused[0]  # it actually trains


# -- fused layernorm + residual ----------------------------------------------


def test_layernorm_residual_interpret_parity_fwd_bwd():
    """Pallas (interpret) forward AND backward == the jnp reference,
    with a row count that exercises the masked tail tile."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(37, 256).astype("f4"))
    r = jnp.asarray(rng.randn(37, 256).astype("f4"))
    w = jnp.asarray(rng.randn(256).astype("f4"))
    b = jnp.asarray(rng.randn(256).astype("f4"))
    eps = 1e-5
    ref = lnr._reference(x, r, w, b, eps)
    y, mean, rstd = lnr._pallas_fwd(x, r, w, b, eps, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(y),
                               rtol=1e-5, atol=1e-5)
    dy = jnp.asarray(rng.randn(37, 256).astype("f4"))
    _, vjp = jax.vjp(lambda x, r, w, b: lnr._reference(x, r, w, b, eps),
                     x, r, w, b)
    dx_ref, dr_ref, dw_ref, db_ref = vjp(dy)
    da, dw, db = lnr._pallas_bwd(x, r, w, mean, rstd, dy, interpret=True)
    np.testing.assert_allclose(np.asarray(dx_ref), np.asarray(da),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dr_ref), np.asarray(da),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw_ref), np.asarray(dw),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(db_ref), np.asarray(db),
                               rtol=1e-4, atol=1e-4)


def test_layernorm_residual_bf16_parity_within_ulp():
    """bf16 parity: the kernel expresses the residual add in the INPUT
    dtype (same expression as the unfused path), so fused and unfused
    agree to bf16 rounding noise. Bit-exactness is NOT achievable even
    between the unfused path's own jitted and eager forms — XLA keeps
    or drops the bf16 rounding of fused intermediates per fusion
    decision — so 1-ulp agreement is the contract, like AMP's."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(16, 128).astype("f4")).astype(jnp.bfloat16)
    r = jnp.asarray(rng.randn(16, 128).astype("f4")).astype(jnp.bfloat16)
    w = jnp.asarray(rng.randn(128).astype("f4"))
    b = jnp.asarray(rng.randn(128).astype("f4"))
    ref = lnr._reference(x, r, w, b, 1e-5)
    y, mean, rstd = lnr._pallas_fwd(x, r, w, b, 1e-5, interpret=True)
    assert y.dtype == jnp.bfloat16
    yf = np.asarray(y, np.float32)
    rf = np.asarray(ref, np.float32)
    # the bound is the bf16 ulp of the PRE-normalization sum propagated
    # through the affine: ulp(|a|_row) * rstd_row * |w| (+ one output
    # rounding) — near-zero outputs legitimately carry the full input
    # rounding, so an output-relative bound would be wrong
    a = np.asarray((x + r).astype(jnp.float32))
    ulp_in = 2.0 ** -8 * np.abs(a).max(axis=-1, keepdims=True)
    bound = (2.0 * ulp_in * np.asarray(rstd) * (np.abs(np.asarray(w)) + 1.0)
             + 2.0 ** -8 * np.abs(rf))
    d = np.abs(yf - rf)
    assert np.all(d <= bound), (d.max(), (d - bound).max())


def test_layernorm_block_rows_scale_with_h(monkeypatch):
    """Row blocks shrink as H grows so the bwd kernel's live blocks fit
    VMEM; _supported rejects H past the floor's budget."""
    assert lnr._block_rows(1024, 2048) == 256  # historical tiling kept
    assert lnr._block_rows(1024, 4096) == 128
    assert lnr._block_rows(1024, 8192) == 64
    assert lnr._block_rows(1024, 16384) == 32
    assert lnr._block_rows(4, 256) == 4  # tiny inputs: one short tile
    monkeypatch.setattr(lnr, "can_emit_mosaic", lambda: True)
    ok = jnp.zeros((2, lnr._MAX_H), jnp.float32)
    wok = jnp.zeros((lnr._MAX_H,), jnp.float32)
    assert lnr._supported(ok, ok, wok, wok)
    big = jnp.zeros((2, lnr._MAX_H * 2), jnp.float32)
    wbig = jnp.zeros((lnr._MAX_H * 2,), jnp.float32)
    assert not lnr._supported(big, big, wbig, wbig)


def test_layernorm_residual_tensor_autograd_matches_unfused():
    """Tensor-level fused op == norm(residual + y), forward and grads
    (through the framework op tape)."""
    from paddle_tpu.ops.pallas import layernorm_residual

    rng = np.random.RandomState(2)
    ln = nn.LayerNorm(64)
    x = to_tensor(rng.randn(5, 7, 64).astype("f4"), stop_gradient=False)
    r = to_tensor(rng.randn(5, 7, 64).astype("f4"), stop_gradient=False)

    out_f = layernorm_residual(x, r, ln.weight, ln.bias, ln.epsilon)
    out_f.sum().backward()
    gx_f, gr_f = np.asarray(x.grad), np.asarray(r.grad)
    gw_f = np.asarray(ln.weight.grad)
    x.clear_grad(), r.clear_grad(), ln.weight.clear_grad()

    out_u = ln(r + x)
    out_u.sum().backward()
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_u),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(gx_f, np.asarray(x.grad),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gr_f, np.asarray(r.grad),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gw_f, np.asarray(ln.weight.grad),
                               rtol=1e-5, atol=1e-6)


def test_post_norm_encoder_layer_flag_parity(_flags_restored):
    """The post-norm TransformerEncoderLayer routes its residual+norm
    pairs through the fused op — flag on/off outputs are identical."""
    def run():
        paddle.seed(11)
        layer = nn.TransformerEncoderLayer(
            64, 4, 128, dropout=0.0, normalize_before=False)
        layer.eval()
        x = to_tensor(np.random.RandomState(5)
                      .randn(2, 9, 64).astype("f4"))
        return np.asarray(layer(x))

    set_flags({"use_fused_layernorm": True})
    fused = run()
    set_flags({"use_fused_layernorm": False})
    unfused = run()
    np.testing.assert_allclose(fused, unfused, rtol=1e-6, atol=1e-6)


def test_pre_norm_layer_unaffected_by_flag(_flags_restored):
    """normalize_before=True has no add+norm pair to fuse: both flag
    states run the identical pre-norm graph."""
    def run():
        paddle.seed(12)
        layer = nn.TransformerEncoderLayer(
            32, 2, 64, dropout=0.0, normalize_before=True)
        layer.eval()
        x = to_tensor(np.random.RandomState(6)
                      .randn(2, 5, 32).astype("f4"))
        return np.asarray(layer(x))

    set_flags({"use_fused_layernorm": True})
    a = run()
    set_flags({"use_fused_layernorm": False})
    b = run()
    np.testing.assert_allclose(a, b, rtol=0, atol=0)


# -- fused conv + batch_norm + relu -------------------------------------------


def _cbr_operands(cin=3, cout=8, kh=1, df="NCHW", seed=0, n=2, h=10):
    rng = np.random.RandomState(seed)
    shape = (n, cin, h, h) if df == "NCHW" else (n, h, h, cin)
    x = jnp.asarray(rng.randn(*shape).astype("f4"))
    w = jnp.asarray(rng.randn(cout, cin, kh, kh).astype("f4") * 0.2)
    gamma = jnp.asarray(rng.rand(cout).astype("f4") + 0.5)
    beta = jnp.asarray(rng.randn(cout).astype("f4") * 0.1)
    mean = jnp.asarray(rng.randn(cout).astype("f4") * 0.1)
    var = jnp.asarray(rng.rand(cout).astype("f4") + 0.5)
    return x, w, gamma, beta, mean, var


def _cbr_grads(fn, x, w, gamma, beta, mean, var, **kw):
    def loss(x, w, g, b):
        y, _, _ = fn(x, w, g, b, mean, var, **kw)
        return (y * jnp.cos(y)).sum()

    return jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, gamma, beta)


def _cbr_forced(*a, **k):
    return cbr._fused(*a, interpret=True, force=True, **k)


# ResNet-50's pointwise triples (each bottleneck block's first conv) at
# 1/16 of their channels and a batch of 2: [N, Cin, HW, HW] -> Cout
_POINTWISE = dict(
    stage1=dict(cin=16, cout=4, h=56),     # [128, 256, 56, 56] -> 64
    stage3=dict(cin=64, cout=16, h=14),    # [128, 1024, 14, 14] -> 256
    stage4=dict(cin=128, cout=32, h=7),    # [128, 2048, 7, 7] -> 512
)


@pytest.mark.parametrize("case", [
    dict(_POINTWISE["stage1"], df="NCHW", training=True),
    dict(_POINTWISE["stage3"], df="NCHW", training=True),
    dict(_POINTWISE["stage4"], df="NHWC", training=False),
    dict(_POINTWISE["stage4"], df="NCHW", training=False),
], ids=["stage1-train", "stage3-train", "stage4-nhwc-eval", "stage4-eval"])
def test_conv_bn_relu_interpret_parity_fwd(case):
    """Pallas (interpret) == the unfused conv2d->batch_norm->relu op
    sequence, including the running-stat outputs, across the pointwise
    shapes / layout / mode."""
    df, training = case["df"], case["training"]
    x, w, gamma, beta, mean, var = _cbr_operands(
        cin=case["cin"], cout=case["cout"], h=case["h"], df=df)
    kw = dict(stride=1, padding=0, training=training, momentum=0.9,
              eps=1e-5, data_format=df)
    ref_y, ref_m, ref_v = cbr._reference(x, w, gamma, beta, mean, var,
                                         **kw)
    y, nm, nv = _cbr_forced(x, w, gamma, beta, mean, var, **kw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(nm), np.asarray(ref_m),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(nv), np.asarray(ref_v),
                               rtol=1e-3, atol=1e-4)


def test_conv_bn_relu_large_mean_variance_is_stable():
    """The training statistics use a CENTERED two-pass variance: a
    channel with mean ~100 and std ~0.1 (unnormalized-image regime)
    must match the reference batch_norm — the one-pass E[x^2]-mean^2
    form loses the entire variance to f32 cancellation here."""
    rng = np.random.RandomState(0)
    # mean ~100, std ~0.1 per channel: the cancellation regime
    x = jnp.asarray((rng.randn(4, 1, 12, 12) * 0.1 + 100.0).astype("f4"))
    w = jnp.asarray(np.full((8, 1, 1, 1), 1.0, "f4"))  # identity-ish conv
    gamma = jnp.asarray(np.ones(8, "f4"))
    beta = jnp.asarray(np.zeros(8, "f4"))
    mean = jnp.asarray(np.zeros(8, "f4"))
    var = jnp.asarray(np.ones(8, "f4"))
    kw = dict(stride=1, padding=0, training=True, momentum=0.9,
              eps=1e-5, data_format="NCHW")
    ref_y, _, ref_v = cbr._reference(x, w, gamma, beta, mean, var, **kw)
    y, _, nv = cbr._fused(x, w, gamma, beta, mean, var,
                          interpret=True, force=True, **kw)
    # the normalized output is O(1); cancellation would blow it up by
    # orders of magnitude, so a tight relative bound pins the fix
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y),
                               rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(np.asarray(nv), np.asarray(ref_v),
                               rtol=1e-2)


@pytest.mark.parametrize("training", [True, False])
def test_conv_bn_relu_interpret_parity_bwd(training):
    """Pallas backward (relu-gate recompute + folded BN backward, the
    matmul grads through jnp.dot) == autodiff of the unfused sequence,
    NHWC in training and NCHW in eval."""
    df = "NHWC" if training else "NCHW"
    ops = _cbr_operands(**_POINTWISE["stage3"], df=df, seed=1)
    kw = dict(stride=1, padding=0, training=training, momentum=0.9,
              eps=1e-5, data_format=df)
    ref = _cbr_grads(cbr._reference, *ops, **kw)
    fused = _cbr_grads(_cbr_forced, *ops, **kw)
    for name, a, b in zip(("dx", "dw", "dgamma", "dbeta"), ref, fused):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4, err_msg=name)


def test_conv_bn_relu_ragged_row_tiles_fwd_bwd():
    """Row counts that do NOT divide the 256-row tile (2*17*17=578 ->
    three tiles, ragged tail) and channels off the lane tile (24 -> 40):
    the reduction kernels must mask the out-of-bounds tail rows
    (undefined content) out of the channel sums — fwd stats AND bwd
    partials."""
    x, w, gamma, beta, _, _ = _cbr_operands(cin=24, cout=40, h=17, seed=3)
    mean, var = jnp.zeros(40), jnp.ones(40)
    kw = dict(stride=1, padding=0, training=True, momentum=0.9,
              eps=1e-5, data_format="NCHW")
    ref_y, _, ref_v = cbr._reference(x, w, gamma, beta, mean, var, **kw)
    y, _, nv = _cbr_forced(x, w, gamma, beta, mean, var, **kw)
    assert not np.isnan(np.asarray(y)).any()
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(nv), np.asarray(ref_v),
                               rtol=1e-3, atol=1e-4)
    ref = _cbr_grads(cbr._reference, x, w, gamma, beta, mean, var, **kw)
    fused = _cbr_grads(_cbr_forced, x, w, gamma, beta, mean, var, **kw)
    for name, a, b in zip(("dx", "dw", "dgamma", "dbeta"), ref, fused):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4, err_msg=name)


def _primitives(jaxpr, into=None):
    """Names of every primitive of a jaxpr, sub-jaxprs included."""
    into = set() if into is None else into
    for eqn in jaxpr.eqns:
        into.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, into)
    return into


@pytest.mark.parametrize("kh,stride,padding,pointwise", [
    (3, 1, 1, False),                        # a block's 3x3
    (7, 2, 3, False),                        # the stem
    (1, 2, 0, False),                        # strided 1x1
    (1, 1, [0, 1, 0, 1], False),             # padded 1x1
    (1, 1, "VALID", False),                  # a string form
    (1, 1, 0, True),                         # pointwise
    (1, (1, 1), [[0, 0], [0, 0]], True),     # the same, spelt out
], ids=["3x3", "stem", "strided", "padded", "valid", "pointwise",
        "pointwise-pairs"])
@pytest.mark.parametrize("force", [False, True])
def test_conv_bn_relu_dispatch_takes_pointwise_only(kh, stride, padding,
                                                    pointwise, force,
                                                    monkeypatch):
    """With the platform gate open, only a 1x1 stride-1 unpadded conv
    reaches the kernels, forced or not; every other conv IS the
    reference: XLA's convolution, no pallas_call, bit for bit."""
    monkeypatch.setattr(cbr, "can_emit_mosaic", lambda: True)
    ops = _cbr_operands(cin=16, cout=128, kh=kh, n=4, h=12)
    kw = dict(stride=stride, padding=padding, training=True, momentum=0.9,
              eps=1e-5, data_format="NCHW")
    assert cbr._supported(ops[0], ops[1], stride, padding, "NCHW", 1,
                          1) == pointwise

    def fused(*a):
        return cbr._fused(*a, interpret=True, force=force, **kw)

    prims = _primitives(jax.make_jaxpr(fused)(*ops).jaxpr)
    if pointwise:
        assert "pallas_call" in prims
        assert "conv_general_dilated" not in prims
    else:
        assert "conv_general_dilated" in prims
        assert "pallas_call" not in prims
        ref = jax.jit(lambda *a: cbr._reference(*a, **kw))(*ops)
        for a, b in zip(jax.jit(fused)(*ops), ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("training", [True, False])
def test_resnet_conv_bn_flag_is_bit_exact_off_tpu(training,
                                                  _flags_restored):
    """Flag on vs off through the REAL model: off-TPU the fused op's
    fallback IS the unfused op sequence, so outputs AND the updated
    running statistics are bit-exact."""
    from paddle_tpu.models import resnet18

    def run(flag_on):
        set_flags({"use_fused_conv_bn": flag_on})
        paddle.seed(0)
        m = resnet18(num_classes=10)
        m.train() if training else m.eval()
        x = to_tensor(np.random.RandomState(3)
                      .randn(2, 3, 32, 32).astype("f4"))
        out = m(x)
        return (np.asarray(out), np.asarray(m.bn1._mean),
                np.asarray(m.bn1._variance))

    fused = run(True)
    unfused = run(False)
    for a, b in zip(fused, unfused):
        np.testing.assert_allclose(a, b, rtol=0, atol=0)


def test_conv_bn_relu_trains_through_compiled_step(_flags_restored):
    """The fused triple traces into TrainStepFn (the resnet50 cell's
    configuration): identical loss trajectory flag on/off, and it
    actually trains."""
    from paddle_tpu.framework import jit as fjit
    from paddle_tpu.models import resnet18

    def run(flag_on):
        set_flags({"use_fused_conv_bn": flag_on})
        paddle.seed(1)
        m = resnet18(num_classes=4)
        opt = popt.Momentum(learning_rate=0.01, momentum=0.9,
                            parameters=m.parameters())
        step = fjit.train_step(
            m, opt,
            lambda mm, x, y: F.cross_entropy(mm(x), y).mean())
        rng = np.random.RandomState(0)
        X = rng.randn(4, 3, 32, 32).astype("f4")
        Y = rng.randint(0, 4, (4,)).astype("int64")
        return [float(np.asarray(step(X, Y)["loss"])) for _ in range(4)]

    fused = run(True)
    unfused = run(False)
    np.testing.assert_allclose(fused, unfused, rtol=1e-6)
    assert fused[-1] < fused[0]


def test_fused_helper_falls_back_for_inadmissible_convs(_flags_restored):
    """Grouped / biased / dilated convs never take the fused path —
    the helper composes the plain layers instead (identical output)."""
    set_flags({"use_fused_conv_bn": True})
    paddle.seed(5)
    conv = nn.Conv2D(4, 8, 3, padding=1, groups=2)  # grouped + biased
    bn = nn.BatchNorm2D(8)
    x = to_tensor(np.random.RandomState(7).randn(2, 4, 8, 8).astype("f4"))
    out = nn.fused_conv_bn_relu(conv, bn, x)
    ref = F.relu(bn(conv(x)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=0)


def test_conv_bn_relu_tensor_autograd_matches_unfused(_flags_restored):
    """Gradients through the op tape: fused helper == relu(bn(conv)),
    for conv weight and bn affine params."""
    def run(flag_on):
        set_flags({"use_fused_conv_bn": flag_on})
        paddle.seed(2)
        conv = nn.Conv2D(3, 8, 3, padding=1, bias_attr=False)
        bn = nn.BatchNorm2D(8)
        x = to_tensor(np.random.RandomState(11)
                      .randn(2, 3, 8, 8).astype("f4"),
                      stop_gradient=False)
        out = nn.fused_conv_bn_relu(conv, bn, x)
        out.sum().backward()
        return (np.asarray(out), np.asarray(x.grad),
                np.asarray(conv.weight.grad), np.asarray(bn.weight.grad),
                np.asarray(bn.bias.grad))

    fused = run(True)
    unfused = run(False)
    for name, a, b in zip(("out", "dx", "dw", "dgamma", "dbeta"),
                          fused, unfused):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                   err_msg=name)


# -- overlapped device prefetch ----------------------------------------------


def _slow_source(n, delay_s):
    for i in range(n):
        time.sleep(delay_s)
        yield np.full((4, 4), i, np.float32)


def _drive(n, source_delay, step_delay):
    from paddle_tpu.io.dataloader import _DevicePrefetcher

    pf = _DevicePrefetcher(_slow_source(n, source_delay), depth=2,
                           to_device=True)
    seen = []
    t0 = time.perf_counter()
    for batch in pf:
        time.sleep(step_delay)  # the consumer's "compute"
        seen.append(int(np.asarray(batch)[0, 0]))
    return seen, time.perf_counter() - t0


def test_prefetch_overlap_delivers_all_batches_in_order(_flags_restored):
    set_flags({"io_prefetch_overlap": True})
    seen, _ = _drive(6, 0.0, 0.0)
    assert seen == list(range(6))
    set_flags({"io_prefetch_overlap": False})
    seen, _ = _drive(6, 0.0, 0.0)
    assert seen == list(range(6))


@pytest.mark.slow
def test_prefetch_overlap_hides_source_latency(_flags_restored):
    """With overlap the producer works during the consumer's step, so
    the loop approaches max(source, step) per batch; the synchronous
    path pays source + step. Generous margins for a loaded box."""
    n, src, step = 6, 0.03, 0.03
    set_flags({"io_prefetch_overlap": False})
    seen_s, sync_wall = _drive(n, src, step)
    set_flags({"io_prefetch_overlap": True})
    seen_o, overlap_wall = _drive(n, src, step)
    assert seen_s == seen_o == list(range(n))
    assert overlap_wall < sync_wall * 0.85, (overlap_wall, sync_wall)


def test_prefetch_propagates_source_errors(_flags_restored):
    from paddle_tpu.io.dataloader import _DevicePrefetcher

    def bad():
        yield np.zeros((2, 2), np.float32)
        raise ValueError("parse failure")

    set_flags({"io_prefetch_overlap": True})
    pf = _DevicePrefetcher(bad(), depth=2, to_device=True)
    next(pf)
    with pytest.raises(ValueError, match="parse failure"):
        next(pf)


def test_prefetch_abandoned_iterator_does_not_leak_thread(_flags_restored):
    """Dropping the iterator mid-epoch must let the fill thread exit:
    the thread closes only over (it, q, stop) — never the prefetcher —
    so GC can collect it and the finalizer stops the loop."""
    import gc

    from paddle_tpu.io.dataloader import _DevicePrefetcher

    set_flags({"io_prefetch_overlap": True})
    pf = _DevicePrefetcher(_slow_source(100, 0.0), depth=2, to_device=True)
    next(pf)
    del pf
    gc.collect()
    deadline = time.perf_counter() + 2.0
    while time.perf_counter() < deadline:
        alive = [t for t in threading.enumerate()
                 if t.name == "ptpu-h2d-prefetch" and t.is_alive()]
        if not alive:
            break
        time.sleep(0.05)
    assert not alive, "abandoned prefetch thread still running"


def test_prefetch_exhaustion_and_error_are_terminal(_flags_restored):
    """Iterator protocol on the overlap path: once exhausted (or after
    the source's error has been raised) every later next() raises
    StopIteration immediately instead of blocking on an empty queue."""
    from paddle_tpu.io.dataloader import _DevicePrefetcher

    set_flags({"io_prefetch_overlap": True})
    pf = _DevicePrefetcher(_slow_source(1, 0.0), depth=2, to_device=True)
    next(pf)
    for _ in range(3):
        with pytest.raises(StopIteration):
            next(pf)

    def bad():
        yield np.zeros((2, 2), np.float32)
        raise ValueError("boom")

    pf = _DevicePrefetcher(bad(), depth=2, to_device=True)
    next(pf)
    with pytest.raises(ValueError, match="boom"):
        next(pf)
    for _ in range(3):
        with pytest.raises(StopIteration):
            next(pf)


def test_prefetch_close_then_iterate_terminates(_flags_restored):
    """close() mid-consumption must end iteration, not deadlock: the
    fill thread refuses every post-stop put (including its DONE tail),
    so the consumer's queue wait has to treat stop+empty as terminal.
    Batches already enqueued still drain first."""
    from paddle_tpu.io.dataloader import _DevicePrefetcher

    set_flags({"io_prefetch_overlap": True})
    pf = _DevicePrefetcher(_slow_source(50, 0.0), depth=2, to_device=True)
    next(pf)
    pf.close()
    got, deadline = 0, time.perf_counter() + 5.0
    try:
        while time.perf_counter() < deadline:
            next(pf)
            got += 1
    except StopIteration:
        pass
    else:
        pytest.fail("close()d prefetcher never raised StopIteration")
    assert got <= 3  # at most the buffered depth drains
    with pytest.raises(StopIteration):
        next(pf)  # and it stays terminal


def test_prefetch_accounts_input_wait(_flags_restored):
    from paddle_tpu.monitor import registry as _reg

    set_flags({"io_prefetch_overlap": True})
    g = _reg.gauge("io/input_wait_ms")
    before = g.value
    _drive(3, 0.005, 0.0)
    assert g.value >= before  # the pop wait feeds the monitor's ratio

"""Every default-on pallas kernel must lower for the TPU platform.

Interpret-mode tests never apply the Pallas->Mosaic lowering rules (block
shapes, memory spaces), so a kernel can pass them and still be refused at
trace time on the chip. Lowering with ``lowering_platforms=("tpu",)``
runs those rules on a CPU-only host: no device is needed and nothing is
executed. Shapes are chip_smoke.py's BERT-base / ResNet-50 ones plus a
ragged tail each. The Mosaic compiler proper (VMEM limits, op support)
only runs on the chip — see .claude/skills/verify/SKILL.md for the AOT
recipe that reaches it from the sandbox.
"""
import importlib
import re

import jax
import jax.numpy as jnp
import pytest

# ops/pallas/__init__.py re-exports functions under the module names
lnr = importlib.import_module("paddle_tpu.ops.pallas.layernorm_residual")
cbr = importlib.import_module("paddle_tpu.ops.pallas.conv_bn_relu")
fla = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
opu = importlib.import_module("paddle_tpu.ops.pallas.optimizer_update")
mld = importlib.import_module("paddle_tpu.ops.pallas.mla_decode")
gex = importlib.import_module("paddle_tpu.ops.pallas.grouped_experts")

F32, BF16 = jnp.float32, jnp.bfloat16


def _sds(shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _lower_for_tpu(fn, *args, mosaic=True):
    """Trace ``fn`` on abstract operands and lower it for TPU; returns
    the module text. The suite runs with x64 on and the chip with it
    off, so tracing happens with x64 off (Mosaic has no float64)."""
    with jax.enable_x64(False):
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    assert ("tpu_custom_call" in text) == mosaic
    return text


@pytest.mark.parametrize("rows,h,xdt,rdt", [
    (128 * 128, 768, BF16, BF16),   # BERT-base phase 1 / 2: 16384 rows
    (128 * 128, 768, BF16, F32),    # first layer: f32 embeddings residual
    (1000, 768, F32, F32),          # ragged last row tile
])
def test_layernorm_residual_fwd_bwd(rows, h, xdt, rdt):
    x, r = _sds((rows, h), xdt), _sds((rows, h), rdt)
    w, col = _sds((h,)), _sds((rows, 1))
    text = _lower_for_tpu(
        lambda x, r, w, b: lnr._pallas_fwd(x, r, w, b, 1e-5), x, r, w, w)
    assert '"layernorm_residual_fwd"' in text
    text = _lower_for_tpu(lnr._pallas_bwd, x, r, w, col, col, x)
    assert '"layernorm_residual_bwd"' in text


@pytest.mark.parametrize("b,h,l,d,rate,names", [
    # BERT-base phase 2: key-padding bias + attention dropout
    (32, 12, 512, 64, 0.1, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")),
    # a 128-multiple that is no 256-multiple: blocks shrink to 128
    (2, 12, 384, 64, 0.0, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")),
    # whole sequence in one tile: the all-heads-per-program kernels
    (8, 12, 128, 64, 0.1, ("flash_fwd_small", "flash_bwd_small")),
])
def test_flash_attention_fwd_bwd(b, h, l, d, rate, names):
    q = _sds((b, h, l, d), BF16)
    bias = _sds((b, 1, 1, l), BF16)
    seed = _sds((), jnp.int32)
    scale = d ** -0.5

    def fwd_bwd(q, k, v, bias, seed, g):
        out, lse = fla._pallas_fwd(q, k, v, bias, seed, False, scale, rate)
        return fla._pallas_bwd(q, k, v, bias, seed, False, scale, rate,
                               out, lse, g)

    text = _lower_for_tpu(fwd_bwd, q, q, q, bias, seed, q)
    for name in names:
        assert f'"{name}"' in text


def test_flash_attention_causal():
    q = _sds((2, 12, 1024, 64), BF16)
    text = _lower_for_tpu(
        lambda q, k, v: fla._pallas_fwd(q, k, v, None, jnp.int32(0), True,
                                        0.125, 0.0), q, q, q)
    assert '"flash_fwd"' in text


_CONV_KERNELS = ("conv_mm_stats", "conv_centered_sumsq", "conv_bn_relu",
                 "conv_bn_bwd_partials", "conv_bn_bwd_dco")


def _kernel_names(text):
    return sorted(re.findall(r'kernel_name = "(\w+)"', text))


# ResNet-50's pointwise triples at batch 128: each bottleneck block's
# first conv is all the fused path takes
@pytest.mark.parametrize("n,cin,hw,cout,df", [
    (128, 256, 56, 64, "NCHW"),     # stage 1: Cout 64 pads to 128 lanes
    (128, 1024, 14, 256, "NCHW"),   # stage 3
    (128, 2048, 7, 512, "NCHW"),    # stage 4: 6272 rows
    (128, 512, 28, 128, "NHWC"),    # stage 2, channels-last
    (2, 24, 7, 40, "NCHW"),         # ragged: 98 rows, channels off the tile
])
def test_conv_bn_relu_train_fwd_bwd_and_eval(n, cin, hw, cout, df):
    x = _sds((n, cin, hw, hw) if df == "NCHW" else (n, hw, hw, cin), BF16)
    w = _sds((cout, cin, 1, 1), BF16)
    vec = _sds((cout,))
    kw = dict(stride=1, padding=0, momentum=0.9, eps=1e-5, data_format=df,
              force=True)

    def train_loss(x, w, gamma, beta, mean, var):
        y, _, _ = cbr._fused(x, w, gamma, beta, mean, var, training=True,
                             **kw)
        return y.astype(F32).sum()

    text = _lower_for_tpu(
        jax.value_and_grad(train_loss, argnums=(0, 1, 2, 3)),
        x, w, vec, vec, vec, vec)
    assert _kernel_names(text) == sorted(_CONV_KERNELS)
    assert "stablehlo.convolution" not in text
    text = _lower_for_tpu(
        lambda *a: cbr._fused(*a, training=False, **kw)[0],
        x, w, vec, vec, vec, vec)
    assert _kernel_names(text) == ["conv_mm_affine_relu"]


@pytest.mark.parametrize("inplanes,xla_convs", [
    (256, 2),   # the 3x3 and the last 1x1
    (64, 3),    # a stage's first block: the projection too
])
def test_bottleneck_block_step_holds_one_fused_triple(inplanes, xla_convs,
                                                      monkeypatch):
    """A whole bottleneck block under AMP, lowered for TPU with the
    platform gate open: its one pointwise triple (conv1) is the five
    kernels, once each; the 3x3, the last 1x1 and the projection are
    XLA convolutions, as they are with the flag off."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import amp
    from paddle_tpu.framework import jit as fjit
    from paddle_tpu.models.resnet import BottleneckBlock

    monkeypatch.setattr(cbr, "can_emit_mosaic", lambda: True)
    paddle.seed(0)
    downsample = None if inplanes == 256 else nn.Sequential(
        nn.Conv2D(inplanes, 256, 1, bias_attr=False), nn.BatchNorm2D(256))
    block = BottleneckBlock(inplanes, 64, downsample=downsample)
    block.train()
    state = jax.tree_util.tree_map(lambda a: _sds(a.shape, a.dtype),
                                   fjit.capture_state(block))
    params = state.pop("params")

    def loss(params, rest, x):
        with amp.auto_cast():
            y, _ = fjit.functional_call(block, dict(rest, params=params), x)
        return y.astype(F32).sum()

    args = (params, state, _sds((128, inplanes, 56, 56)))
    fwd = _lower_for_tpu(loss, *args)
    assert _kernel_names(fwd) == sorted(_CONV_KERNELS[:3])
    assert fwd.count("stablehlo.convolution") == xla_convs
    assert _kernel_names(_lower_for_tpu(jax.value_and_grad(loss), *args)) \
        == sorted(_CONV_KERNELS)


@pytest.mark.parametrize("shape", [
    (1000, 2048),     # ResNet-50 classifier weight
    (64, 3, 7, 7),    # stem weight: 9408 elements, padded to whole tiles
])
def test_momentum_update(shape):
    p = _sds(shape)
    text = _lower_for_tpu(
        lambda p, g, v, lr: opu._pallas_update(p, g, v, lr, 0.9, 1e-4,
                                               False),
        p, p, p, _sds((), F32))
    assert '"momentum_update"' in text


@pytest.mark.parametrize("shape,kernel", [
    ((2048,), True),             # a batch-norm leaf
    ((1000, 2048), True),        # the classifier, either way round
    ((2048, 1000), True),
    ((2048, 512, 1, 1), True),   # a pointwise weight: [2048, 512]
    ((512, 512, 3, 3), False),   # 3 x 3 in a tile: 57 x when flattened
    ((64, 64, 3, 3), False),
    ((64, 3, 7, 7), False),      # the stem: 21 x
])
def test_fused_momentum_update_takes_dense_views_only(shape, kernel,
                                                      monkeypatch):
    """With the platform gate open the public function emits the kernel
    only for an operand whose [rows, 128] view costs no padded
    re-tiling; a weight with a spatial extent lowers to plain
    elementwise ops on the 4-D array (PERF.md, PR 35)."""
    monkeypatch.setattr(opu, "can_emit_mosaic", lambda: True)
    p = _sds(shape)
    text = _lower_for_tpu(
        lambda p, g, v, lr: opu.fused_momentum_update(
            p, g, v, lr, momentum=0.9, weight_decay=1e-4),
        p, p, p, _sds((), F32), mosaic=kernel)
    assert _kernel_names(text) == (["momentum_update"] if kernel else [])
    if not kernel:
        assert "stablehlo.reshape" not in text


def test_momentum_step_holds_the_kernel_for_dense_leaves_only(monkeypatch):
    """A Momentum train step over conv 3x3 -> bn -> conv 1x1 -> linear,
    lowered for TPU with the gate open: one kernel for each of the five
    leaves whose flat view is dense (by its [rows, 128] operand), none
    for the 3x3 weight."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as popt
    from paddle_tpu.framework import jit as fjit

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.conv3 = nn.Conv2D(16, 128, 3, padding=1, bias_attr=False)
            self.bn = nn.BatchNorm2D(128)
            self.conv1 = nn.Conv2D(128, 64, 1, bias_attr=False)
            self.fc = nn.Linear(64, 256)

        def forward(self, x):
            y = self.conv1(F.relu(self.bn(self.conv3(x))))
            return self.fc(y.mean(axis=[2, 3]))

    monkeypatch.setattr(opu, "can_emit_mosaic", lambda: True)
    paddle.seed(0)
    net = Net()
    step = fjit.train_step(
        net, popt.Momentum(learning_rate=0.1, momentum=0.9,
                           parameters=net.parameters()),
        lambda m, x, y: F.mse_loss(m(x), y).mean())
    state = jax.tree_util.tree_map(lambda a: _sds(a.shape, a.dtype),
                                   step.state)
    text = _lower_for_tpu(step.pure, state,
                          (_sds((2, 16, 8, 8)), _sds((2, 256))),
                          _sds(()), _sds(step._rng.shape, step._rng.dtype))
    assert _kernel_names(text) == ["momentum_update"] * 5
    # bn gain and bias and the linear bias pad to one (8, 128) tile;
    # conv1 is 64 x 128, the linear weight 64 x 256
    rows = re.findall(r'kernel_name = "momentum_update".*?'
                      r"-> \(tensor<(\d+)x128xf32>", text)
    assert sorted(map(int, rows)) == [8, 8, 8, 64, 128]


@pytest.mark.parametrize("slots,ring,dtype", [
    (32, 8192, BF16),   # longcat-flash-omni.longreply-overload's rings
    (1, 1024, F32),     # one slot, a float32 ring of two blocks
])
def test_mla_decode_through_the_absorbed_step(slots, ring, dtype,
                                              monkeypatch):
    """`CachedLatentAttention.absorbed` at the served widths (64 heads,
    a 512 + 64 row) with the platform gate open: one `mla_decode` call
    whose ring operand is the transposed view, lengths as the scalar
    prefetch. (Off the TPU the kernel would run interpreted: the
    module's own platform test is opened too.)"""
    from paddle_tpu.nn import mla

    monkeypatch.setattr(mla, "can_emit_mosaic", lambda: True)
    monkeypatch.setattr(mld, "on_tpu_platform", lambda: True)
    m = mla.CachedLatentAttention(
        hidden_size=256, num_heads=64, q_rank=64, kv_rank=512, nope_dim=128,
        rope_dim=64, v_dim=128, key_chunk=4096, initializer_range=None,
        dtype=dtype)
    text = _lower_for_tpu(
        lambda qn, qr, c, mask, pos: m.absorbed(qn, qr, c, mask, pos),
        _sds((slots, 1, 64, 128), dtype), _sds((slots, 1, 64, 64), dtype),
        _sds((slots, ring, 576), dtype), _sds((slots, 1, 1, ring)),
        _sds((slots,), jnp.int32))
    assert _kernel_names(text) == ["mla_decode"]
    operands = re.search(r'kernel_name = "mla_decode".*?: \((.*?)\) ->',
                         text).group(1)
    el = "bf16" if dtype == BF16 else "f32"
    assert operands.split(", ") == [
        f"tensor<{slots}xi32>", f"tensor<{slots}x64x576x{el}>",
        f"tensor<{slots}x576x{ring}x{el}>"]
    assert mld.key_block(ring) == 512


@pytest.mark.parametrize("rows,tile", [(64 * 22, 16), (1024 * 22, 128)])
def test_grouped_relu2_at_the_served_shapes(rows, tile, monkeypatch):
    """The non-gated experts' kernel at the served cut's widths (128 held
    experts, latent 1,024, hidden width 2,688, bfloat16) for a decode
    step's 64 x 22 sorted pairs and for a 1,024-token chunk's: ONE call
    named `ragged-dot-none-relu2` (the name the benchmark's readers find
    the grouped products by); its operands are the five scalar-prefetch
    arrays (the work items' groups and tiles, the groups' first rows and
    ends, the number of items), the sorted rows and the two stacks of
    weights, a whole expert a block."""
    monkeypatch.setattr(gex, "on_tpu_platform", lambda: True)
    shapes = (rows, 1024), (128, 1024, 2688), (128, 2688, 1024)
    assert gex.grouped_experts_supported(*shapes, "bfloat16")
    assert gex.row_tile(rows, 128, BF16) == tile
    assert gex._hidden_block(1024, 2688, BF16) == 2688
    text = _lower_for_tpu(gex.grouped_experts,
                          *(_sds(s, BF16) for s in shapes),
                          _sds((128,), jnp.int32))
    assert re.findall(r'kernel_name = "([\w.-]+)"', text) \
        == ["ragged-dot-none-relu2"]
    operands = re.search(
        r'kernel_name = "ragged-dot-none-relu2".*?: \((.*?)\) ->',
        text).group(1)
    items = rows // tile + 128 - 1
    assert operands.split(", ") == [
        f"tensor<{items}xi32>", f"tensor<{items}xi32>", "tensor<128xi32>",
        "tensor<128xi32>", "tensor<1xi32>", f"tensor<{rows}x1024xbf16>",
        "tensor<128x1024x2688xbf16>", "tensor<128x2688x1024xbf16>"]


@pytest.mark.parametrize("cell,n,w,f,rows,tile,block", [
    ("solar-open2-250b decode", 40, 4096, 1280, 32 * 8, 16, 1280),
    ("solar-open2-250b 2,048 prompt", 40, 4096, 1280, 2048 * 8, 128, 1280),
    ("k-exaone-236b decode", 16, 6144, 2048, 32 * 8, 16, 1024),
    ("k-exaone-236b 2,048 chunk", 16, 6144, 2048, 2048 * 8, 128, 1024),
    ("longcat-flash-omni decode", 16, 6144, 2048, 32 * 12, 32, 1024),
    ("longcat-flash-omni 1,024 chunk", 16, 6144, 2048, 1024 * 12, 128,
     1024)])
def test_grouped_swiglu_at_the_served_shapes(cell, n, w, f, rows, tile,
                                             block, monkeypatch):
    """The gated experts' kernel at the three served cuts' widths
    (bfloat16), a decode step's sorted pairs and a prompt's: ONE call
    named `ragged-dot-none-swiglu` (the prefix is what the benchmark's
    readers find the grouped products by), the sorted rows and the three
    stacks of weights behind the five scalar-prefetch arrays, gate
    first; a whole expert a block where 31.5 MB fit VMEM twice, half of
    one where 75.5 MB do not."""
    monkeypatch.setattr(gex, "on_tpu_platform", lambda: True)
    up, down = (n, w, f), (n, f, w)
    assert gex.grouped_experts_supported((rows, w), up, down, "bfloat16", up)
    assert gex.row_tile(rows, n, BF16) == tile
    assert gex._hidden_block(w, f, BF16, 3) == block
    text = _lower_for_tpu(
        lambda xs, g, u, d, s: gex.grouped_experts(xs, u, d, s, g),
        _sds((rows, w), BF16), _sds(up, BF16), _sds(up, BF16),
        _sds(down, BF16), _sds((n,), jnp.int32))
    assert re.findall(r'kernel_name = "([\w.-]+)"', text) \
        == ["ragged-dot-none-swiglu"]
    operands = re.search(
        r'kernel_name = "ragged-dot-none-swiglu".*?: \((.*?)\) ->',
        text).group(1)
    items = rows // tile + n - 1
    assert operands.split(", ") == [
        f"tensor<{items}xi32>", f"tensor<{items}xi32>", f"tensor<{n}xi32>",
        f"tensor<{n}xi32>", "tensor<1xi32>", f"tensor<{rows}x{w}xbf16>",
        f"tensor<{n}x{w}x{f}xbf16>", f"tensor<{n}x{w}x{f}xbf16>",
        f"tensor<{n}x{f}x{w}xbf16>"]


@pytest.mark.parametrize("gated", [False, True])
def test_the_expert_layers_of_a_program_share_one_lowered_kernel(
        gated, monkeypatch):
    """Four expert layers of one program at `solar-open2-250b`'s decode
    shapes: the kernel's call is a `jax.jit` of its own, so the module
    holds ONE function with ONE Mosaic call, called four times (the
    host traces and lowers the kernel once a program, not once a
    layer)."""
    monkeypatch.setattr(gex, "on_tpu_platform", lambda: True)
    n, w, f, rows = 40, 4096, 1280, 32 * 8
    stack = (4, n, w, f)

    def layers(xs, gate, up, down, sizes):
        for i in range(4):
            xs, _ = gex.grouped_experts(
                xs, up[i], down[i], sizes, gate[i] if gated else None)
        return xs

    text = _lower_for_tpu(
        layers, _sds((rows, w), BF16), _sds(stack, BF16), _sds(stack, BF16),
        _sds((4, n, f, w), BF16), _sds((n,), jnp.int32))
    name = "ragged-dot-none-" + ("swiglu" if gated else "relu2")
    assert re.findall(r'kernel_name = "([\w.-]+)"', text) == [name]
    assert len(re.findall(r"func\.func private @_call", text)) == 1
    assert len(re.findall(r"call @_call\(", text)) == 4


def test_gate_closes_under_a_multi_device_mesh(monkeypatch):
    """jax refuses to partition a Mosaic call automatically, so under a
    mesh of more than one device every kernel hands its op to XLA: a
    sharded trainer with default flags must trace (found by AOT-compiling
    the dp=2 x tp=2 BERT step; it raised NotImplementedError)."""
    from paddle_tpu import parallel
    from paddle_tpu.ops.pallas import _platform

    monkeypatch.setattr(_platform, "on_tpu_platform", lambda: True)
    x, w = jnp.zeros((256, 128), BF16), jnp.zeros((128,))
    assert _platform.can_emit_mosaic() and lnr._supported(x, x, w, w)
    from paddle_tpu.parallel import moe

    experts = [moe.RoutedExperts(32, 128, 8, 2, activation=activation,
                                 latent_size=128, dtype=BF16)
               for activation in ("relu2", "swiglu")]
    rows = jnp.zeros((64, 128), BF16)
    assert all(e.takes_kernel(rows) for e in experts)
    with parallel.mesh_scope(parallel.create_mesh(dp=2, tp=2)):
        assert not _platform.can_emit_mosaic()
        assert not lnr._supported(x, x, w, w)
        assert not any(e.takes_kernel(rows) for e in experts)
    with parallel.mesh_scope(parallel.create_mesh(dp=1)):
        assert _platform.can_emit_mosaic()

#!/usr/bin/env python
"""Does today's code start on the chip? One process, the normal entry
points, full width, a few steps and a few requests.

Legs, run in sequence by one process that holds the chip throughout:

- ``kernels``  each default-on pallas kernel against its in-file
  reference at the shapes the trainers below use, once.
- ``bert``     BERT-base pretraining through ``framework.jit.train_step``
  (AdamW, bf16 AMP): a few steps at batch 128 x seq 128 (XLA attention +
  fused layernorm) and at batch 32 x seq 512 (flash attention with a bias
  and dropout).
- ``resnet``   ResNet-50 through ``train_step`` (Momentum, bf16 AMP) at
  batch 128 x 224 x 224: the fused conv+bn+relu and momentum kernels.
- ``gpt``      a GPT-2-width ``GenerationServer`` answering real HTTP
  ``POST /generate`` requests, compared with the same engine offline.
- ``hybrid``   the hybrid MoE decoder (linear attention beside grouped-
  query attention, 40 of 320 routed experts held, bfloat16) at its
  published widths and two layers through the same server: a K/V ring
  and a recurrent state in one cache.
- ``window``   the window/full attention decoder (sliding window 128
  with rotary positions beside full attention, a dense layer 0, 16 of
  128 routed experts held, bfloat16) at its published widths and two
  layers through the same server: rings of two lengths in one cache,
  prompts and outputs that cross the window's wrap.
- ``latent``   one latent attention (MLA) at longcat-flash-omni's
  published widths: the absorbed decode step against the expanded
  attention on the same weights and the same latent ring.
- ``bert4``    the BERT trainer's first phase on a dp=2 x tp=2 mesh,
  when the process sees four or more devices.

Every leg is a function of a size preset, so tests/test_chip_smoke.py
rehearses the same functions at ``TINY`` on the CPU. ``main`` has no CPU
mode: without a TPU it says so and exits 1. It sets no JAX_PLATFORMS,
forces no interpret mode, starts no process and catches no leg's failure.
The timings it prints are smoke timings of a handful of steps, not
benchmark numbers.

Last line of stdout on success:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
"""
from __future__ import annotations

import collections
import gc
import importlib
import json
import re
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from urllib.request import Request, urlopen

import numpy as np

CHIP = {
    "kernels": {
        "layernorm": (128 * 128, 768),
        "flash": (32, 12, 512, 64),
        # (n, cin, hw, cout, k, stride, pad): the pointwise triples of
        # stages 1, 3 and 4 (all the kernels take) and, as the one that
        # must go to XLA's convolution, a 3x3
        "conv": ((128, 256, 56, 64, 1, 1, 0), (128, 1024, 14, 256, 1, 1, 0),
                 (128, 2048, 7, 512, 1, 1, 0), (128, 256, 14, 256, 3, 1, 1)),
        # a matrix (the kernel) and a weight with a spatial extent, which
        # fused_momentum_update must leave to XLA (PERF.md, PR 35)
        "momentum": (1000, 2048),
        "momentum_spatial": (256, 256, 3, 3),
    },
    # AdamW's first steps overshoot on a fixed batch (11.18, 14.17, 12.76,
    # 11.55 on the chip); by the tenth the loss is under where it began
    "bert": {"config": {}, "steps": 10,
             "phases": ((128, 128, 20), (32, 512, 80))},
    "resnet": {"make": "resnet50", "classes": 1000, "batch": 128,
               "size": 224, "steps": 6},
    "gpt": {"config": {}, "engine": {}, "prompt_lens": (5, 20, 48, 100),
            "max_new_tokens": 16},
    # published widths, two layers (one of each kind), the share of one
    # chip in eight: 40 of 320 experts, an eighth of the vocabulary
    "hybrid": {"config": dict(num_hidden_layers=2, gqa_layers=(0,),
                              experts_held=(0, 40), vocab_held=24576,
                              dtype="bfloat16"),
               "engine": dict(slots=4, cache_len=512,
                              prefill_buckets=(64, 128),
                              kv_cache_dtype="bfloat16"),
               "prompt_lens": (5, 20, 48, 100), "max_new_tokens": 16},
    # published widths, layer 0 (sliding, dense) and a full sparse layer,
    # the share of one chip in eight; 120 + 16 tokens wrap a ring of 128
    # while decoding, 200 wrapped it in the prefill
    "window": {"config": dict(
        num_hidden_layers=2,
        layer_types=("sliding_attention", "full_attention"),
        experts_held=(0, 16), vocab_held=19200, dtype="bfloat16"),
        "engine": dict(slots=4, cache_len=512,
                       prefill_buckets=(64, 128, 256),
                       kv_cache_dtype="bfloat16"),
        "prompt_lens": (5, 120, 200, 48), "max_new_tokens": 16},
    # one latent attention at the published widths of longcat-flash-omni:
    # 600 rows prefilled (expanded), then steps over a ring of 1,024 rows
    # in two key chunks (absorbed)
    "latent": {"layer": dict(
        hidden_size=6144, num_heads=64, q_rank=1536, kv_rank=512,
        nope_dim=128, rope_dim=64, v_dim=128, rope_theta=1e7, scale_q=True,
        scale_kv=True, prefill_block=256, key_chunk=512, dtype="bfloat16"),
        "slots": 4, "ring": 1024, "rows": 600, "steps": 3, "tol": 3e-2},
}

TINY = {
    "kernels": {
        "layernorm": (200, 128),
        "flash": (2, 2, 128, 64),
        "conv": ((2, 24, 16, 8, 1, 1, 0), (2, 3, 16, 8, 3, 2, 1)),
        "momentum": (40, 128),
        "momentum_spatial": (8, 4, 3, 3),
    },
    "bert": {"config": dict(vocab_size=1024, hidden_size=128,
                            num_hidden_layers=1, num_attention_heads=4,
                            intermediate_size=512),
             "steps": 3, "phases": ((4, 128, 8), (2, 512, 16))},
    "resnet": {"make": "resnet18", "classes": 10, "batch": 2, "size": 16,
               "steps": 4},
    "gpt": {"config": dict(vocab_size=211, hidden_size=64,
                           num_hidden_layers=2, num_attention_heads=4,
                           intermediate_size=128,
                           max_position_embeddings=128),
            "engine": dict(slots=2, cache_len=32, prefill_buckets=(4, 8)),
            "prompt_lens": (1, 3, 8, 5), "max_new_tokens": 6},
    "hybrid": {"config": dict(
        vocab_size=97, vocab_held=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        gqa_layers=(0,), linear_attn_config={
            "short_conv_kernel_size": 4, "head_dim": 8, "num_heads": 4},
        moe_intermediate_size=16, n_routed_experts=16,
        num_experts_per_tok=4, experts_held=(4, 8), initializer_range=0.2),
        "engine": dict(slots=2, cache_len=32, prefill_buckets=(4, 8),
                       kv_cache_dtype="float32"),
        "prompt_lens": (1, 3, 8, 5), "max_new_tokens": 6},
    "window": {"config": dict(
        vocab_size=97, vocab_held=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        layer_types=("sliding_attention", "full_attention"),
        sliding_window=8, intermediate_size=48, moe_intermediate_size=16,
        num_experts=16, num_experts_per_tok=2, experts_held=(4, 4),
        initializer_range=0.2),
        "engine": dict(slots=2, cache_len=32, prefill_buckets=(4, 8, 16),
                       kv_cache_dtype="float32"),
        "prompt_lens": (1, 6, 13, 5), "max_new_tokens": 6},
    "latent": {"layer": dict(
        hidden_size=32, num_heads=4, q_rank=16, kv_rank=12, nope_dim=8,
        rope_dim=4, v_dim=6, rope_theta=1e4, scale_q=True, scale_kv=True,
        prefill_block=4, key_chunk=8, dtype="float32"),
        "slots": 2, "ring": 16, "rows": 11, "steps": 3, "tol": 1e-4},
}

# Mosaic calls a compiled step must contain on one chip. Under a mesh the
# kernels hand the op to XLA (ops/pallas/_platform.py), so there: none.
BERT_XLA_ATTN_KERNELS = {"layernorm_residual_fwd", "layernorm_residual_bwd"}
BERT_FLASH_KERNELS = BERT_XLA_ATTN_KERNELS | {
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
RESNET_KERNELS = {"conv_mm_stats", "conv_centered_sumsq", "conv_bn_relu",
                  "conv_bn_bwd_partials", "conv_bn_bwd_dco",
                  "momentum_update"}


def _require(ok, message):
    """The script's one way to fail a leg (``assert`` goes with -O)."""
    if not ok:
        raise AssertionError(message)


def _on_tpu() -> bool:
    import jax

    return jax.devices()[0].platform == "tpu"


def _peak_bytes():
    """Process-lifetime peaks of device 0 (None where the backend keeps
    no counters, i.e. the CPU). On the v5e ``in_use`` counts live arrays
    only; a running program's scratch shows under ``reserved``."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return stats and {"in_use": stats["peak_bytes_in_use"],
                      "reserved": stats["peak_bytes_reserved"]}


def _mosaic_calls(compiled_text: str) -> collections.Counter:
    """Names of the Mosaic custom calls in a compiled module's text. The
    kernel's ``name=`` is the scope right above ``pallas_call`` in the
    op_name, wrapped by whatever transformed it: ``jvp(conv_mm_stats)``,
    ``transpose(jvp(conv_bn_bwd_dco))``."""
    names = collections.Counter()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r'op_name="[^"]*?([^/"]+)/pallas_call', line)
            names[re.findall(r"\w+", m.group(1))[-1] if m else "?"] += 1
    return names


def _check_kernels(found: collections.Counter, want: set, what: str):
    """On the chip the compiled step holds every kernel in ``want``; off
    it (the rehearsal) it holds none. Either way a kernel that gave way
    to its reference where it should have run, or ran where it should
    not, fails the leg."""
    want = want if _on_tpu() else set()
    _require(set(found) == want,
             f"{what}: Mosaic calls in the compiled step are "
             f"{sorted(found)}, expected {sorted(want)}")


def _newest_executable(step):
    """(Mosaic calls, cost_analysis FLOPs) of the executable a TrainStepFn
    compiled last: what XLA built, not what a second trace would give."""
    entry = list(step._exec.entries().values())[-1]
    return _mosaic_calls(entry.aot.as_text()), entry.record.flops


def _run_steps(step, batch, n):
    """First call (trace + compile + one step), then n - 1 more on the
    same batch. Returns (losses, compile_s, run_s): compile_s is the
    first call less one steady step."""
    t0 = time.perf_counter()
    losses = [float(np.asarray(step(*batch)["loss"]))]
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n - 1):
        losses.append(float(np.asarray(step(*batch)["loss"])))
    run = time.perf_counter() - t0
    _require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _require(losses[-1] < losses[0],
             f"loss did not fall on a fixed batch: {losses}")
    return losses, max(first - run / max(n - 1, 1), 0.0), run


def _rel_err(got, ref) -> float:
    """Relative L2 error ||got - ref|| / ||ref||, in f64; non-finite is an
    error. Not the max norm: on the chip a conv's dx differs from XLA's
    by 0.007-0.12 of max|dx| from one input sample to the next (a few
    relu gates flip under bf16) while its L2 error stays at 0.003."""
    got = np.asarray(got, np.float64).ravel()
    ref = np.asarray(ref, np.float64).ravel()
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        return float("inf")
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


# -- leg: kernels -------------------------------------------------------------


def leg_kernels(preset) -> dict:
    """Each default-on kernel through its public function against the
    module's own reference, forward and gradients. On the chip the public
    function must take the pallas path (its predicate says so, and there
    is no handler between predicate and call); off it both sides are the
    reference and the comparison is exact."""
    import jax
    import jax.numpy as jnp

    lnr = importlib.import_module("paddle_tpu.ops.pallas.layernorm_residual")
    fla = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    cbr = importlib.import_module("paddle_tpu.ops.pallas.conv_bn_relu")
    opu = importlib.import_module("paddle_tpu.ops.pallas.optimizer_update")
    p = preset["kernels"]
    tpu = _on_tpu()
    bf16, f32 = jnp.bfloat16, jnp.float32
    rng = np.random.RandomState(0)
    errs = {}

    def normal(shape, dtype=f32, scale=1.0):
        # drawn and rounded on the host: an eager jax.random call compiles
        # one program per shape
        return jnp.asarray((rng.standard_normal(shape) * scale)
                           .astype(dtype))

    def compare(name, fn, ref, args):
        """fn vs ref on (value, grads w.r.t. every arg) under one jit.
        The cotangent is random: an all-ones one sends a near-zero
        gradient through a normalisation and leaves rounding noise to
        compare. Tolerance: on the chip every leaf of every kernel sits
        within 0.0041 of its reference (two input samples, PR 21), about
        one bf16 ulp; a wrong tile, mask or statistic is 0.01 to 1."""
        def both(f):
            def run(*a):
                out, vjp = jax.vjp(f, *a)
                leaves, tree = jax.tree_util.tree_flatten(out)
                cot = [jax.random.normal(jax.random.PRNGKey(i), x.shape,
                                         x.dtype)
                       for i, x in enumerate(leaves)]
                return out, vjp(jax.tree_util.tree_unflatten(tree, cot))
            return jax.jit(run)
        got, want = (jax.tree_util.tree_leaves(both(f)(*args))
                     for f in (fn, ref))
        errs[name] = round(max(map(_rel_err, got, want)), 5)
        _require(errs[name] <= 1e-2,
                 f"kernel {name}: rel L2 err {errs[name]} > 0.01")

    # layernorm(x + residual): bf16 + bf16, and bf16 + the f32 residual
    # the first encoder layer sees
    rows, h = p["layernorm"]
    w, b = 1.0 + normal((h,), scale=0.1), normal((h,), scale=0.1)
    for rdt in (bf16, f32):
        x, r = normal((rows, h), bf16), normal((rows, h), rdt)
        _require(lnr._supported(x, r, w, b) == tpu, "layernorm predicate")
        compare(f"layernorm_{jnp.dtype(rdt).name}",
                lambda *a: lnr.layernorm_residual(*a, 1e-5).astype(f32),
                lambda *a: lnr._reference(*a, 1e-5).astype(f32),
                (x, r, w, b))

    # flash attention with a key-padding bias, no dropout: vs plain.
    # k carries a constant column for the dropout check below.
    bsz, heads, seq, d = p["flash"]
    q, v, g = (normal((bsz, heads, seq, d), bf16) for _ in range(3))
    k = rng.standard_normal((bsz, heads, seq, d))
    k[..., 0] = 1.0
    k = jnp.asarray(k.astype(bf16))
    pad = np.arange(seq)[None, :] >= seq - 3 * np.arange(bsz)[:, None]
    bias = jnp.asarray(
        np.where(pad, -1e9, 0.0).astype(bf16)[:, None, None, :])
    _require(fla._supported(q, k, v, bias) == tpu, "flash predicate")
    compare("flash",
            lambda q, k, v: fla.flash_attention(q, k, v, bias=bias)
            .astype(f32),
            lambda q, k, v: fla._plain_attention(
                q, k, v, bias, False, float(d) ** -0.5).astype(f32),
            (q, k, v))

    # in-kernel dropout has no reference (the TPU PRNG is the kernel's
    # own), so check what must hold whatever the mask is, as long as
    # forward and both backward kernels regenerate the SAME one:
    # out is linear in v, so <out, g> == <v, dv> per head; and with a
    # constant k[..., 0], dq[..., 0] = scale * rowsum(dS) == 0.
    @jax.jit
    def dropped(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: fla.flash_attention(
                q, k, v, bias=bias, dropout_rate=0.1,
                dropout_key=jax.random.PRNGKey(7)), q, k, v)
        dq, _, dv = vjp(g)
        lhs = (out.astype(f32) * g.astype(f32)).sum(axis=(2, 3))
        rhs = (v.astype(f32) * dv.astype(f32)).sum(axis=(2, 3))
        return out, dq, lhs, rhs

    out, dq, lhs, rhs = (np.asarray(a, np.float32)
                         for a in dropped(q, k, v, g))
    errs["flash_dropout_dkv_mask"] = round(float(
        np.linalg.norm(lhs - rhs) / np.linalg.norm(lhs)), 5)
    errs["flash_dropout_dq_mask"] = round(float(
        np.abs(dq[..., 0]).max() / np.abs(dq[..., 1:]).max()), 5)
    _require(np.isfinite(out).all()
             and errs["flash_dropout_dkv_mask"] <= 5e-2
             and errs["flash_dropout_dq_mask"] <= 5e-2,
             f"flash dropout masks disagree: {errs}")

    # conv + bn + relu, training mode: output, new running stats, grads
    for n, cin, hw, cout, ksz, stride, padding in p["conv"]:
        x = normal((n, cin, hw, hw), bf16)
        wt = normal((cout, cin, ksz, ksz), bf16, (cin * ksz * ksz) ** -0.5)
        gamma = 1.0 + normal((cout,), scale=0.1)
        beta = normal((cout,), scale=0.1)
        mean, var = jnp.zeros((cout,)), jnp.ones((cout,))
        kw = dict(stride=stride, padding=padding, training=True,
                  momentum=0.9, data_format="NCHW")
        _require(cbr._supported(x, wt, stride, padding, "NCHW", 1, 1)
                 == (tpu and (ksz, stride, padding) == (1, 1, 0)),
                 "conv predicate")

        def fused(x, wt, gamma, beta):
            y, *stats = cbr.conv_bn_relu(x, wt, gamma, beta, mean, var,
                                         epsilon=1e-5, **kw)
            return y.astype(f32), stats

        def ref(x, wt, gamma, beta):
            y, *stats = cbr._reference(x, wt, gamma, beta, mean, var,
                                       eps=1e-5, **kw)
            return y.astype(f32), stats

        compare(f"conv{ksz}x{ksz}_c{cin}", fused, ref,
                (x, wt, gamma, beta))

    # momentum update (f32 elementwise: near exact). The matrix takes the
    # kernel; the 3x3 weight's [rows, 128] view would be re-tiled, so it
    # is the reference itself and its program holds no Mosaic call
    lr = jnp.float32(0.1)
    for name, kernel in (("momentum", True), ("momentum_spatial", False)):
        prm, grd, vel = (normal(p[name]) for _ in range(3))
        _require(opu._supported(prm, grd, vel) == kernel,
                 f"{name} predicate")
        fused = jax.jit(lambda *a: opu.fused_momentum_update(
            *a, momentum=0.9, weight_decay=1e-4)).lower(
                prm, grd, vel, lr).compile()
        _check_kernels(_mosaic_calls(fused.as_text()),
                       {"momentum_update"} if kernel else set(), name)
        want = jax.jit(lambda *a: opu._jnp_update(*a, 0.9, 1e-4, False))(
            prm, grd, vel, lr)
        errs[name] = max(map(_rel_err, fused(prm, grd, vel, lr), want))
        _require(errs[name] <= (1e-5 if kernel else 0.0),
                 f"kernel {name}: rel err {errs[name]}")
    return {"pallas": tpu, "rel_err": errs}


# -- legs: BERT ---------------------------------------------------------------


def _bert_batch(cfg, batch, seq, n_pred):
    rng = np.random.RandomState(0)
    ids = rng.randint(1, cfg.vocab_size, (batch, seq)).astype("int32")
    tt = rng.randint(0, 2, (batch, seq)).astype("int32")
    pos = np.stack([rng.choice(seq, n_pred, replace=False) + i * seq
                    for i in range(batch)]).ravel().astype("int32")
    mlm = rng.randint(0, cfg.vocab_size, (batch * n_pred,)).astype("int32")
    nsp = rng.randint(0, 2, (batch, 1)).astype("int32")
    return ids, tt, pos, mlm, nsp


def _bert_trainer(preset, mesh=None):
    """The recipe of examples/train_bert_pretrain.py --full."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import amp, parallel
    from paddle_tpu.framework import jit as fjit
    from paddle_tpu.models import (BertConfig, BertForPretraining,
                                   BertPretrainingCriterion,
                                   bert_sharding_rules)

    cfg = BertConfig(use_flash_attention=True, **preset["bert"]["config"])
    paddle.seed(0)
    model = BertForPretraining(cfg)
    crit = BertPretrainingCriterion(cfg.vocab_size)
    optimizer = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())

    def loss_fn(m, ids, tt, pos, mlm, nsp):
        with amp.auto_cast():
            pred, rel = m(ids, tt, masked_positions=pos)
        return crit(pred.astype("float32"), rel.astype("float32"), mlm, nsp)

    if mesh is None:
        return cfg, fjit.train_step(model, optimizer, loss_fn)
    return cfg, parallel.sharded_train_step(
        model, optimizer, loss_fn, mesh, rules=bert_sharding_rules())


def leg_bert(preset) -> dict:
    from paddle_tpu.nn.transformer import FLASH_ATTENTION_MIN_SEQ

    cfg, step = _bert_trainer(preset)
    phases = []
    for batch, seq, n_pred in preset["bert"]["phases"]:
        losses, compile_s, run_s = _run_steps(
            step, _bert_batch(cfg, batch, seq, n_pred),
            preset["bert"]["steps"])
        flash = seq >= FLASH_ATTENTION_MIN_SEQ
        found, flops = _newest_executable(step)
        _check_kernels(found,
                       BERT_FLASH_KERNELS if flash else BERT_XLA_ATTN_KERNELS,
                       f"bert {batch}x{seq}")
        phases.append({
            "batch": batch, "seq": seq, "flash": flash,
            "losses": [round(x, 4) for x in losses],
            "compile_s": round(compile_s, 1), "run_s": round(run_s, 2),
            "flops": flops, "mosaic_calls": dict(found),
        })
    return {"layers": cfg.num_hidden_layers, "hidden": cfg.hidden_size,
            "phases": phases, "peak_bytes": _peak_bytes()}


def leg_bert4(preset, one_chip: dict) -> dict:
    """The same trainer, first phase, on a dp=2 x tp=2 mesh: state really
    on four devices, first loss equal to the one-chip leg's within bf16
    noise, per-device FLOPs of the compiled step about a quarter."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import parallel

    mesh = parallel.create_mesh(dp=2, tp=2)
    cfg, step = _bert_trainer(preset, mesh)
    leaves = jax.tree_util.tree_leaves(
        (step.state["params"], step.state["opt"]))
    on = set().union(*(a.sharding.device_set for a in leaves))
    split = sum(1 for a in leaves if not a.sharding.is_fully_replicated)
    _require(len(on) == 4 and split,
             f"state lives on {len(on)} devices, {split} leaves sharded")
    batch, seq, n_pred = preset["bert"]["phases"][0]
    ref = one_chip["phases"][0]
    data = _bert_batch(cfg, batch, seq, n_pred)
    losses, compile_s, run_s = _run_steps(step, data,
                                          preset["bert"]["steps"])
    # dropout masks differ between the layouts; the first loss sits at
    # ln(vocab) + ln(2) either way
    _require(abs(losses[0] - ref["losses"][0]) <= 2e-2 * ref["losses"][0],
             f"first loss {losses[0]} vs one chip {ref['losses'][0]}")
    # the step's own executable again (a compile-cache hit by now)
    with parallel.mesh_scope(mesh):
        arrs = tuple(map(jnp.asarray, data))
        arrs = jax.tree_util.tree_map(
            jax.device_put, arrs,
            parallel.shard_batch(arrs, mesh, step.batch_axes))
        compiled = step.compiled.lower(
            step.state, arrs, jnp.float32(1e-4), step._rng).compile()
    text = compiled.as_text()
    found = _mosaic_calls(text)
    _require(not found, f"Mosaic calls under a mesh: {found}")
    share = float(compiled.cost_analysis()["flops"]) / ref["flops"]
    _require(0.2 <= share <= 0.4, f"per-device FLOPs share {share}")
    return {"mesh": {"dp": 2, "tp": 2},
            "devices": sorted(d.id for d in on), "sharded_leaves": split,
            "batch": batch, "seq": seq,
            "losses": [round(x, 4) for x in losses],
            "compile_s": round(compile_s, 1), "run_s": round(run_s, 2),
            "flops_per_device": share * ref["flops"],
            "flops_vs_one_chip": round(share, 3),
            "all_reduces": text.count(" all-reduce("),
            "peak_bytes": _peak_bytes()}


# -- leg: ResNet --------------------------------------------------------------


def leg_resnet(preset) -> dict:
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu import amp, models
    from paddle_tpu.framework import jit as fjit

    p = preset["resnet"]
    paddle.seed(0)
    model = getattr(models, p["make"])(num_classes=p["classes"])
    # lr is a traced scalar, so its value changes no program: at 0.1 the
    # first steps on ONE fixed batch overshoot (8.7 -> 12.7 in five
    # steps, ResNet-50 on the CPU), at 0.02 they fall
    optimizer = opt.Momentum(learning_rate=0.02, momentum=0.9,
                             parameters=model.parameters())

    def loss_fn(m, x, y):
        with amp.auto_cast():
            logits = m(x)
        return F.cross_entropy(logits.astype("float32"), y).mean()

    step = fjit.train_step(model, optimizer, loss_fn)
    rng = np.random.RandomState(0)
    x = rng.randn(p["batch"], 3, p["size"], p["size"]).astype("float32")
    y = rng.randint(0, p["classes"], (p["batch"],)).astype("int32")
    losses, compile_s, run_s = _run_steps(step, (x, y), p["steps"])
    found, _ = _newest_executable(step)
    _check_kernels(found, RESNET_KERNELS, p["make"])
    return {"model": p["make"], "batch": p["batch"],
            "losses": [round(v, 4) for v in losses],
            "compile_s": round(compile_s, 1), "run_s": round(run_s, 2),
            "mosaic_calls": dict(found), "peak_bytes": _peak_bytes()}


# -- leg: GPT server ----------------------------------------------------------


def _post_generate(url, payload):
    body = json.dumps(payload).encode()
    r = urlopen(Request(url + "/generate", data=body,
                        headers={"Content-Type": "application/json"}),
                timeout=300)
    raw = r.read().decode()
    if payload.get("stream"):
        lines = [json.loads(line) for line in raw.splitlines()]
        tokens = [line["token"] for line in lines if "token" in line]
        _require(lines[-1].get("done") and lines[-1]["tokens"] == tokens,
                 f"broken stream: {lines[-1]}")
        return r.status, tokens
    return r.status, json.loads(raw)["tokens"]


def _serve_leg(engine, prompts, max_new) -> dict:
    """A started GenerationServer over ``engine`` (warm-up compiles
    exactly ``expected_compiles()``: the ladder + 1 decode, or, where
    long prompts go in by chunks, the ladder's first two buckets + the
    chunk program + 1 decode), the prompts in flight together over HTTP, then
    the same engine offline: token for token."""
    from paddle_tpu import profiler
    from paddle_tpu.generation import COMPILE_COUNTER
    from paddle_tpu.serving import GenerationServer

    expected = engine.expected_compiles()
    srv = GenerationServer(engine, port=0)
    c0 = profiler.counters().get(COMPILE_COUNTER, 0)
    t0 = time.perf_counter()
    srv.start()  # with warm-up: every program compiles here
    compile_s = time.perf_counter() - t0
    try:
        warm = profiler.counters().get(COMPILE_COUNTER, 0) - c0
        _require(warm == expected,
                 f"warm-up compiled {warm} programs, expected {expected}")
        # all requests in flight together (continuous batching), the
        # first one streamed; a failed request raises out of map()
        def client(i):
            return _post_generate(srv.url, {
                "prompt": prompts[i], "max_new_tokens": max_new,
                "temperature": 0.0, "stream": i == 0})

        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(prompts)) as pool:
            served = list(pool.map(client, range(len(prompts))))
        run_s = time.perf_counter() - t0
        _require(all(status == 200 for status, _ in served),
                 f"requests failed: {served}")
        statz = json.loads(urlopen(srv.url + "/statz", timeout=60).read())
        _require(statz["compiles"]["unexpected"] == 0,
                 f"unexpected compiles: {statz['compiles']}")
    finally:
        srv.stop(drain=True)
    _require(not (srv.scheduler.live_slots or srv.scheduler.alive),
             "server did not drain")
    # the same engine, offline, greedy: token for token
    offline = engine.generate(prompts, max_new_tokens=max_new,
                              temperature=0.0)
    for (_, tokens), ref in zip(served, offline):
        _require(tokens == ref and 1 <= len(tokens) <= max_new,
                 f"served {tokens} != offline {ref}")
    _require(engine.extra_compiles() == 0,
             f"{engine.extra_compiles()} extra compiles")
    return {"requests": len(prompts),
            "tokens_served": sum(len(t) for _, t in served),
            "warmup_compiles": warm, "compile_s": round(compile_s, 1),
            "run_s": round(run_s, 2), "peak_bytes": _peak_bytes()}


def _prompts(lens, vocab):
    rng = np.random.RandomState(0)
    return [[int(t) for t in rng.randint(3, vocab, size=n)] for n in lens]


def leg_gpt(preset) -> dict:
    import paddle_tpu as paddle
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    p = preset["gpt"]
    cfg = GPTConfig(hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
                    **p["config"])
    paddle.seed(11)
    engine = GenerationEngine(GPTForCausalLM(cfg), **p["engine"])
    out = _serve_leg(engine, _prompts(p["prompt_lens"], cfg.vocab_size),
                     p["max_new_tokens"])
    return dict({"layers": cfg.num_hidden_layers, "hidden": cfg.hidden_size,
                 "vocab": cfg.vocab_size}, **out)


def leg_hybrid(preset) -> dict:
    """The hybrid MoE decoder (linear attention beside GQA, routed
    experts of which this chip holds a share) through the same server:
    a K/V ring and a recurrent state in one cache."""
    import paddle_tpu as paddle
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.models import HybridMoEConfig, HybridMoEForCausalLM

    p = preset["hybrid"]
    cfg = HybridMoEConfig(**p["config"])
    paddle.seed(13)
    engine = GenerationEngine(HybridMoEForCausalLM(cfg), temperature=0.0,
                              top_k=0, kv_cache_layout="ring", **p["engine"])
    kinds = [type(k).__name__ for k in engine.model.cache_spec()]
    _require(set(kinds) == {"KVKind", "StateKind"},
             f"expected layers of both kinds, got {kinds}")
    out = _serve_leg(engine, _prompts(p["prompt_lens"], cfg.vocab_held),
                     p["max_new_tokens"])
    return dict({"layers": cfg.num_hidden_layers, "hidden": cfg.hidden_size,
                 "experts_held": list(cfg.experts_held),
                 "state_bytes": engine.state_nbytes(),
                 "cache_bytes": engine.cache_nbytes()}, **out)


def leg_window(preset) -> dict:
    """The window/full attention decoder through the same server: a
    ring as long as the cache and a ring of the window's rows in one
    cache, served past the window's wrap."""
    import paddle_tpu as paddle
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.models import ExaoneMoEConfig, ExaoneMoEForCausalLM

    p = preset["window"]
    cfg = ExaoneMoEConfig(**p["config"])
    paddle.seed(17)
    engine = GenerationEngine(ExaoneMoEForCausalLM(cfg), temperature=0.0,
                              top_k=0, kv_cache_layout="ring", **p["engine"])
    rings = [k.ring(engine.store_len) for k in engine.model.cache_spec()]
    _require(rings == [cfg.sliding_window, engine.cache_len],
             f"expected a window ring and a full ring, got {rings}")
    _require(max(p["prompt_lens"]) > cfg.sliding_window,
             "no prompt wraps the window")
    out = _serve_leg(engine, _prompts(p["prompt_lens"], cfg.vocab_held),
                     p["max_new_tokens"])
    full, window = engine.cache_bytes_by_kind()[:2]
    return dict({"layers": cfg.num_hidden_layers, "hidden": cfg.hidden_size,
                 "experts_held": list(cfg.experts_held), "rings": rings,
                 "full_ring_bytes": full, "window_ring_bytes": window},
                **out)


def leg_latent(preset) -> dict:
    """One latent attention (nn/mla.py), its two paths against each
    other on the same weights and the same ring: rows prefilled by the
    expanded path, then absorbed decode steps, each compared with the
    expanded attention's row at that position recomputed from the
    latent rows (the largest difference over the largest value)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.generation import cache as gcache
    from paddle_tpu.nn.mla import CachedLatentAttention

    p = preset["latent"]
    paddle.seed(23)
    layer = CachedLatentAttention(initializer_range=0.02, **p["layer"])
    b, ring, n, steps = p["slots"], p["ring"], p["rows"], p["steps"]
    dtype, hidden = p["layer"]["dtype"], p["layer"]["hidden_size"]
    x = jax.random.normal(jax.random.PRNGKey(5), (b, n + steps, hidden),
                          jnp.float32).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(n + steps)[None], (b, n + steps))
    kind = gcache.latent(layer.rank, layer.rope)

    @jax.jit
    def both(x):
        cache = kind.wrap(kind.arrays(b, ring, dtype),
                          jnp.zeros((b,), jnp.int32))
        _, cache = layer(x[:, :n], cache=cache, positions=pos[:, :n])
        q_nope, q_rot, row = layer._query_and_row(x, pos)
        worst = jnp.zeros((), jnp.float32)
        for i in range(n, n + steps):
            at = jnp.full((b,), i, jnp.int32)
            got, cache = layer(x[:, i:i + 1], positions=at[:, None],
                               cache=kind.wrap((cache.c,), at),
                               mask=gcache.decode_mask(at, ring))
            want = jnp.matmul(layer.expanded(
                q_nope[:, :i + 1], q_rot[:, :i + 1], row[:, :i + 1],
                None)[:, -1], layer.wo._array).astype(jnp.float32)
            worst = jnp.maximum(worst, jnp.abs(
                got[:, 0].astype(jnp.float32) - want).max()
                / jnp.abs(want).max())
        return worst

    err = float(both(x))
    _require(err <= p["tol"], f"absorbed latent attention is {err:.3g} of "
             f"the expanded one's largest value off it (limit {p['tol']})")
    return {"heads": layer.num_heads, "row": layer.rank + layer.rope,
            "ring": ring, "rows": n, "steps": steps,
            "absorbed_vs_expanded": err}


# -- driver -------------------------------------------------------------------


def run_legs(preset) -> dict:
    """Every leg this process has the devices for, in order. A failing
    leg raises; nothing here catches it."""
    import jax

    legs = {}
    for name, leg in (("kernels", leg_kernels), ("bert", leg_bert),
                      ("resnet", leg_resnet), ("gpt", leg_gpt),
                      ("hybrid", leg_hybrid), ("window", leg_window),
                      ("latent", leg_latent)):
        legs[name] = leg(preset)
        print(f"leg {name} on 1 device: {json.dumps(legs[name])}",
              flush=True)
        gc.collect()  # drop the leg's model and state before the next
    if len(jax.devices()) >= 4:
        legs["bert4"] = leg_bert4(preset, legs["bert"])
        print(f"leg bert4 on 4 devices: {json.dumps(legs['bert4'])}",
              flush=True)
    return legs


def main() -> int:
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}",
          flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: FAILED — needs a TPU, jax reports platform "
              f"{dev.platform!r}", flush=True)
        return 1
    stats = dev.memory_stats()
    print(f"chip_smoke: bytes_limit={stats['bytes_limit']}", flush=True)
    t0 = time.perf_counter()
    legs = run_legs(CHIP)
    print(f"chip_smoke: legs {', '.join(legs)} passed in "
          f"{time.perf_counter() - t0:.0f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Each plain reference against the system's model with the same
(benchmark-made) weights, at a tiny size on the CPU in float32: the
value, and for the trainers the gradients."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import common
from benchmark.tests import tiny


def _mod(config, name):
    return common.load_module(os.path.join(tiny.BENCH, "configs", config,
                                           name + ".py"))


@pytest.mark.parametrize("config,mixname", [
    ("bert-base", "pretrain-seq128"), ("resnet50", "imagenet-b128")])
def test_trainer_matches_reference(config, mixname):
    """The program's first three steps against the reference's: loss,
    first gradient (worst leaf) and parameter change. AMP rounds to
    bfloat16 on the CPU as on the chip, so the limits are loose; an
    exact float32 comparison follows below."""
    from benchmark.lib import train_check

    cfg = tiny.config(config)
    mix = dict(common.load_json(os.path.join(
        tiny.BENCH, "traffic", mixname + ".json")), **tiny.MIXES[mixname])
    build, ref = _mod(config, "build"), _mod(config, "reference")
    tr = build.trainer(cfg, mix, 3, jax.devices()[:1])
    batches = [tr.feed(i) for i in range(3)]
    got = train_check.program_readings(tr, batches)
    w = jax.jit(lambda k: ref.weights(cfg, k))(common.seed_key(3))
    want = train_check.reference_readings(ref, cfg, w, batches, rng=tr.rng)
    nums, _ = train_check.compare(got, want)
    lim = cfg["check"]
    assert nums["loss_gap"] <= lim["loss_gap"]
    assert nums["grad_norm_gap"] <= lim["grad_norm_gap"]
    assert nums["delta_norm_gap"] <= lim["delta_norm_gap"]
    assert nums["grad_diff"] <= lim["grad_diff"]


@pytest.mark.parametrize("config,mixname", [
    ("bert-base", "pretrain-seq128"), ("resnet50", "imagenet-b128")])
def test_model_matches_reference_in_float32(config, mixname, monkeypatch):
    """Without AMP both sides compute in float32: loss and every
    gradient leaf agree to rounding - BERT's with dropout 0.1 on both
    sides, so the reference has drawn the program's masks."""
    from paddle_tpu import amp

    import contextlib
    monkeypatch.setattr(amp, "auto_cast",
                        lambda *a, **k: contextlib.nullcontext())
    cfg = tiny.config(config)
    mix = dict(common.load_json(os.path.join(
        tiny.BENCH, "traffic", mixname + ".json")), **tiny.MIXES[mixname])
    build, ref = _mod(config, "build"), _mod(config, "reference")
    tr = build.trainer(cfg, mix, 4, jax.devices()[:1])
    batch = tr.feed(0)
    tr.step(*batch)  # lr > 0: one step; the first gradient is in the state
    acc = tr.step.state["opt"]["accums"][tr.first_moment]
    w = jax.jit(lambda k: ref.weights(cfg, k))(common.seed_key(4))
    key = {"key": ref.step_keys(tr.rng, 1)[0]} if tr.rng else {}
    loss, grads = ref.value_and_grad(w, batch, cfg, **key)
    if hasattr(ref, "by_program_name"):
        grads = ref.by_program_name(grads)
    for name, m in zip(tr.accum_names, acc):
        g = np.asarray(m) / tr.first_moment_scale
        want = np.asarray(grads[name])
        scale = max(np.abs(want).max(), 1e-6)
        assert np.abs(g - want).max() <= 2e-3 * scale + 1e-6, name
    if tr.rng:  # the next step's key draws other masks: another gradient
        _, other = ref.value_and_grad(w, batch, cfg,
                                      key=ref.step_keys(tr.rng, 2)[1])
        name = "bert.encoder.layers.0.linear1.weight"
        want, got = (np.asarray(t[name]) for t in
                     (grads, ref.by_program_name(other)))
        assert np.linalg.norm(got - want) > 0.1 * np.linalg.norm(want)


def test_gpt2_model_matches_reference():
    """The served model's full forward against the reference's, float32
    on the CPU: logits agree to rounding."""
    cfg = tiny.config("gpt2-large")
    build, ref = _mod("gpt2-large", "build"), _mod("gpt2-large", "reference")
    model = build.model(cfg, 5)
    tokens = np.asarray(common.host_rng(5).integers(3, cfg["vocab_size"],
                                                    (2, 40)), np.int32)
    got = np.asarray(model(tokens)._array)
    w = jax.jit(lambda k: ref.weights(cfg, k))(common.seed_key(5))
    want = np.asarray(jax.jit(lambda w, t: ref.forward(w, t, cfg))(
        w, jnp.asarray(tokens)))
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err <= 1e-5, err


@pytest.mark.parametrize("config,mixname,limit", [
    ("bert-base", "pretrain-seq128", 0.015),
    ("resnet50", "imagenet-b128", None)])
def test_fp8_control_fails_the_gradient_check(config, mixname, limit):
    """The control: the reference with matmul operands in fp8, put in the
    program's place. Its first gradient sits several times farther from
    the float32 reference than the bf16 program's does; at this size a
    limit between the two holds for BERT (the tiny ResNet, 8 images
    through batch norm, is only asked for the factor)."""
    from benchmark.lib import train_check

    cfg = tiny.config(config)
    mix = dict(common.load_json(os.path.join(
        tiny.BENCH, "traffic", mixname + ".json")), **tiny.MIXES[mixname])
    build, ref = _mod(config, "build"), _mod(config, "reference")
    tr = build.trainer(cfg, mix, 6, jax.devices()[:1])
    batches = [tr.feed(i) for i in range(3)]
    got = train_check.program_readings(tr, batches)
    make = jax.jit(lambda k: ref.weights(cfg, k))
    want = train_check.reference_readings(
        ref, cfg, make(common.seed_key(6)), batches, rng=tr.rng)
    low = train_check.reference_readings(
        ref, cfg, make(common.seed_key(6)), batches, control=True,
        rng=tr.rng)
    program = train_check.compare(got, want)[0]["grad_diff"]
    control = train_check.compare(low, want)[0]["grad_diff"]
    assert control >= 2.0 * program
    if limit is not None:
        assert program <= limit < control

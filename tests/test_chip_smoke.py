"""Rehearsal of chip_smoke.py on the CPU: the SAME leg functions the chip
runs, at the TINY preset. Off the chip every kernel takes its reference
path (no interpret mode), so this checks the script, the entry points it
drives and its pass/fail logic — not the device. ``main`` itself has no
CPU mode and must fail here."""
import importlib.util
import json
import os

import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chip_smoke.py")
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


def test_every_leg_passes_at_the_tiny_preset():
    legs = chip_smoke.run_legs(chip_smoke.TINY)
    # the suite's 8 virtual devices bring the four-device leg in as well
    assert list(legs) == ["kernels", "bert", "resnet", "gpt", "hybrid",
                          "window", "latent", "bert4"]
    json.dumps(legs)  # what main prints per leg
    assert legs["kernels"]["pallas"] is False
    assert legs["kernels"]["rel_err"]["momentum_spatial"] == 0.0
    for run in legs["bert"]["phases"] + [legs["bert4"]]:
        assert run["losses"][-1] < run["losses"][0]
    assert [p["flash"] for p in legs["bert"]["phases"]] == [False, True]
    assert legs["gpt"]["warmup_compiles"] == 3  # ladder of 2, + 1 decode
    assert legs["gpt"]["tokens_served"] >= legs["gpt"]["requests"]
    assert legs["hybrid"]["warmup_compiles"] == 3
    assert 0 < legs["hybrid"]["state_bytes"] < legs["hybrid"]["cache_bytes"]
    # a cache of rings alone takes long prompts by chunks of the ladder's
    # second bucket: two buckets, the chunk program and the decode program
    assert legs["window"]["warmup_compiles"] == 4
    assert legs["window"]["rings"] == [8, 32]
    assert 0 < legs["window"]["window_ring_bytes"] \
        < legs["window"]["full_ring_bytes"]
    assert legs["latent"]["row"] == 16 and legs["latent"]["ring"] == 16
    assert 0 < legs["latent"]["absorbed_vs_expanded"] <= 1e-4
    assert len(legs["bert4"]["devices"]) == 4


def test_a_broken_leg_raises(monkeypatch):
    """Nothing in the script catches a leg's failure: one kernel off its
    reference (forced here) propagates out of run_legs."""
    import importlib

    lnr = importlib.import_module(
        "paddle_tpu.ops.pallas.layernorm_residual")
    monkeypatch.setattr(lnr, "_ln_res", lambda x, r, w, b, eps: x * 0)
    with pytest.raises(AssertionError, match="kernel layernorm"):
        chip_smoke.run_legs(chip_smoke.TINY)


def test_a_kernel_missing_from_the_step_fails_the_leg(monkeypatch):
    """On the chip a compiled step without its Mosaic calls is a failed
    leg, not a pass on the reference path."""
    monkeypatch.setattr(chip_smoke, "_on_tpu", lambda: True)
    with pytest.raises(AssertionError, match="expected .*momentum_update"):
        chip_smoke._check_kernels({}, chip_smoke.RESNET_KERNELS, "resnet")
    chip_smoke._check_kernels(
        dict.fromkeys(chip_smoke.RESNET_KERNELS, 1),
        chip_smoke.RESNET_KERNELS, "resnet")


def test_mosaic_call_names_are_read_from_compiled_text():
    line = ('  %x.1 = f32[8,128]{1,0} custom-call(%a), '
            'custom_call_target="tpu_custom_call", metadata={op_name='
            '"jit(pure)/jvp(bert)/layernorm_residual_fwd/pallas_call" '
            'stack_frame_id=6}, backend_config={}')
    other = '  %y = f32[8] custom-call(%a), custom_call_target="Sharding"'
    # a transformed kernel's scope is wrapped by the transformation
    wrapped = line.replace("jvp(bert)/layernorm_residual_fwd",
                           "transpose(jvp(conv_bn_bwd_dco))")
    assert chip_smoke._mosaic_calls("\n".join([line, other, wrapped])) == {
        "layernorm_residual_fwd": 1, "conv_bn_bwd_dco": 1}


def test_main_fails_without_a_tpu(capsys):
    assert chip_smoke.main() == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert out[0].startswith("chip_smoke: platform=cpu device_kind='cpu'")
    assert "needs a TPU" in out[-1]
    assert not any(line.startswith("{") for line in out)  # no result line

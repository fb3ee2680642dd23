"""`solar-open2-250b` at a tiny size on the CPU: the plain reference
against the program's model, the cell end to end through the harness (a
sound run is `correct`, a run with an altered token is not), the traced
run's per-layer readers, and the float8 control against the check's
limits. The tiny size is this file's own (tests/tiny.py has the sizes of
the configurations the benchmark began with)."""
import io
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.lib import common
from benchmark.tests import tiny

CELL = "solar-open2-250b.docchat-overload"
SIZES = dict(
    hidden_size=32, num_attention_heads=4, head_dim=8,
    num_key_value_heads=2, vocab_size=64, moe_intermediate_size=16,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=8,
                            num_heads=4, num_kv_heads=None),
    n_routed_experts=8, experts_held=[4, 8], num_experts_per_tok=4,
    published=dict(num_hidden_layers=48, n_routed_experts=16,
                   vocab_size=97),
    assumed_sizes=dict(shared_expert_width=16, kda_gate_rank=8,
                       initializer_range=0.2),
    program_dtype="float32")
MIX = dict(rate_per_s=4.0, context_limit=128, drain_s=30.0,
           backlog_at_start=4,
           prompt_tokens=dict(median=14, sigma=0.5, min=4, max=48),
           output_tokens=dict(median=8, sigma=0.5, min=2, max=16),
           check_requests=8, trace_after_s=0.3, trace_s=1.5)


def config():
    cfg = common.load_json(os.path.join(
        tiny.BENCH, "configs", "solar-open2-250b", "config.json"))
    cfg.update(SIZES)
    cfg["engine"] = dict(cfg["engine"], slots=4, cache_len=128,
                         prefill_buckets=[16, 32, 64],
                         kv_cache_dtype="float32")
    cfg["check"] = dict(cfg["check"], gap_max=1e-3, err_scale=1e-4,
                        min_tokens=8, requests=8,
                        score_lengths=[64, 128])
    return cfg


def _mod(name):
    return common.load_module(os.path.join(
        tiny.BENCH, "configs", "solar-open2-250b", name + ".py"))


@pytest.fixture()
def root(tmp_path):
    root = tiny.checkout(tmp_path)
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", "solar-open2-250b",
                           "config.json"), "w") as f:
        json.dump(config(), f)
    path = os.path.join(b, "traffic", "docchat-overload.json")
    mix = dict(common.load_json(path), **MIX)
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


def _run(root, seconds=3.0, trace=0):
    out = io.StringIO()
    res = harness.run_cell(root, CELL, 2147483997, seconds, trace,
                           require_chip=False, out=out)
    return res, out.getvalue()


def test_reference_matches_program_model():
    """Full forward, float32 both sides, the benchmark's weights."""
    cfg = config()
    build, ref = _mod("build"), _mod("reference")
    m = build.model(cfg, 11)
    w = ref.weights(cfg, common.seed_key(11))
    toks = np.random.default_rng(0).integers(3, cfg["vocab_size"], size=50)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg))
    got = np.asarray(m(jnp.asarray(toks[None]))._array[0])
    assert want.std() > 0.3
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_served_run_is_correct_and_altered_token_is_not(root, monkeypatch):
    res, text = _run(root)
    assert res["correct"], text
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    from paddle_tpu.generation import GenerationEngine

    sound = GenerationEngine.step

    def altered(self, tokens, temps):
        nxt = sound(self, tokens, temps).copy()
        nxt[0] = (nxt[0] + 17) % 50 + 3  # slot 0 serves a wrong token
        return nxt

    monkeypatch.setattr(GenerationEngine, "step", altered)
    res, text = _run(root)
    assert not res["correct"] and "gap_max" in text


def test_traced_run_reads_the_counters(root):
    """Off the chip the trace has no device plane with scopes, so the
    three device readers give nothing and do not raise; the two counter
    readers read the program's samples."""
    res, text = _run(root, trace=1)
    assert res["correct"], text
    m = res["metrics"]
    assert 0 < m["experts_hit_pct.decode"]["value"] <= 100
    assert m["expert_load_max_over_mean"]["value"] >= 1.0
    assert "decode_step_ms" in m or "slots_busy_pct.sched" in m


def test_float8_control_fails_the_check():
    """The reference one precision down, put in the program's place,
    does not pass limits the program passes."""
    cfg = config()
    check = _mod("check")
    rng = np.random.default_rng(3)
    build = _mod("build")
    from paddle_tpu.generation import GenerationEngine

    eng = GenerationEngine(
        build.model(cfg, 5), slots=2, cache_len=128,
        prefill_buckets=(16, 32, 64), temperature=0.0, top_k=0,
        kv_cache_layout="ring", kv_cache_dtype="float32")
    prompts = [rng.integers(3, cfg["vocab_size"], size=n).tolist()
               for n in (9, 21, 30)]
    outs = eng.generate(prompts, max_new_tokens=20, stop_at_eos=False)
    reqs = [{"prompt": p, "tokens": o} for p, o in zip(prompts, outs)]
    served, control = check.gaps(cfg, 5, reqs, control=True)
    lim = cfg["check"]
    assert served["gap_max"] <= lim["gap_max"]
    assert served["err_scale"] <= lim["err_scale"]
    assert control["gap_max"] > lim["gap_max"] \
        or control["err_scale"] > lim["err_scale"]

"""`nemotron-3-super-120b` at a tiny size on the CPU: the configuration's
files against the catalog's row and the floors of a cut, the opcount
against the built model and ISSUE 40's arithmetic, the plain reference
against the program's model, the cell end to end through the harness (a
sound run is `correct`), the traced run's counter readers, the two new
shape readers on events written out here and on an empty trace, and the
check against the float8 control and planted faults. The tiny size is
this file's own."""
import io
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.lib import common, tracing
from benchmark.tests import tiny

NAME = "nemotron-3-super-120b"
CELL = NAME + ".agentturn-overload"
SIZES = dict(
    hidden_size=32, expand=1, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, mamba_num_heads=8, mamba_head_dim=4, ssm_state_size=16,
    n_groups=2, chunk_size=8, moe_latent_size=16, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=40, n_routed_experts=8,
    experts_held=[8, 8], num_experts_per_tok=6, vocab_size=64,
    assumed_sizes=dict(initializer_range=0.2), program_dtype="float32")
MIX = dict(rate_per_s=4.0, context_limit=128, drain_s=30.0,
           backlog_at_start=2,
           prompt_tokens=dict(median=20, sigma=0.5, min=9, max=60),
           output_tokens=dict(median=8, sigma=0.5, min=2, max=16),
           check_requests=8, trace_after_s=0.3, trace_s=1.5)
PUBLISHED = dict(
    num_hidden_layers=88, n_routed_experts=512, vocab_size=131072,
    num_nextn_predict_layers=1,
    hybrid_override_pattern="MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM"
    "*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def _path(*parts):
    return os.path.join(tiny.BENCH, *parts)


def config():
    cfg = common.load_json(_path("configs", NAME, "config.json"))
    cfg.update(SIZES)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16,
                            vocab_size=97)
    cfg["engine"] = dict(cfg["engine"], slots=6, cache_len=128,
                         prefill_buckets=[16, 32, 64],
                         kv_cache_dtype="float32")
    cfg["check"] = dict(cfg["check"], gap_max=2e-3, err_scale=2e-4,
                        min_tokens=8, requests=8, score_lengths=[64, 128],
                        score_rows=16)
    return cfg


def _mod(name):
    return common.load_module(_path("configs", NAME, name + ".py"))


def test_the_configuration_keeps_every_published_key():
    """Every number of the catalog's row is in the file under its own
    key, but for the five keys `reduced` names, which `published`
    keeps; the floors of a cut hold; the reference imports nothing of
    the program; the traffic fits the engine and the check."""
    cfg = common.load_json(_path("configs", NAME, "config.json"))
    bench = common.load_json(os.path.join(tiny.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == list(cfg["published"])
    assert entry["source"] == cfg["source"]
    want = dict(
        attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
        head_dim=128, hidden_size=4096, intermediate_size=2688,
        layer_norm_epsilon=1e-5, mamba_head_dim=64, mamba_hidden_act="silu",
        mamba_num_heads=128, mamba_proj_bias=False,
        max_position_embeddings=262144, mlp_bias=False,
        mlp_hidden_act="relu2", moe_intermediate_size=2688,
        moe_latent_size=1024, moe_shared_expert_intermediate_size=5376,
        n_group=1, n_groups=8, n_shared_experts=1, norm_eps=1e-5,
        norm_topk_prob=True, num_attention_heads=32, num_experts_per_tok=22,
        num_key_value_heads=2, partial_rotary_factor=1, rope_theta=10000,
        routed_scaling_factor=5, ssm_state_size=128,
        tie_word_embeddings=False, time_step_floor=1e-4, time_step_max=0.1,
        time_step_min=1e-3, topk_group=1, use_bias=False,
        use_conv_bias=True)
    assert {k: cfg[k] for k in want} == want
    assert cfg["published"] == PUBLISHED
    pattern = cfg["hybrid_override_pattern"]
    assert PUBLISHED["hybrid_override_pattern"].startswith(pattern)
    assert len(pattern) == cfg["num_hidden_layers"] == 11
    # one whole period at the published 5 : 5 : 1, and the floors
    assert [pattern.count(c) for c in "ME*"] == [5, 5, 1]
    assert [PUBLISHED["hybrid_override_pattern"].count(c)
            for c in "ME*"] == [40, 40, 8]
    assert cfg["n_routed_experts"] == cfg["experts_held"][1] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["num_nextn_predict_layers"] == 0
    for key in ("deployment", "changed", "assumed", "assumed_sizes",
                "precision", "engine", "opcount", "check"):
        assert cfg[key]
    assert all("lternative" in cfg["assumed"][k] for k in (
        "positions", "dt_clamp", "gated_norm", "latent_moe"))
    with open(_path("configs", NAME, "reference.py")) as f:
        assert "paddle_tpu" not in f.read()
    mix = common.load_json(_path("traffic", "agentturn-overload.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and mix["kind"] == "open_loop_http"
    assert len(cell["why"]) <= 200
    assert mix["context_limit"] == cfg["engine"]["cache_len"]
    assert mix["prompt_tokens"]["max"] <= max(cfg["engine"]["prefill_buckets"])
    assert mix["output_tokens"]["max"] <= cfg["check"]["score_rows"]
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= max(cfg["check"]["score_lengths"])
    assert mix["queue_capacity"] > mix["rate_per_s"] * bench["run_seconds"]
    assert mix["backlog_at_start"] >= cfg["engine"]["slots"]
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"].startswith("ssm_")}
    assert set(new) == {"ssm_time_share_pct", "ssm_state_roofline_pct.decode"}
    for m in new.values():
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
        assert m["layer"] == "model code" and m["source"] == "device_trace"
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "longcat-flash-omni.longreply-overload" in m.get("workloads", ()) \
                and not m["name"].startswith(("mla_", "zero_expert")):
            assert CELL in m["workloads"], m["name"]


def test_opcount_counts_the_built_models_parameters():
    cfg = config()
    oc = common.load_module(_path("opcount", "nemotron_h.py"))
    m = _mod("build").model(cfg, 3)
    built = sum(int(np.prod(p._array.shape))
                for _, p in m.named_parameters())
    assert oc.param_count(cfg) == built
    ref = _mod("reference")
    assert sum(int(np.prod(s)) for s in ref.leaf_shapes(cfg).values()) \
        == built
    # at the published widths, by shape arithmetic, nothing allocated:
    # ISSUE 40's numbers (it leaves out the norms' gains and the
    # selection bias, under 0.1 M in all)
    real = common.load_json(_path("configs", NAME, "config.json"))
    assert sum(int(np.prod(s)) for s in ref.leaf_shapes(real).values()) \
        == oc.param_count(real)
    assert round(oc.param_count(real) / 1e6) == 4648           # 4,648 M
    assert oc.expert_params(real) == 5505024
    assert round(oc.mixer_params(real, "M") / 1e4) == 10964     # 109.64 M
    assert round(oc.mixer_params(real, "*") / 1e4) == 3565      # 35.65 M
    assert round(oc.expert_layer_dense_params(real) / 1e4) == 5453
    uncut = dict(real, **real["published"], experts_held=[0, 512])
    assert round(oc.param_count(uncut) / 1e7) == 12067          # 120.67 B
    assert round(oc.active_params(uncut) / 1e8) == 122          # 12.2 B
    assert oc.state_bytes_per_slot(real) == 5 * (4194304 + 61440)
    assert oc.state_bytes_per_slot(real, tail=False) == 5 * 4194304
    assert oc.kv_bytes_per_token(real) == 1024
    e = real["engine"]
    assert round(e["slots"] * oc.state_bytes_per_slot(real) / 1e7) == 136
    assert round(e["slots"] * e["cache_len"] * 1024 / 1e7) == 107
    assert 120 < oc.expected_experts_hit(real, 64) < 121     # of 128
    assert 11.0e9 < oc.decode_bytes(real, 64 * 2000) < 12.5e9


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
    some = np.asarray(ref.forward(w, jnp.asarray(toks), cfg, rows=(30, 8)))
    np.testing.assert_allclose(some, want[30:38], atol=1e-5)


@pytest.fixture()
def root(tmp_path):
    root = tiny.checkout(tmp_path)
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", NAME, "config.json"), "w") as f:
        json.dump(config(), f)
    path = os.path.join(b, "traffic", "agentturn-overload.json")
    mix = dict(common.load_json(path), **MIX)
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


def _run(root, seconds=3.0, trace=0):
    out = io.StringIO()
    res = harness.run_cell(root, CELL, 2147483997, seconds, trace,
                           require_chip=False, out=out)
    return res, out.getvalue()


def test_served_run_is_correct(root):
    """The configuration, the mix, the cell and the two readers are
    files and entries the harness finds by name."""
    res, text = _run(root)
    assert res["correct"], text
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}


def test_traced_run_reads_the_counters(root):
    """Off the chip the trace has no device plane with shapes, so the
    device readers give nothing or zero and do not raise; the counter
    readers read the program's samples."""
    res, text = _run(root, trace=1)
    assert res["correct"], text
    m = res["metrics"]
    assert 0 < m["experts_hit_pct.decode"]["value"] <= 100
    assert m["expert_load_max_over_mean"]["value"] >= 1.0
    assert 0 < m["kv_live_pct"]["value"] <= 100
    assert "ssm_state_roofline_pct.decode" not in m
    assert "kda_time_share_pct" not in m and "mla_time_share_pct" not in m


def _served(cfg, seed=5):
    from paddle_tpu.generation import GenerationEngine

    eng = GenerationEngine(
        _mod("build").model(cfg, seed), slots=2, cache_len=128,
        prefill_buckets=(16, 32, 64), temperature=0.0, top_k=0,
        kv_cache_layout="ring", kv_cache_dtype="float32")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(3, cfg["vocab_size"], size=n).tolist()
               for n in (9, 21, 30)]
    outs = eng.generate(prompts, max_new_tokens=16, stop_at_eos=False)
    return [{"prompt": p, "tokens": o} for p, o in zip(prompts, outs)]


def _fails(cfg, got):
    lim = cfg["check"]
    return got["gap_max"] > lim["gap_max"] \
        or got["err_scale"] > lim["err_scale"]


def test_float8_control_fails_the_check():
    """The reference one precision down, put in the program's place,
    does not pass limits the program passes."""
    cfg = config()
    served, control = _mod("check").gaps(cfg, 5, _served(cfg), control=True)
    assert not _fails(cfg, served) and _fails(cfg, control)


def _padding_advances_the_state(monkeypatch):
    from paddle_tpu.nn.state_space import Mamba2Mixer

    sound = Mamba2Mixer.forward
    monkeypatch.setattr(
        Mamba2Mixer, "forward",
        lambda self, x, cache=None, valid=None: sound(self, x, cache=cache))


def _step_decays_twice(monkeypatch):
    from paddle_tpu.nn import state_space

    sound = state_space.ssm_step
    monkeypatch.setattr(
        state_space, "ssm_step",
        lambda s, x, b, c, dt, a, d: sound(s, x, b, c, dt, 2.0 * a, d))


def _experts_weighted_before_the_square(monkeypatch):
    from paddle_tpu.parallel import moe

    sound = moe.RoutedExperts.route

    def unscaled(self, x):
        idx, w = sound(self, x)
        return idx, w / self.routed_scaling_factor

    monkeypatch.setattr(moe.RoutedExperts, "route", unscaled)


@pytest.mark.parametrize("plant", [
    _padding_advances_the_state, _step_decays_twice,
    _experts_weighted_before_the_square])
def test_a_planted_fault_fails_the_check(plant, monkeypatch):
    """Each fault in the program alone: the served tokens no longer
    pass limits that the sound program passes (the test above)."""
    cfg = config()
    plant(monkeypatch)
    got = _mod("check").gaps(cfg, 5, _served(cfg))
    assert _fails(cfg, got), got


def test_state_readers_on_written_out_events_and_on_an_empty_trace():
    """The recurrence's time share and the state pass's roofline go by
    operand shape (as the compiler keeps them: my AOT compile, PR 40): in
    a decode run one fusion a state layer that reads and writes all 64
    states, beside a matrix product; in a prefill run the chunk x chunk
    decay, the chunk borders' states and the admission's write of a
    slot, beside an expert product."""
    cfg = common.load_json(_path("configs", NAME, "config.json"))
    oc = common.load_module(_path("opcount", "nemotron_h.py"))
    us = 1e3
    evs = [
        ("fusion.26", 0.0, 800 * us,
         "%fusion.26 = (f32[64,128,64]{2,1,0}, f32[64,128,64,128]{3,2,1,0}) "
         "fusion(f32[64,128,128] %b, f32[64,128,128] %c, f32[64,128,64] %x, "
         "f32[64,128,64,128] %state, f32[64,128] %decay)"),
        ("fusion.3", 800 * us, 200 * us,
         "%fusion.3 = bf16[64,18560]{1,0} fusion(bf16[64,4096] %h, "
         "bf16[4096,18560] %w)"),
        ("fusion.7", 2000 * us, 300 * us,
         "%fusion.7 = f32[16,128,128,8,16]{4,3,2,1,0} fusion("
         "f32[1,16,128,128] %cum)"),
        ("fusion.8", 2300 * us, 100 * us,
         "%fusion.8 = f32[16,1,128,64,128]{4,3,2,1,0} fusion("
         "f32[16,8,16,64,128] %add)"),
        ("dynamic_update_slice.9", 2400 * us, 50 * us,
         "%dynamic_update_slice.9 = f32[64,128,64,128]{3,2,1,0} "
         "dynamic-update-slice(f32[64,128,64,128] %all, "
         "f32[1,128,64,128] %one)"),
        ("ragged-dot-none.5", 2450 * us, 550 * us,
         "%ragged-dot-none.5 = bf16[22528,2688]{1,0} custom-call("
         "bf16[22528,1024] %x, bf16[128,1024,2688] %w)"),
    ]
    assert [oc.is_state_op(e[3], cfg) for e in evs] == [
        True, False, True, True, True, False]
    assert [oc.is_expert_kernel(e[0], e[3]) for e in evs] == [False] * 5 \
        + [True]
    tr = tracing.DeviceTrace({
        "devices": {"/device:TPU:0": evs}, "marks": [],
        "modules": {"/device:TPU:0": [
            ("jit__decode_pure(1)", 0.0, 1000 * us),
            ("jit__kinds_prefill_pure(2)", 2000 * us, 1000 * us)]}})

    class Cell:
        dir = tiny.BENCH
    Cell.cfg = cfg
    peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    ctx = {"cell": Cell, "trace": tr, "peaks": peaks,
           "res": {"window": (0.0, 1e9), "slots": 64}}
    share = common.load_module(_path("layer_metrics",
                                     "ssm_time_share_pct.py"))
    roof = common.load_module(_path("layer_metrics",
                                    "ssm_state_roofline_pct.decode.py"))
    assert share.read(ctx) == pytest.approx(100 * 1250 / 2000)
    least = 64 * 5 * 4194304 * 2 / 819e9          # 3.28 ms for five layers
    assert roof.read(ctx) == pytest.approx(100 * least / 800e-6)
    empty = tracing.DeviceTrace({"devices": {}, "marks": [], "modules": {}})
    ctx = dict(ctx, trace=empty)
    assert share.read(ctx) is None and roof.read(ctx) is None

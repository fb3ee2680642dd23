"""`longcat-flash-omni` at a tiny size on the CPU: the configuration's
files against the catalog's row and the floors of a cut, the opcount
against the built model and ISSUE 36's arithmetic, the plain reference
against the program's model, the cell end to end through the harness (a
sound run is `correct`), the traced run's counter readers, the shape
readers on events written out here, and the check against the float8
control and three planted faults. The tiny size is this file's own."""
import io
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.lib import common, tracing
from benchmark.tests import tiny

NAME = "longcat-flash-omni"
CELL = NAME + ".longreply-overload"
SIZES = dict(
    hidden_size=32, ffn_hidden_size=48, expert_ffn_hidden_size=16,
    num_layers=2, num_attention_heads=4, kv_lora_rank=12, q_lora_rank=16,
    qk_rope_head_dim=4, v_head_dim=8, qk_nope_head_dim=8, vocab_size=64,
    n_routed_experts=4, experts_held=[4, 4], zero_expert_num=8, moe_topk=6,
    published=dict(num_layers=28, n_routed_experts=16, vocab_size=97),
    assumed_sizes=dict(initializer_range=0.2), program_dtype="float32")
MIX = dict(rate_per_s=4.0, context_limit=128, drain_s=30.0,
           backlog_at_start=4,
           prompt_tokens=dict(median=20, sigma=0.5, min=9, max=60),
           output_tokens=dict(median=8, sigma=0.5, min=2, max=16),
           check_requests=8, trace_after_s=0.3, trace_s=1.5)


def _path(*parts):
    return os.path.join(tiny.BENCH, *parts)


def config():
    cfg = common.load_json(_path("configs", NAME, "config.json"))
    cfg.update(SIZES)
    cfg["engine"] = dict(cfg["engine"], slots=4, cache_len=128,
                         prefill_buckets=[16, 32, 64],
                         kv_cache_dtype="float32")
    cfg["check"] = dict(cfg["check"], gap_mean=2e-4, err_scale=2e-4,
                        min_tokens=8, requests=8, score_lengths=[64, 128],
                        score_rows=16)
    return cfg


def _mod(name):
    return common.load_module(_path("configs", NAME, name + ".py"))


def test_the_configuration_keeps_every_published_key():
    """Every number of the catalog's row is in the file under its own
    key, but for the three keys `reduced` names, which `published`
    keeps; the floors of a cut hold; the reference imports nothing of
    the program; the traffic fits the engine and the check."""
    cfg = common.load_json(_path("configs", NAME, "config.json"))
    bench = common.load_json(os.path.join(tiny.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == list(cfg["published"])
    assert entry["source"] == cfg["source"]
    want = dict(
        attention_bias=False, hidden_size=6144, ffn_hidden_size=12288,
        expert_ffn_hidden_size=2048, num_attention_heads=64,
        kv_lora_rank=512, q_lora_rank=1536, qk_rope_head_dim=64,
        v_head_dim=128, qk_nope_head_dim=128, mla_scale_q_lora=True,
        mla_scale_kv_lora=True, routed_scaling_factor=6,
        max_position_embeddings=131072, rms_norm_eps=1e-5,
        rope_theta=10000000, attention_method="MLA", zero_expert_num=256,
        zero_expert_type="identity", moe_topk=12)
    assert {k: cfg[k] for k in want} == want
    assert cfg["published"] == dict(num_layers=28, n_routed_experts=512,
                                    vocab_size=131072)
    assert cfg["num_layers"] >= 4
    assert cfg["n_routed_experts"] == cfg["experts_held"][1] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    for key in ("deployment", "changed", "assumed", "precision", "engine",
                "opcount", "check"):
        assert cfg[key]
    with open(_path("configs", NAME, "reference.py")) as f:
        assert "paddle_tpu" not in f.read()
    mix = common.load_json(_path("traffic", "longreply-overload.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and mix["kind"] == "open_loop_http"
    assert mix["context_limit"] == cfg["engine"]["cache_len"]
    assert mix["prompt_tokens"]["max"] <= max(cfg["engine"]["prefill_buckets"])
    assert mix["output_tokens"]["max"] <= cfg["check"]["score_rows"]
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= max(cfg["check"]["score_lengths"])
    assert mix["queue_capacity"] > mix["rate_per_s"] * bench["run_seconds"]
    for m in bench["per_layer"]:
        if m["name"] in ("mla_time_share_pct", "mla_decode_roofline_pct",
                         "zero_expert_pair_share_pct"):
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"


def test_opcount_counts_the_built_models_parameters():
    cfg = config()
    oc = common.load_module(_path("opcount", "longcat_flash.py"))
    m = _mod("build").model(cfg, 3)
    built = sum(int(np.prod(p._array.shape))
                for _, p in m.named_parameters())
    assert oc.param_count(cfg) == built
    ref = _mod("reference")
    assert sum(int(np.prod(s)) for s in ref.leaf_shapes(cfg).values()) \
        == built
    # at the published widths: ISSUE 36's arithmetic (it leaves out the
    # norms' gains and the selection bias, 0.1 M in all)
    real = common.load_json(_path("configs", NAME, "config.json"))
    assert round(oc.mla_params(real) / 1e5) == 906            # 90.6 M
    assert oc.expert_params(real) == 37748736                 # 37.75 M
    assert round(oc.param_count(real) / 1e6) == 5173          # 5,172.8 M
    assert oc.latent_row_bytes(real) == 1152
    assert oc.kv_bytes_per_token(real) == 9216
    e = real["engine"]
    assert round(e["slots"] * e["cache_len"] * 9216 / 1e7) == 242  # 2.42 GB
    assert 6.3 < oc.expected_experts_hit(real, 32) < 6.4     # 40 % of 16
    # a row's operations a byte: 121, under the v5e's ridge of 240
    peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    assert oc.mla_decode_least_s(real, 1e6, peaks) == 1e6 * 1152 / 819e9
    assert round(64 * 1088 * 2 / 1152) == 121


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
    path = os.path.join(b, "traffic", "longreply-overload.json")
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
    assert 0 < m["zero_expert_pair_share_pct"]["value"] < 100
    assert "kda_time_share_pct" not in m
    assert "attn_full_time_share_pct" not in m


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
    return got["gap_mean"] > lim["gap_mean"] \
        or got["err_scale"] > lim["err_scale"]


def test_float8_control_fails_the_check():
    """The reference one precision down, put in the program's place,
    does not pass limits the program passes."""
    cfg = config()
    served, control = _mod("check").gaps(cfg, 5, _served(cfg), control=True)
    assert not _fails(cfg, served) and _fails(cfg, control)


def _step_reads_the_other_ring(monkeypatch):
    from paddle_tpu.models import longcat_flash

    sound = longcat_flash.LongcatDecoderLayer.forward

    def swapped(self, x, caches=None, mask=None, positions=None, valid=None):
        if caches is not None and x.shape[1] == 1:
            caches = caches[::-1]
        return sound(self, x, caches, mask, positions, valid)

    monkeypatch.setattr(longcat_flash.LongcatDecoderLayer, "forward",
                        swapped)


def _absorbed_view_off_by_a_head(monkeypatch):
    from paddle_tpu.nn import mla

    sound = mla.CachedLatentAttention._kvb

    def rolled(self):
        w = sound(self)
        return jnp.concatenate([w[..., :self.nope],
                                jnp.roll(w[..., self.nope:], 1, axis=1)], -1)

    absorbed = mla.CachedLatentAttention.absorbed

    def off(self, *a):
        monkeypatch.setattr(mla.CachedLatentAttention, "_kvb", rolled)
        try:
            return absorbed(self, *a)
        finally:
            monkeypatch.setattr(mla.CachedLatentAttention, "_kvb", sound)

    monkeypatch.setattr(mla.CachedLatentAttention, "absorbed", off)


def _zero_experts_left_out(monkeypatch):
    from paddle_tpu.parallel import moe

    sound = moe.RoutedExperts.forward

    def without(self, x, valid=None):
        n, self.zero_experts = self.zero_experts, 0
        try:
            return sound(self, x, valid)
        finally:
            self.zero_experts, self.last_zero = n, jnp.zeros((), jnp.int32)

    monkeypatch.setattr(moe.RoutedExperts, "forward", without)


@pytest.mark.parametrize("plant", [
    _step_reads_the_other_ring, _absorbed_view_off_by_a_head,
    _zero_experts_left_out])
def test_a_planted_fault_fails_the_check(plant, monkeypatch):
    """Each fault in the program alone: the served tokens no longer
    pass limits that the sound program passes (the test above)."""
    cfg = config()
    plant(monkeypatch)
    got = _mod("check").gaps(cfg, 5, _served(cfg))
    assert _fails(cfg, got), got


def test_shape_readers_on_written_out_events():
    """The latent attention's time share and its decode roofline go by
    operand shape (as the compiler keeps them: my AOT compile, PR 36): a
    decode run with the ring's row write, the scores of all heads over a
    key chunk and one matrix product; a prefill score block outside
    it."""
    cfg = common.load_json(_path("configs", NAME, "config.json"))
    oc = common.load_module(_path("opcount", "longcat_flash.py"))
    us = 1e3
    evs = [
        ("dynamic_update_slice.256", 0.0, 10 * us,
         "%dynamic_update_slice.256 = bf16[32,1,8192,576]{2,3,0,1} "
         "dynamic-update-slice(bf16[32,1,8192,576] %ring, bf16[1,1,1,576]"
         " %row)"),
        ("fusion.947", 10 * us, 40 * us,
         "%fusion.947 = (f32[32,64]{1,0}, f32[32,64,4096]{2,1,0}) fusion("
         "f32[32,4096] %mask, bf16[32,64,576] %q)"),
        ("fusion.3", 50 * us, 50 * us,
         "%fusion.3 = bf16[32,6144]{1,0} fusion(bf16[32,12288] %h, "
         "bf16[12288,6144] %w)"),
        ("fusion.4", 200 * us, 30 * us,
         "%fusion.4 = f32[64,256,3840]{2,1,0} fusion(bf16[64,256,192] %q, "
         "bf16[64,3840,192] %k)"),
        ("fusion.5", 230 * us, 20 * us,
         "%fusion.5 = bf16[4096,6144]{1,0} fusion(bf16[4096,8192] %o)"),
    ]
    assert [oc.is_mla_op(e[3], cfg) for e in evs] == [
        True, True, False, True, False]
    tr = tracing.DeviceTrace({
        "devices": {"/device:TPU:0": evs}, "marks": [],
        "modules": {"/device:TPU:0": [
            ("jit__decode_pure(1)", 0.0, 100 * us),
            ("jit__prefill_pure(2)", 200 * us, 50 * us)]}})

    class Cell:
        dir = tiny.BENCH
    Cell.cfg = cfg
    peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    ctx = {"cell": Cell, "trace": tr, "peaks": peaks,
           "res": {"window": (0.0, 1e9), "slots": 32}}
    share = common.load_module(_path("layer_metrics",
                                     "mla_time_share_pct.py"))
    assert share.read(ctx) == pytest.approx(100 * 80 / 150)
    from paddle_tpu import profiler

    roof = common.load_module(_path("layer_metrics",
                                    "mla_decode_roofline_pct.py"))
    zero = common.load_module(_path("layer_metrics",
                                    "zero_expert_pair_share_pct.py"))
    profiler.reset_profiler()
    # no samples (the parent's program has none): nothing, and no raise
    assert roof.read(ctx) is None and zero.read(ctx) is None
    profiler.start_profiler(state="CPU")
    try:
        profiler.record_counter("generation::kv_rows_read",
                                [0, 0, 8 * 32 * 2000])
        profiler.record_counter("generation::kv_rows_read", [5, 7])
        profiler.record_counter("moe::zero_pairs", [120, 130, 128, 134])
    finally:
        profiler.stop_profiler()
    try:
        least = 8 * 32 * 2000 * 1152 / 819e9
        assert roof.read(ctx) == pytest.approx(100 * least / 50e-6)
        assert zero.read(ctx) == pytest.approx(100 * 128 / (12 * 32))
    finally:
        profiler.reset_profiler()

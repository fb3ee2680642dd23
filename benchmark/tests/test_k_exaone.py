"""`k-exaone-236b` at a tiny size on the CPU: the configuration's files
against the catalog's rules, the opcount against the built model, the
plain reference against the program's model, the cell end to end through
the harness (a sound run is `correct`), the traced run's counter
readers, the shape readers on events written out here, and the check
against the float8 control and three planted faults. The tiny size is
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

CELL = "k-exaone-236b.longdoc-overload"
WINDOW = 8
SIZES = dict(
    hidden_size=32, num_attention_heads=4, head_dim=8,
    num_key_value_heads=2, vocab_size=64, intermediate_size=48,
    moe_intermediate_size=16, sliding_window=WINDOW, num_experts=4,
    experts_held=[4, 4], num_experts_per_tok=2, num_shared_experts=1,
    published=dict(num_hidden_layers=48, num_experts=16, vocab_size=97,
                   num_nextn_predict_layers=1),
    assumed_sizes=dict(shared_expert_width=16, initializer_range=0.2),
    program_dtype="float32")
MIX = dict(rate_per_s=4.0, context_limit=128, drain_s=30.0,
           backlog_at_start=4,
           prompt_tokens=dict(median=20, sigma=0.5, min=9, max=60),
           output_tokens=dict(median=8, sigma=0.5, min=2, max=16),
           check_requests=8, trace_after_s=0.3, trace_s=1.5)


def _path(*parts):
    return os.path.join(tiny.BENCH, *parts)


def config():
    cfg = common.load_json(_path("configs", "k-exaone-236b", "config.json"))
    cfg.update(SIZES)
    cfg["engine"] = dict(cfg["engine"], slots=4, cache_len=128,
                         prefill_buckets=[16, 32, 64],
                         kv_cache_dtype="float32")
    cfg["check"] = dict(cfg["check"], gap_mean=2e-4, err_scale=2e-4,
                        min_tokens=8, requests=8, score_lengths=[64, 128],
                        score_rows=16)
    return cfg


def _mod(name):
    return common.load_module(_path("configs", "k-exaone-236b",
                                    name + ".py"))


def test_the_configuration_keeps_every_published_width():
    """Every number of the published config is in the file under its own
    key, but for the four keys `reduced` names, which `published` keeps;
    the floors of a cut hold; the reference imports nothing of the
    program."""
    cfg = common.load_json(_path("configs", "k-exaone-236b", "config.json"))
    bench = common.load_json(os.path.join(tiny.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == list(cfg["published"])
    assert entry["source"] == cfg["source"]
    want = dict(hidden_size=6144, num_attention_heads=64, head_dim=128,
                num_key_value_heads=8, intermediate_size=18432,
                moe_intermediate_size=2048, num_experts_per_tok=8,
                sliding_window=128, first_k_dense_replace=1,
                num_shared_experts=1, routed_scaling_factor=2.5,
                max_position_embeddings=262144, rms_norm_eps=1e-5)
    assert {k: cfg[k] for k in want} == want
    assert cfg["published"] == dict(num_hidden_layers=48, num_experts=128,
                                    vocab_size=153600,
                                    num_nextn_predict_layers=1)
    layers = cfg["num_hidden_layers"]
    assert len(cfg["layer_types"]) == 48
    assert cfg["layer_types"][:layers].count("full_attention") == 1
    assert cfg["mlp_layer_types"][:layers] == ["dense"] + ["sparse"] * 4
    assert cfg["num_experts"] == cfg["experts_held"][1] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    with open(_path("configs", "k-exaone-236b", "reference.py")) as f:
        assert "paddle_tpu" not in f.read()
    mix = common.load_json(_path("traffic", "longdoc-overload.json"))
    assert mix["prompt_tokens"]["min"] > cfg["sliding_window"]
    assert mix["context_limit"] == cfg["engine"]["cache_len"]
    assert mix["output_tokens"]["max"] <= cfg["check"]["score_rows"]


def test_opcount_counts_the_built_models_parameters():
    cfg = config()
    oc = common.load_module(_path("opcount", "k_exaone.py"))
    m = _mod("build").model(cfg, 3)
    built = sum(int(np.prod(p._array.shape))
                for _, p in m.named_parameters())
    assert oc.param_count(cfg) == built
    ref = _mod("reference")
    assert sum(int(np.prod(s)) for s in ref.leaf_shapes(cfg).values()) \
        == built
    # at the published widths: the issue's arithmetic
    real = common.load_json(_path("configs", "k-exaone-236b", "config.json"))
    assert round(oc.param_count(real) / 1e6) == 3712
    assert round(2 * oc.dense_params(real) / 1e7) == 236      # 2.36 GB
    assert oc.kv_bytes_per_token(real) == 4096
    assert oc.window_bytes_per_slot(real) == 4 * 128 * 4096
    assert 13.9 < oc.expected_experts_hit(real, 32) < 14.0
    assert 7.6e9 < oc.decode_bytes(real, 32 * 8000) < 7.8e9


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
    with open(os.path.join(b, "configs", "k-exaone-236b",
                           "config.json"), "w") as f:
        json.dump(config(), f)
    path = os.path.join(b, "traffic", "longdoc-overload.json")
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
    assert "kda_time_share_pct" not in m


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


def _band_one_key_too_wide(monkeypatch):
    from paddle_tpu.nn import gqa

    sound = gqa.CachedGQAttention.forward

    def wide(self, x, cache=None, mask=None, positions=None):
        if self.window is None or (cache is not None and x.shape[1] == 1):
            return sound(self, x, cache, mask, positions)
        self.window += 1    # the prefill's band; the ring stays 8 rows
        try:
            return sound(self, x, cache, mask, positions)
        finally:
            self.window -= 1

    monkeypatch.setattr(gqa.CachedGQAttention, "forward", wide)


def _rotary_left_off_the_ring_write(monkeypatch):
    from paddle_tpu.nn import gqa

    sound = gqa.apply_rotary
    calls = []

    def only_q(x, positions, theta):
        calls.append(0)       # q is rotated first, k second
        decode_k = len(calls) % 2 == 0 and x.shape[1] == 1
        return x if decode_k else sound(x, positions, theta)

    monkeypatch.setattr(gqa, "apply_rotary", only_q)


def _dense_layer_skipped(monkeypatch):
    from paddle_tpu.models import exaone_moe

    monkeypatch.setattr(exaone_moe.DenseSwiGLU, "forward",
                        lambda self, x: jnp.zeros_like(x))


@pytest.mark.parametrize("plant", [
    _band_one_key_too_wide, _rotary_left_off_the_ring_write,
    _dense_layer_skipped])
def test_a_planted_fault_fails_the_check(plant, monkeypatch):
    """Each fault in the program alone: the served tokens no longer
    pass limits that the sound program passes (the test above)."""
    cfg = config()
    plant(monkeypatch)
    got = _mod("check").gaps(cfg, 5, _served(cfg))
    assert _fails(cfg, got), got


def test_shape_readers_on_written_out_events():
    """The two time shares and the K/V roofline go by operand shape: a
    decode run with one event on the full ring, one on a window ring and
    one matrix product; a prefill block of each kind outside it."""
    cfg = common.load_json(_path("configs", "k-exaone-236b", "config.json"))
    oc = common.load_module(_path("opcount", "k_exaone.py"))
    us = 1e3
    evs = [
        ("fusion.1", 0.0, 50 * us,
         "%fusion.1 = f32[32,8,8,16384]{3,2,1,0} fusion(bf16[32,8,8,128]"
         " %q, bf16[32,8,16384,128]{3,2,1,0} %k)"),
        ("fusion.2", 50 * us, 10 * us,
         "%fusion.2 = bf16[32,8,8,128]{3,2,1,0} fusion(f32[32,8,8,128]"
         " %p, bf16[32,8,128,128]{3,2,1,0} %v)"),
        ("fusion.3", 60 * us, 40 * us,
         "%fusion.3 = bf16[32,6144]{1,0} fusion(bf16[32,8192] %o)"),
        ("fusion.4", 200 * us, 30 * us,
         "%fusion.4 = f32[1,8,8,128,4096]{4,3,2,1,0} fusion(bf16[1,8,8,128,"
         "128] %q)"),
        ("fusion.5", 230 * us, 20 * us,
         "%fusion.5 = f32[1,8,8,512,639]{4,3,2,1,0} fusion(bf16[1,8,8,512,"
         "128] %q)"),
    ]
    assert [oc.is_full_attn_op(e[3], cfg) for e in evs] == [
        True, False, False, True, False]
    assert [oc.is_window_attn_op(e[3], cfg) for e in evs] == [
        False, True, False, False, True]
    tr = tracing.DeviceTrace({
        "devices": {"/device:TPU:0": evs}, "marks": [],
        "modules": {"/device:TPU:0": [
            ("jit__decode_pure(1)", 0.0, 100 * us),
            ("jit__prefill_pure(2)", 200 * us, 50 * us)]}})

    class Cell:
        dir = tiny.BENCH
    Cell.cfg = cfg
    ctx = {"cell": Cell, "trace": tr, "peaks": {"hbm_bytes_per_s": 819e9},
           "res": {"window": (0.0, 1e9)}}
    full = common.load_module(_path("layer_metrics",
                                    "attn_full_time_share_pct.py"))
    window = common.load_module(_path("layer_metrics",
                                      "attn_window_time_share_pct.py"))
    assert full.read(ctx) == pytest.approx(100 * 80 / 150)
    assert window.read(ctx) == pytest.approx(100 * 30 / 150)
    from paddle_tpu import profiler

    kv = common.load_module(_path("layer_metrics",
                                  "kv_read_roofline_pct.decode.py"))
    profiler.reset_profiler()
    assert kv.read(ctx) is None      # no samples: nothing, and no raise
    profiler.start_profiler(state="CPU")
    try:
        profiler.record_counter("generation::kv_rows_read",
                                [32 * 8000, 4 * 32 * 128])
    finally:
        profiler.stop_profiler()
    try:
        least = (32 * 8000 + 4 * 32 * 128) * 4096 / 819e9
        assert kv.read(ctx) == pytest.approx(100 * least / 60e-6)
    finally:
        profiler.reset_profiler()

"""`minicpm-sala-9b` at a tiny size on the CPU: the configuration's files
against the catalog's row and the floors of a cut, the opcount against
the built model and ISSUE 49's arithmetic, the plain reference against
the program's model, the cell end to end through the harness (a sound
run is `correct`), the traced run's counter readers, the four new
readers on events written out here and on an empty trace, and the check
against the float8 control and planted faults. The tiny size is this
file's own (the sparse sizes keep the published ratios: stride 2, kernel
4, block 8, window 16, top-6, dense_len 64)."""
import io
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.lib import common, tracing
from benchmark.tests import tiny

NAME = "minicpm-sala-9b"
CELL = NAME + ".longctx-overload"
SPARSE = dict(kernel_size=4, kernel_stride=2, block_size=8, init_blocks=1,
              window_size=16, topk=6, dense_len=64)
SIZES = dict(
    hidden_size=32, intermediate_size=64, num_attention_heads=8,
    num_key_value_heads=2, head_dim=8, lightning_nh=4, lightning_nkv=4,
    lightning_head_dim=8, dim_model_base=8, vocab_size=64,
    sparse_config=SPARSE,
    assumed_sizes=dict(SPARSE, initializer_range=0.2),
    program_dtype="float32")
MIX = dict(rate_per_s=4.0, context_limit=128, drain_s=60.0,
           backlog_at_start=2,
           prompt_tokens=dict(median=50, sigma=0.5, min=9, max=100),
           output_tokens=dict(median=10, sigma=0.5, min=2, max=16),
           check_requests=6, trace_after_s=0.3, trace_s=1.5)
PUBLISHED_MIXERS = ["minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31)
                    else "lightning-attn" for i in range(32)]


def _path(*parts):
    return os.path.join(tiny.BENCH, *parts)


def config():
    cfg = common.load_json(_path("configs", NAME, "config.json"))
    cfg.update(SIZES)
    cfg["engine"] = dict(cfg["engine"], slots=6, cache_len=128,
                         prefill_buckets=[16, 32, 64, 128],
                         kv_cache_dtype="float32")
    cfg["check"] = dict(cfg["check"], gap_max=2e-3, err_scale=2e-4,
                        chosen_set_diff_share=0.02, min_tokens=8,
                        requests=6, score_lengths=[64, 128], score_rows=16)
    return cfg


def _mod(name):
    return common.load_module(_path("configs", NAME, name + ".py"))


def test_the_configuration_keeps_every_published_key():
    """Every number of the catalog's row is in the file under its own
    key, but for the two keys `reduced` names, which `published` keeps;
    the floors of a cut hold; the reference imports nothing of the
    program; the traffic fits the engine and the check."""
    cfg = common.load_json(_path("configs", NAME, "config.json"))
    bench = common.load_json(os.path.join(tiny.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == list(cfg["published"])
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    want = dict(
        attention_bias=False, attn_use_rope=False, head_dim=128,
        hidden_act="silu", hidden_size=4096, intermediate_size=16384,
        lightning_head_dim=128, lightning_nh=32, lightning_nkv=32,
        lightning_scale="1/sqrt(d)", lightning_use_rope=True,
        max_position_embeddings=524288, model_type="minicpm_sala",
        num_attention_heads=32, num_key_value_heads=2, qk_norm=True,
        rand_init=False, rms_norm_eps=1e-6, vocab_size=73448,
        rope_theta=10000, scale_emb=12, scale_depth=1.4, mup_denominator=32,
        dim_model_base=256, tie_word_embeddings=False, use_output_gate=True,
        use_output_norm=True, attn_use_output_gate=True)
    assert {k: cfg[k] for k in want} == want
    assert cfg["published"] == dict(num_hidden_layers=32,
                                    mixer_types=PUBLISHED_MIXERS)
    # eight consecutive published layers, two periods' worth at the
    # published 1 : 3, every width and the whole vocabulary as published
    off = cfg["layer_offset"]
    assert cfg["mixer_types"] == PUBLISHED_MIXERS[off:off + 8]
    assert len(cfg["mixer_types"]) == cfg["num_hidden_layers"] == 8 >= 4
    assert [cfg["mixer_types"].count(k) for k in (
        "minicpm4", "lightning-attn")] == [2, 6]
    assert [PUBLISHED_MIXERS.count(k) for k in (
        "minicpm4", "lightning-attn")] == [8, 24]
    sc = cfg["sparse_config"]
    assert sc == {k: cfg["assumed_sizes"][k] for k in sc}
    assert sc["kernel_size"] == 2 * sc["kernel_stride"]
    assert sc["block_size"] == 4 * sc["kernel_stride"]
    assert sc["dense_len"] >= sc["topk"] * sc["block_size"]
    for key in ("deployment", "changed", "assumed", "assumed_sizes",
                "precision", "engine", "opcount", "check"):
        assert cfg[key]
    assert all("lternative" in cfg["assumed"][k] for k in (
        "sparse_config", "topk_counts_forced_blocks", "pooled_softmax",
        "dense_len", "attn_gate", "lightning_slopes", "lightning_qkv",
        "lightning_output_norm", "scalings"))
    with open(_path("configs", NAME, "reference.py")) as f:
        assert "paddle_tpu" not in f.read()
    mix = common.load_json(_path("traffic", "longctx-overload.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and mix["kind"] == "open_loop_http"
    assert len(cell["why"]) <= 200
    e, chk = cfg["engine"], cfg["check"]
    assert mix["context_limit"] == e["cache_len"] == 32768
    assert e["cache_len"] % sc["block_size"] == 0
    assert mix["prompt_tokens"]["max"] <= max(e["prefill_buckets"])
    assert mix["prompt_tokens"]["min"] < sc["dense_len"] \
        < mix["prompt_tokens"]["median"]
    assert mix["output_tokens"]["max"] <= chk["score_rows"]
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= max(chk["score_lengths"]) <= e["cache_len"]
    assert min(chk["score_lengths"]) < sc["dense_len"]
    assert sum(n > sc["dense_len"] for n in chk["score_lengths"]) >= 2
    assert all(n % sc["block_size"] == 0 for n in chk["score_lengths"])
    assert mix["queue_capacity"] > mix["rate_per_s"] * bench["run_seconds"]
    assert mix["backlog_at_start"] >= e["slots"] and mix["ignore_eos"]
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"].startswith(("sparse_", "lightning_"))}
    assert set(new) == {
        "sparse_attn_time_share_pct", "sparse_read_roofline_pct.decode",
        "sparse_blocks_read_pct.decode", "lightning_time_share_pct"}
    for m in new.values():
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
        assert m["layer"] == "model code"
    assert new["sparse_blocks_read_pct.decode"]["source"] == "program_counter"
    # every list the served cells share, but for the two readers that
    # need decode iterations INSIDE the 30 s window: prompts go in whole
    # and the window is one iteration that fills the slots (PERF.md)
    silent = {"host_gap_ms.serve", "trace_overhead_pct.serve"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "longcat-flash-omni.longreply-overload" in m.get("workloads", ()) \
                and not m["name"].startswith(("mla_", "zero_expert", "moe_",
                                              "expert")):
            assert (CELL in m["workloads"]) == (m["name"] not in silent), \
                m["name"]
    # the trace lies behind the fill: past the window, inside the drain
    assert bench["run_seconds"] < mix["trace_after_s"] \
        < mix["trace_after_s"] + mix["trace_s"] < mix["drain_s"]
    roof = next(m for m in bench["per_layer"]
                if m["name"] == "ssm_state_roofline_pct.decode")
    assert CELL in roof["workloads"]
    assert len(bench["workloads"]) == 8 and len(bench["configs"]) == 8
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_opcount_counts_the_built_models_parameters():
    cfg = config()
    oc = common.load_module(_path("opcount", "minicpm_sala.py"))
    m = _mod("build").model(cfg, 3)
    built = sum(int(np.prod(p._array.shape))
                for _, p in m.named_parameters())
    assert oc.param_count(cfg) == built
    ref = _mod("reference")
    assert sum(int(np.prod(s)) for s in ref.leaf_shapes(cfg).values()) \
        == built
    # at the published widths, by shape arithmetic, nothing allocated:
    # ISSUE 49's numbers
    real = common.load_json(_path("configs", NAME, "config.json"))
    assert sum(int(np.prod(s)) for s in ref.leaf_shapes(real).values()) \
        == oc.param_count(real)
    assert round(oc.param_count(real) / 1e6) == 2821            # 2,820.6 M
    assert round(oc.layer_params(real, "minicpm4") / 1e5) == 2538
    assert round(oc.layer_params(real, "lightning-attn") / 1e5) == 2852
    uncut = dict(real, **real["published"])
    assert round(oc.param_count(uncut) / 1e7) == 948            # 9.48 B
    assert oc.state_bytes_per_slot(real) == 6 * 2097152 \
        == oc.state_bytes_per_slot(real, tail=False)
    assert oc.kv_row_bytes(real) == 1024
    assert oc.kv_bytes_per_token(real) == 2 * (1024 + 32)
    e = real["engine"]
    slot = e["cache_len"] * oc.kv_bytes_per_token(real) \
        + oc.state_bytes_per_slot(real)
    assert round(slot / 1e5) == 818                             # 81.8 MB
    resident = 2 * oc.param_count(real) + e["slots"] * slot
    assert round(resident / 1e7) == 826                         # 8.26 GB
    # a step past dense_len reads 64 blocks and the pooled keys, not the
    # ring: 4,096 rows + half of 1,249 at 20,000 live rows
    assert oc.sparse_rows(real, 20000) == 63 * 64 + 19999 % 64 + 1 + 625
    assert oc.sparse_rows(real, 5000) == 5000
    assert oc.sparse_rows_bytes(real, 32 * 20000, 32) \
        == 2 * 32 * oc.sparse_rows(real, 20000) * 1024
    assert 6.0e9 < oc.decode_bytes(real, 32 * 20000) < 6.4e9
    whole_rings = 2 * 32 * 20000 * 1024
    assert oc.decode_bytes(real, 32 * 20000) < 2 * oc.dense_params(real) \
        + 2 * 32 * oc.state_bytes_per_slot(real) + whole_rings / 4


def test_reference_matches_program_model():
    """Full forward, float32 both sides, the benchmark's weights; `rows`
    and `detail` read the same pass."""
    cfg = config()
    build, ref = _mod("build"), _mod("reference")
    m = build.model(cfg, 11)
    w = ref.weights(cfg, common.seed_key(11))
    toks = np.random.default_rng(0).integers(3, cfg["vocab_size"], size=100)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg))
    got = np.asarray(m(jnp.asarray(toks[None]))._array[0])
    assert want.std() > 0.2
    np.testing.assert_allclose(got, want, atol=2e-4)
    some, seen = ref.forward(w, jnp.asarray(toks), cfg, rows=(60, 8),
                             detail=True)
    np.testing.assert_allclose(np.asarray(some), want[60:68], atol=1e-5)
    assert len(seen) == 2 and seen[0]["chosen"].shape == (8, 2, 13)
    # past dense_len a query keeps six blocks, the first and the two
    # newest among them
    kept = np.asarray(seen[0]["chosen"])
    assert (kept.sum(-1)[:3] == 8).all()              # 61, 62, 63 rows: all
    assert (kept.sum(-1)[3:] == 6).all()
    assert kept[4:, :, 0].all() and kept[4:, :, 7:9].all()


@pytest.fixture()
def root(tmp_path):
    root = tiny.checkout(tmp_path)
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", NAME, "config.json"), "w") as f:
        json.dump(config(), f)
    path = os.path.join(b, "traffic", "longctx-overload.json")
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
    """The configuration, the mix, the cell and the four readers are
    files and entries the harness finds by name."""
    res, text = _run(root)
    assert res["correct"], text
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert res["checks"]["chosen_set_diff_share"]["value"] == 0.0
    assert "chosen sets compared" in text


def test_traced_run_reads_the_counters(root):
    """Off the chip the trace has no device plane with shapes, so the
    device readers give nothing or zero and do not raise; the counter
    readers read the program's samples."""
    res, text = _run(root, trace=1)
    assert res["correct"], text
    m = res["metrics"]
    assert 0 < m["sparse_blocks_read_pct.decode"]["value"] <= 100
    assert 0 < m["kv_live_pct"]["value"] <= 100
    assert m["prefill_chunks_per_admission"]["value"] == 1.0
    assert "sparse_read_roofline_pct.decode" not in m
    assert "ssm_state_roofline_pct.decode" not in m
    assert "kda_time_share_pct" not in m and "moe_time_share_pct" not in m


def _served(cfg, seed=5):
    from paddle_tpu.generation import GenerationEngine

    eng = GenerationEngine(
        _mod("build").model(cfg, seed), slots=2, cache_len=128,
        prefill_buckets=(16, 32, 64, 128), temperature=0.0, top_k=0,
        kv_cache_layout="ring", kv_cache_dtype="float32")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(3, cfg["vocab_size"], size=n).tolist()
               for n in (9, 55, 90)]
    outs = eng.generate(prompts, max_new_tokens=16, stop_at_eos=False)
    return [{"prompt": p, "tokens": o} for p, o in zip(prompts, outs)]


def _fails(cfg, got):
    lim = cfg["check"]
    return got["gap_max"] > lim["gap_max"] \
        or got["err_scale"] > lim["err_scale"] \
        or got["chosen_set_diff_share"] > lim["chosen_set_diff_share"]


def test_float8_control_fails_the_check():
    """The reference one precision down, put in the program's place,
    does not pass limits the program passes."""
    cfg = config()
    served, control = _mod("check").gaps(cfg, 5, _served(cfg), control=True)
    assert not _fails(cfg, served) and _fails(cfg, control)
    assert served["chosen_sets"] == control["chosen_sets"] > 0


def _dense_for_sparse(monkeypatch):
    from paddle_tpu.nn import sparse_attention as sa

    monkeypatch.setattr(sa, "select_blocks", lambda q, pooled, t, cfg, s: (
        jnp.arange(pooled.shape[-2] * cfg.stride // cfg.block)
        <= t[..., None] // cfg.block))


def _no_forced_blocks(monkeypatch):
    from paddle_tpu.nn import sparse_attention as sa

    sound = sa.select_blocks
    monkeypatch.setattr(sa, "select_blocks", lambda q, p, t, cfg, s: sound(
        q, p, t, cfg._replace(init_blocks=0, window=0), s))


def _top_three(monkeypatch):
    from paddle_tpu.nn import sparse_attention as sa

    sound = sa.select_blocks
    monkeypatch.setattr(sa, "select_blocks", lambda q, p, t, cfg, s: sound(
        q, p, t, cfg._replace(topk=3), s))


def _step_decays_twice(monkeypatch):
    from paddle_tpu.nn import linear_attention as la

    sound = la.lightning_step
    monkeypatch.setattr(la, "lightning_step",
                        lambda s, q, k, v, g: sound(s, q, k, v, 2.0 * g))


def _padding_advances_the_state(monkeypatch):
    from paddle_tpu.nn.linear_attention import LightningAttention

    sound = LightningAttention.forward
    monkeypatch.setattr(
        LightningAttention, "forward",
        lambda self, x, positions, cache=None, valid=None: sound(
            self, x, positions, cache=cache))


@pytest.mark.parametrize("plant", [
    _dense_for_sparse, _no_forced_blocks, _top_three,
    _step_decays_twice, _padding_advances_the_state])
def test_a_planted_fault_fails_the_check(plant, monkeypatch):
    """Each fault in the program alone: the served tokens, or the
    program's selection on the reference's queries and keys, no longer
    pass limits that the sound program passes (the test above)."""
    cfg = config()
    check = _mod("check")
    monkeypatch.setattr(check, "_SCORERS", {})   # compiled with the fault
    plant(monkeypatch)
    got = check.gaps(cfg, 5, _served(cfg))
    assert _fails(cfg, got), got


def test_readers_on_written_out_events_and_on_an_empty_trace():
    """The four new readers and `ssm_state_roofline_pct.decode` go by
    operand shape (as the compiler keeps them: my AOT compile for a
    described v5e, PR 49) and by the program's counters: in a decode run
    one fusion a Lightning layer over all 32 states, the selection's
    scores against the pooled ring, its sort, the gathered blocks and
    the scores over them, beside a matrix product; in a prefill run a
    chunk's decay matrix and a query block's scores against a key
    chunk."""
    from paddle_tpu import profiler

    cfg = common.load_json(_path("configs", NAME, "config.json"))
    oc = common.load_module(_path("opcount", "minicpm_sala.py"))
    us = 1e3
    evs = [
        ("multiply_reduce_fusion.5", 0.0, 600 * us,
         "%multiply_reduce_fusion.5 = (f32[32,32,128]{2,1,0}, "
         "f32[32,32,128,128]{3,2,1,0}) fusion(f32[32,32,128] %q, "
         "f32[32,32,128,128] %state)"),
        ("fusion.57", 600 * us, 60 * us,
         "%fusion.57 = f32[32,2,16,2048]{3,2,1,0} fusion("
         "bf16[32,2,2048,128] %pooled, bf16[32,2,16,128] %q)"),
        ("sort.4", 660 * us, 20 * us,
         "%sort.4 = (f32[32,2,1,512]{3,1,0,2}, s32[32,2,1,512]{3,1,0,2}) "
         "sort(f32[32,2,1,512] %score, s32[32,2,1,512] %iota)"),
        ("broadcast_select_fusion.3", 680 * us, 200 * us,
         "%broadcast_select_fusion.3 = (bf16[32,2,128,64,128]{4,3,2,1,0}, "
         "bf16[32,2,128,64,128]{4,3,2,1,0}) fusion(bf16[32,2,512,64,128] %k)"),
        ("fusion.395", 880 * us, 120 * us,
         "%fusion.395 = f32[32,2,16,128]{3,2,1,0} fusion("
         "f32[32,2,16,8192] %p, bf16[32,2,128,64,128] %v)"),
        ("fusion.3", 1000 * us, 1000 * us,
         "%fusion.3 = bf16[32,16384]{1,0} fusion(bf16[32,4096] %h, "
         "bf16[4096,16384] %w)"),
        ("fusion.7", 3000 * us, 300 * us,
         "%fusion.7 = f32[1,32,256,256]{3,2,1,0} fusion(f32[1,32,256] %cum)"),
        ("fusion.8", 3300 * us, 500 * us,
         "%fusion.8 = f32[1,2,16,512,2048]{4,3,2,1,0} fusion("
         "bf16[1,2,16,512,128] %q, bf16[1,2,2048,128] %k)"),
        ("fusion.9", 3800 * us, 200 * us,
         "%fusion.9 = bf16[8192,16384]{1,0} fusion(bf16[8192,4096] %h, "
         "bf16[4096,16384] %w)"),
    ]
    assert [oc.is_state_op(e[3], cfg) for e in evs] == [
        True] + [False] * 5 + [True, False, False]
    # a prompt's [., 512, 2048] scores are the attention's at every
    # bucket and also the selection's at the 32,768 one (2,048 pooled
    # rows): either way the sparse layers' time, and counted once
    assert [oc.is_sparse_select_op(e[3], cfg) for e in evs] == [
        False, True, True] + [False] * 4 + [True, False]
    assert [oc.is_sparse_attend_op(e[3], cfg) for e in evs] == [
        False] * 3 + [True, True, False, False, True, False]
    tr = tracing.DeviceTrace({
        "devices": {"/device:TPU:0": evs}, "marks": [],
        "modules": {"/device:TPU:0": [
            ("jit__decode_pure(1)", 0.0, 2000 * us),
            ("jit__kinds_prefill_pure(2)", 3000 * us, 1000 * us)]}})

    class Cell:
        dir = tiny.BENCH
    Cell.cfg = cfg
    peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    ctx = {"cell": Cell, "trace": tr, "peaks": peaks, "spans": None,
           "clock_offset_ns": 0.0,
           "res": {"window": (0.0, 1e9), "slots": 32}}

    def reader(name):
        return common.load_module(_path("layer_metrics", name + ".py"))

    busy = 3000.0
    assert reader("lightning_time_share_pct").read(ctx) \
        == pytest.approx(100 * 900 / busy)
    assert reader("sparse_attn_time_share_pct").read(ctx) \
        == pytest.approx(100 * 900 / busy)
    least = 2 * 32 * 6 * 2097152 / 819e9               # 0.98 ms: six layers
    assert reader("ssm_state_roofline_pct.decode").read(ctx) \
        == pytest.approx(100 * least / 600e-6)
    # the counters: two iterations' samples on the host's clock
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    try:
        import time

        t0 = time.perf_counter_ns()
        for rows, blocks in ((300000, 4000), (340000, 4096)):
            profiler.record_counter("generation::kv_rows_read",
                                    [rows, 0, 0])
            profiler.record_counter("sparse::blocks_read", blocks)
            profiler.record_counter("sparse::blocks_live", 4 * blocks)
        t1 = time.perf_counter_ns()
        # the samples of the device-traced interval count, wherever the
        # measured window lies (this cell's lies before it): the trace
        # above is 4 ms long, put here around the samples
        ctx["res"]["window"] = (time.monotonic() - 9.0,
                                time.monotonic() - 8.0)
        ctx["clock_offset_ns"] = t0 - tr.t0
        tl = reader("host_gap_ms.serve")
        assert tl.traced_ns(ctx)[0] <= t0 < t1 < tl.traced_ns(ctx)[1]
        assert reader("sparse_blocks_read_pct.decode").read(ctx) \
            == pytest.approx(25.0)
        least = 320000 * 1024 / 819e9                  # 0.4 ms a step
        assert reader("sparse_read_roofline_pct.decode").read(ctx) \
            == pytest.approx(100 * least / 400e-6)
        # samples outside the traced interval: nothing
        ctx["clock_offset_ns"] = t0 - tr.t0 - 1e9
        assert reader("sparse_blocks_read_pct.decode").read(ctx) is None
        assert reader("sparse_read_roofline_pct.decode").read(ctx) is None
        ctx["clock_offset_ns"] = t0 - tr.t0
    finally:
        profiler.stop_profiler()
        profiler.reset_profiler()
    # no samples, no trace: nothing, and no error
    assert reader("sparse_blocks_read_pct.decode").read(ctx) is None
    assert reader("sparse_read_roofline_pct.decode").read(ctx) is None
    empty = tracing.DeviceTrace({"devices": {}, "marks": [], "modules": {}})
    ctx = dict(ctx, trace=empty)
    for name in ("lightning_time_share_pct", "sparse_attn_time_share_pct",
                 "sparse_read_roofline_pct.decode"):
        assert reader(name).read(ctx) is None

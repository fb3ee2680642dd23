"""Quantization end-to-end (ISSUE 11): int8 KV cache + quantized
gradient all-reduce + compile-accounting contracts.

Pins the three hot-path legs:
- **int8 KV cache** (``FLAGS_generation_kv_cache_dtype=int8``): ring
  write/read parity vs the f32 cache and the full forward at the
  documented envelope (incl. wraparound), the HBM claim measured on
  real arrays (>=3x fewer bytes at head_dim 16, >=1.8x slots at equal
  HBM), greedy-token agreement, and the compile-once discipline per
  dtype mode (distinct store signatures, zero steady-state compiles);
- **quantized all-reduce** (``FLAGS_quantized_allreduce``): blockwise
  quant round-trip bounds, zero-block safety, eager/sim parity, the
  >=3.5x traced-wire-byte cut certified from the collective ledger
  under a dp-8 mesh, and loss-curve convergence vs fp32 through the
  real ``TrainStepFn`` hook;
- **int8 serving programs**: flag-on/off numeric identity of the int8
  matmul (integer math — the pallas gate may never change numerics).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu import monitor, parallel, profiler
from paddle_tpu.distributed import quantized as qar
from paddle_tpu.framework import jit as fjit
from paddle_tpu.generation import (
    COMPILE_COUNTER,
    GenerationEngine,
    QuantizedStaticCache,
    cache_nbytes,
    init_cache,
    kv_bytes_per_token,
    layer_caches,
)
from paddle_tpu.generation import cache as C
from paddle_tpu.models import GPTForCausalLM, gpt_tiny_config
from paddle_tpu.nn.transformer import dequantize_kv, quantize_kv


def _tiny_lm(window=None, seed=3):
    paddle.seed(seed)
    cfg = gpt_tiny_config()
    cfg.attention_window = window
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture
def quantized_allreduce_flag():
    paddle.set_flags({"quantized_allreduce": True})
    yield
    paddle.set_flags({"quantized_allreduce": False})


# -- int8 KV cache -----------------------------------------------------------


def test_quantize_kv_roundtrip_bound_and_zero_vector():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 3, 5, 16).astype("f4") * 4)
    q, s = quantize_kv(x)
    assert str(q.dtype) == "int8" and s.shape == (2, 3, 5)
    back = np.asarray(dequantize_kv(q, s))
    # per-vector bound: half a step of that vector's own scale
    bound = np.asarray(s)[..., None] / 127 / 2 + 1e-6
    assert (np.abs(back - np.asarray(x)) <= bound).all()
    # an all-zero head vector must not produce NaN on dequant
    qz, sz = quantize_kv(jnp.zeros((1, 1, 1, 8)))
    assert np.isfinite(np.asarray(dequantize_kv(qz, sz))).all()
    assert np.asarray(dequantize_kv(qz, sz)).max() == 0.0


def test_int8_cache_state_shapes_and_bytes():
    kv = init_cache(2, 4, 2, 8, 16, dtype="int8")
    assert len(kv) == 5
    k, v, ks, vs, pos = kv
    # one leaf per layer and plane: nothing is stacked
    assert len(k) == len(v) == len(ks) == len(vs) == 2
    assert all(str(a.dtype) == "int8" and a.shape == (4, 2, 8, 16)
               for a in k + v)
    assert all(a.shape == (4, 2, 8) and str(a.dtype) == "float32"
               for a in ks + vs)
    caches = layer_caches(*kv)
    assert all(isinstance(c, QuantizedStaticCache) for c in caches)
    fp = init_cache(2, 4, 2, 8, 16)
    assert len(fp) == 3
    # the HBM claim, measured on the real arrays: (D+4)/(4D) at D=16
    ratio = cache_nbytes(fp) / cache_nbytes(kv)
    assert ratio > 3.0
    assert kv_bytes_per_token(2, 2, 16, "float32") == 2 * 2 * 2 * 64
    assert kv_bytes_per_token(2, 2, 16, "int8") == 2 * 2 * 2 * 20


def _incremental_logits(m, ids, cache_len, dtype):
    spec = m.cache_spec()
    kv = C.init_cache(spec[0], 1, spec[1], cache_len, spec[2], dtype=dtype)
    outs = []
    for t, tok in enumerate(ids):
        caches = C.layer_caches(*kv)
        mask = C.decode_mask(kv[-1], cache_len)
        logits, new_caches = m(
            np.asarray([[tok]], "int32"),
            position_ids=np.asarray([[t]], "int32"),
            attention_mask=jnp.asarray(mask), caches=caches)
        kv = C.unzip_layer_caches(new_caches) + (kv[-1] + 1,)
        outs.append(np.asarray(logits.numpy())[0, 0])
    return np.stack(outs)


def test_int8_cache_parity_vs_full_forward_including_wraparound():
    """int8 ring decode vs the fp32 full forward: within the documented
    envelope (5% of the logit scale) and argmax-agreeing at every
    position, including past the window where the ring wraps."""
    W = 6
    m = _tiny_lm(window=W)
    ids = np.random.RandomState(7).randint(3, 200, size=17)  # 17 >> 6
    full = np.asarray(m(np.asarray(ids)[None].astype("int32")).numpy())[0]
    inc8 = _incremental_logits(m, ids, cache_len=W, dtype="int8")
    scale = np.abs(full).max()
    assert np.abs(inc8 - full).max() < 0.05 * scale
    np.testing.assert_array_equal(inc8.argmax(-1), full.argmax(-1))
    # and the f32 ring stays the exact baseline the int8 one approximates
    inc32 = _incremental_logits(m, ids, cache_len=W, dtype="float32")
    assert np.abs(inc8 - inc32).max() < 0.05 * scale
    np.testing.assert_allclose(inc32, full, rtol=2e-4, atol=2e-4)


def test_engine_int8_kv_greedy_agreement_and_compile_accounting():
    """The int8-KV engine decodes the same greedy tokens as the fp32
    engine on the same weights, doubles+ the slots per HBM byte, keys
    DISTINCT compiled programs per dtype mode, and stays compile-bound
    (zero extra compiles after its own warmup)."""
    m = _tiny_lm(window=16)
    eng32 = GenerationEngine(m, slots=2, cache_len=16,
                             prefill_buckets=(4, 8), seed=2).warmup()
    prompts = [[5, 9, 4], [7, 3]]
    ref = eng32.generate(prompts, max_new_tokens=8, temperature=0.0)

    c0 = profiler.counters().get(COMPILE_COUNTER, 0)
    eng8 = GenerationEngine(m, slots=2, cache_len=16,
                            prefill_buckets=(4, 8),
                            kv_cache_dtype="int8", seed=2).warmup()
    # distinct dtype mode -> its own programs through the CompiledStore
    assert profiler.counters().get(COMPILE_COUNTER, 0) - c0 == 3
    got = eng8.generate(prompts, max_new_tokens=8, temperature=0.0)
    assert got == ref
    assert eng8.extra_compiles() == 0  # steady state: zero recompiles
    assert eng8.kv_cache_dtype == "int8"
    ratio = eng32.cache_nbytes() / eng8.cache_nbytes()
    assert ratio >= 1.8  # >= 1.8x slots in equal HBM
    assert eng8.kv_bytes_per_token() < eng32.kv_bytes_per_token() / 1.8
    # the capacity denominators land as registry gauges (/metrics)
    snap = monitor.registry_snapshot()
    assert snap["generation/kv_cache_bytes"]["value"] == eng8.cache_nbytes()
    assert (snap["generation/kv_bytes_per_token"]["value"]
            == eng8.kv_bytes_per_token())


def test_engine_kv_dtype_flag_and_validation():
    m = _tiny_lm()
    paddle.set_flags({"generation_kv_cache_dtype": "int8"})
    try:
        eng = GenerationEngine(m, slots=1, cache_len=16,
                               prefill_buckets=(4,))
        assert eng.kv_cache_dtype == "int8"
        assert len(eng._kv) == 5
    finally:
        paddle.set_flags({"generation_kv_cache_dtype": "float32"})
    from paddle_tpu.errors import InvalidArgumentError

    with pytest.raises(InvalidArgumentError, match="kv_cache_dtype"):
        GenerationEngine(m, slots=1, cache_len=16, prefill_buckets=(4,),
                         kv_cache_dtype="int4")


# -- quantized all-reduce ----------------------------------------------------


def test_blockwise_quantize_roundtrip_and_padding():
    rng = np.random.RandomState(0)
    x = rng.randn(5000).astype("f4") * 3
    q, s, meta = qar.quantize_blockwise(jnp.asarray(x), block_size=512,
                                        pad_multiple=8)
    assert q.shape[0] % 8 == 0 and q.shape[1] == 512
    back = np.asarray(qar.dequantize_blockwise(q, s, meta))
    assert back.shape == x.shape
    bound = np.asarray(s).max() / 127 / 2 + 1e-6
    assert np.abs(back - x).max() <= bound
    # all-zero input: scale floors at epsilon, dequant stays finite zero
    qz, sz, mz = qar.quantize_blockwise(jnp.zeros(100), block_size=64)
    bz = np.asarray(qar.dequantize_blockwise(qz, sz, mz))
    assert np.isfinite(bz).all() and bz.max() == 0.0


def test_quantized_all_reduce_eager_sim_numerics():
    """Single-controller path: identity collectives + the two
    quantization hops — error bounded by one step per hop."""
    rng = np.random.RandomState(1)
    x = rng.randn(3, 700).astype("f4")
    out = np.asarray(qar.quantized_all_reduce(jnp.asarray(x),
                                              block_size=256))
    assert out.shape == x.shape and out.dtype == np.float32
    q, s, _ = qar.quantize_blockwise(jnp.asarray(x), block_size=256)
    bound = 2 * (np.asarray(s).max() / 127) + 1e-6
    assert np.abs(out - x).max() <= bound


def test_quantized_allreduce_ledger_byte_cut():
    """The headline wire-byte claim from the ledger itself: tracing the
    gradient-sync entry under a dp-8 mesh, int8 mode moves >= 3.5x
    fewer algorithmic bytes than fp32 mode for the same grad tree."""
    mesh = parallel.create_mesh(dp=8)
    g = jnp.ones((4096, 64), jnp.float32)
    with parallel.mesh_scope(mesh):
        s0 = monitor.registry_snapshot()
        try:
            # accounting fires in _account.__enter__ before psum needs a
            # bound axis (the cost-model test idiom)
            jax.make_jaxpr(
                lambda a: qar.sync_grads({"w": a}, quantized=False))(g)
        except Exception:
            pass
        s1 = monitor.registry_snapshot()
        jax.make_jaxpr(
            lambda a: qar.sync_grads({"w": a}, quantized=True))(g)
        s2 = monitor.registry_snapshot()
    fp32_bytes = qar.wire_bytes_per_step(s0, s1)
    int8_bytes = qar.wire_bytes_per_step(s1, s2)
    assert fp32_bytes == int(2 * 7 / 8 * g.size * 4)
    assert int8_bytes > 0
    assert fp32_bytes / int8_bytes >= 3.5


def test_quantized_allreduce_training_convergence(quantized_allreduce_flag):
    """The real TrainStepFn hook: loss curve with the int8 gradient
    sync converges within tolerance of the fp32 curve, and the flag is
    captured at step construction (distinct steps, zero steady-state
    recompiles each)."""
    rng = np.random.RandomState(0)
    X = rng.randn(64, 8).astype("f4")
    Y = rng.randint(0, 4, (64,)).astype("i8")

    def run(flag_on):
        paddle.set_flags({"quantized_allreduce": flag_on})
        paddle.seed(1)
        m = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 4))
        o = opt.Adam(learning_rate=0.01, parameters=m.parameters())
        step = fjit.train_step(
            m, o, lambda mm, x, y: F.cross_entropy(mm(x), y).mean())
        losses = [float(np.asarray(step(X, Y)["loss"]))
                  for _ in range(20)]
        return losses, step

    q_losses, q_step = run(True)
    fp_losses, _ = run(False)
    assert q_losses[-1] < q_losses[0] * 0.8  # it converges
    assert max(abs(a - b) for a, b in zip(fp_losses, q_losses)) < 0.02
    # one compiled executable, zero steady-state recompiles
    assert len(q_step._exec.mapping()) == 1


def test_quantized_sync_mode_is_captured_at_step_construction():
    """The flag is read when the step is BUILT: flipping it afterwards
    (before the first trace) must not swap the step back to the fp32
    sync — the traced ledger must show the quantized hops."""
    rng = np.random.RandomState(0)
    X = rng.randn(16, 8).astype("f4")
    Y = rng.randint(0, 4, (16,)).astype("i8")
    paddle.set_flags({"quantized_allreduce": True})
    try:
        paddle.seed(0)
        m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        o = opt.Adam(learning_rate=0.01, parameters=m.parameters())
        step = fjit.train_step(
            m, o, lambda mm, x, y: F.cross_entropy(mm(x), y).mean())
    finally:
        paddle.set_flags({"quantized_allreduce": False})
    s0 = monitor.registry_snapshot()
    step(X, Y)  # first call = first trace, AFTER the flag flip

    def val(snap, name):
        return snap.get(name, {}).get("value", 0)

    s1 = monitor.registry_snapshot()
    assert (val(s1, "collective/alltoall/traced_calls")
            > val(s0, "collective/alltoall/traced_calls"))
    assert (val(s1, "collective/all_reduce/traced_calls")
            == val(s0, "collective/all_reduce/traced_calls"))


def test_quantized_all_reduce_average_identity_convention():
    """average=True must NOT divide on the single-controller identity
    path — all_reduce(op=AVG) is an identity there (the global view
    already holds the mean), and the quantized twin must agree."""
    mesh = parallel.create_mesh(dp=8)
    x = jnp.ones((512,), jnp.float32) * 3.0
    with parallel.mesh_scope(mesh):
        summed = np.asarray(qar.quantized_all_reduce(x, block_size=64))
        avged = np.asarray(qar.quantized_all_reduce(x, block_size=64,
                                                    average=True))
    np.testing.assert_allclose(avged, summed, rtol=1e-6)
    np.testing.assert_allclose(avged, 3.0, rtol=1e-2)


def test_sync_grads_fp32_mode_routes_through_all_reduce():
    snap0 = monitor.registry_snapshot()
    g = {"w": jnp.ones((8,), jnp.float32)}
    out = qar.sync_grads(g, quantized=False)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.ones(8))
    snap = monitor.registry_snapshot()
    before = snap0.get("collective/all_reduce/calls", {}).get("value", 0)
    assert snap["collective/all_reduce/calls"]["value"] == before + 1


# -- int8 matmul flag discipline --------------------------------------------


def test_use_int8_matmul_flag_never_changes_numerics():
    """Integer math: flag on/off (pallas vs jnp fallback) is bit-equal;
    on CPU both routes resolve to the fallback, and interpret-mode
    pallas equals it exactly (test_quantization pins that) — here we
    pin that flipping the FLAG leaves op outputs identical."""
    from paddle_tpu.ops.registry import kernel

    rng = np.random.RandomState(3)
    xq = jnp.asarray(rng.randint(-127, 128, (16, 32)).astype(np.int8))
    wq = jnp.asarray(rng.randint(-127, 128, (32, 8)).astype(np.int8))
    a = np.asarray(kernel("matmul_int8")(xq, wq, scale_x=1.0, scale_y=1.0))
    paddle.set_flags({"use_int8_matmul": False})
    try:
        b = np.asarray(
            kernel("matmul_int8")(xq, wq, scale_x=1.0, scale_y=1.0))
    finally:
        paddle.set_flags({"use_int8_matmul": True})
    np.testing.assert_array_equal(a, b)


def test_int8_model_served_at_bounded_compiles(tmp_path):
    """PTQ -> ``save_int8_model`` -> an unchanged Predictor inside a real
    ``InferenceServer``: the int8 program warms one compile a bucket
    through the same store as any other, mixed-size traffic adds none,
    and the HTTP answers stay within the documented 5 % envelope of the
    fp32 program."""
    import json
    from urllib.request import Request, urlopen

    import paddle_tpu.static as static
    from paddle_tpu import slim
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.serving import InferenceServer

    buckets = (1, 2, 4)
    rng = np.random.RandomState(4)
    static.enable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    try:
        x = static.data("x", [None, 16], "float32")
        y = static.nn.fc(static.nn.fc(x, 64, activation="relu", name="qs1"),
                         8, name="qs2")
        exe = static.Executor()
        exe.run_startup()
        tests = [rng.randn(r, 16).astype("float32") for r in (1, 2, 3, 1)]
        refs = [np.asarray(exe.run(feed={"x": a}, fetch_list=[y])[0])
                for a in tests]
        ptq = slim.PostTrainingQuantization(
            exe, static.default_main_program(),
            [{"x": rng.randn(16, 16).astype("float32")} for _ in range(4)])
        ptq.quantize()
        ptq.save_int8_model(str(tmp_path), ["x"], [y])
    finally:
        static.disable_static()
        static.reset_default_programs()
        static.global_scope().clear()

    def misses():
        return profiler.counters().get("executor::jit_cache_miss", 0)

    pred = create_predictor(Config(str(tmp_path)))
    assert "mul_int8" in [op.type for op in pred._program.global_block().ops]
    srv = InferenceServer(pred, port=0, replicas=2, buckets=buckets,
                          batch_timeout_ms=1.0)
    try:
        before = misses()
        srv.start()  # warms every bucket
        assert misses() - before == len(buckets)
        scale = max(np.abs(r).max() for r in refs)
        for a, ref in zip(tests, refs):
            body = json.dumps({"inputs": a.tolist()}).encode()
            out = json.loads(urlopen(Request(
                srv.url + "/predict", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=30).read())
            got = np.asarray(next(iter(out["outputs"].values())), "float32")
            assert np.abs(got - ref).max() < 0.05 * scale + 0.05
        assert misses() - before == len(buckets)
        assert srv.pool.extra_compiles() == 0
    finally:
        srv.stop(drain=True)

"""The pattern-driven state-space decoder (models/nemotron_h.py) and what
it forced: a Mamba-2 mixer with a chunked scan beside a one-token step
(nn/state_space.py), layers that are one module alone so that only some
have a cache entry, and through the generation engine and continuous
batching a state that is most of a slot. Tiny widths with the real
ratios, float32, seeded; the plain reference is the benchmark's
(benchmark/configs/nemotron-3-super-120b/reference.py), which imports
nothing of the program and runs the recurrence token by token.

Tolerances: program and reference are both float32 here and differ in
the order of their sums only (chunks, blocks, grouped products): 2e-4 on
logits whose standard deviation is over 0.5, as the other kinds models'
tests; a padded position let into a state, a tail off by a step or a
chunk border's decay left out moves a logit by 1e-2 to 1 (the planted
faults at the end of this file)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.errors import InvalidArgumentError
from paddle_tpu.generation import GenerationEngine
from paddle_tpu.generation import cache as gcache
from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM
from paddle_tpu.nn import Mamba2Mixer, RecurrentCache, StaticCache
from paddle_tpu.nn import state_space
from paddle_tpu.serving.continuous import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmark", "configs",
                        "nemotron-3-super-120b", "reference.py")
    spec = importlib.util.spec_from_file_location("nemotron_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
CACHE_LEN = 64
# the reference's configuration keys at a toy size: the cut's own
# pattern, 8 heads of 4 channels in 2 groups with a state of 16, chunks
# of 8 tokens; this member holds experts 8..15 of 16 routed (6 a token,
# in a latent of 16) and 64 of 97 vocabulary rows
CFG = dict(
    hidden_size=32, num_hidden_layers=11,
    hybrid_override_pattern="MEMEMEM*EME", num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, mamba_num_heads=8, mamba_head_dim=4,
    ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=8,
    time_step_min=1e-3, time_step_max=1e-1, time_step_floor=1e-4,
    n_routed_experts=8, experts_held=[8, 8], num_experts_per_tok=6,
    moe_latent_size=16, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=40, mlp_hidden_act="relu2",
    norm_topk_prob=True, routed_scaling_factor=5.0,
    layer_norm_epsilon=1e-5, vocab_size=64,
    published=dict(num_hidden_layers=88, n_routed_experts=16,
                   vocab_size=97),
    assumed_sizes=dict(initializer_range=0.2))
_OWN = ("published", "assumed_sizes", "experts_held", "n_routed_experts",
        "vocab_size")


def _config(cfg=CFG, **kw):
    keys = {k: v for k, v in cfg.items() if k not in _OWN}
    return NemotronHConfig(**dict(dict(
        keys, vocab_size=cfg["published"]["vocab_size"],
        vocab_held=cfg["vocab_size"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        experts_held=tuple(cfg["experts_held"])), **kw))


def _model(seed=5, cfg=CFG):
    m = NemotronHForCausalLM(_config(cfg))
    w = REF.weights(cfg, jax.random.PRNGKey(seed))
    named = dict(m.named_parameters())
    assert set(named) == set(w)
    for name, p in named.items():
        assert tuple(p._array.shape) == tuple(w[name].shape), name
        p._array = w[name].astype(jnp.float32)
    m.eval()
    return m, w


@pytest.fixture(scope="module")
def model():
    return _model()


def _engine(m, **kw):
    kw = dict(dict(slots=2, cache_len=CACHE_LEN, prefill_buckets=(8, 16, 32),
                   temperature=0.0, top_k=0, kv_cache_layout="ring",
                   kv_cache_dtype="float32"), **kw)
    return GenerationEngine(m, **kw)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, 64, size=n)


def _want(w, toks, cfg=CFG):
    """The reference's full forward; past CACHE_LEN tokens the attention
    layer sees what a ring of CACHE_LEN rows keeps."""
    return np.asarray(REF.forward(w, jnp.asarray(toks), cfg,
                                  window=CACHE_LEN))


# -- (a) the mixer: chunks, steps, padding ------------------------------------

def _mixer(seed=3):
    from paddle_tpu.framework.random import seed as set_seed

    set_seed(seed)
    mix = Mamba2Mixer(32, 8, 4, 16, groups=2, conv_size=4, chunk=8,
                      initializer_range=0.2)
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    mix.conv_b._array = 0.2 * jax.random.normal(k[0], (mix.conv_dim,))
    mix.d_skip._array = 1.0 + 0.2 * jax.random.normal(k[1], (8,))
    mix.norm._array = 1.0 + 0.2 * jax.random.normal(k[2], (32,))
    return mix


def _fresh(mix, rows):
    shapes, dtypes = mix.cache_shapes()
    return RecurrentCache(*(jnp.zeros((rows,) + s, d)
                            for s, d in zip(shapes, dtypes)),
                          jnp.zeros((rows,), jnp.int32))


def _reference_mixer(mix, x):
    """The benchmark reference's `M` layer on one sequence, given the
    mixer's own weights."""
    w = {n: p._array.astype(jnp.float32)
         for n, p in mix.named_parameters()}
    with jax.default_matmul_precision("highest"):
        return REF._mamba(x, w, REF._widths(CFG), REF._mm(False), 1e-5)


@pytest.mark.parametrize("t", [16, 8, 37, 5, 1])
def test_chunked_scan_is_the_recurrence_and_the_reference(t):
    """Lengths that are and are not multiples of the chunk (8), shorter
    than one chunk, and one token: the SSD form, the token-by-token
    recurrence of the same module and the benchmark reference's give one
    answer, and the scan ends in the recurrence's state."""
    k = jax.random.split(jax.random.PRNGKey(t), 6)
    x = jax.random.normal(k[0], (2, t, 8, 4))
    b, c = (jax.random.normal(k[i], (2, t, 2, 16)) for i in (1, 2))
    dt = jax.nn.softplus(jax.random.normal(k[3], (2, t, 8)))
    a = -jnp.linspace(1.0, 16.0, 8)
    d = jnp.ones((8,))
    s0 = jax.random.normal(k[4], (2, 8, 4, 16))
    s1, y1 = state_space.ssm_recurrent(s0, x, b, c, dt, a, d)
    s2, y2 = state_space.ssm_chunked(s0, x, b, c, dt, a, d, chunk=8)
    np.testing.assert_allclose(y2, y1, atol=2e-5)
    np.testing.assert_allclose(s2, s1, atol=2e-5)
    mix = _mixer()
    u = jax.random.normal(k[5], (t, 32))
    got = mix(u[None])[0] if t > 1 else mix(u[None], cache=_fresh(mix, 1))[0][0]
    np.testing.assert_allclose(got, _reference_mixer(mix, u), atol=2e-5)


@pytest.mark.parametrize("n,m", [(13, 8), (8, 3), (16, 16), (1, 6)])
def test_prefill_then_steps_is_one_longer_prefill(n, m):
    """A prompt of n tokens into a fresh cache and then m one-token
    steps: outputs, final state and convolution tail are those of a
    prompt of n + m tokens."""
    mix = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(n), (2, n + m, 32))
    want, whole = mix(x, cache=_fresh(mix, 2))
    if n > 1:
        out, cache = mix(x[:, :n], cache=_fresh(mix, 2))
        outs = [out]
    else:
        outs, cache, n = [], _fresh(mix, 2), 0
    for i in range(n, x.shape[1]):
        out, cache = mix(x[:, i:i + 1], cache=cache)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=2e-5)
    np.testing.assert_allclose(cache.state, whole.state, atol=2e-5)
    np.testing.assert_allclose(cache.conv_tail, whole.conv_tail, atol=1e-6)


@pytest.mark.parametrize("real,bucket", [(13, 16), (5, 32), (2, 8), (16, 16)])
def test_right_padding_advances_neither_state_nor_tail(real, bucket):
    mix = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(real), (1, bucket, 32))
    valid = (jnp.arange(bucket) < real)[None]
    got, padded = mix(x, cache=_fresh(mix, 1), valid=valid)
    want, plain = mix(x[:, :real], cache=_fresh(mix, 1))
    np.testing.assert_allclose(got[:, :real], want, atol=2e-5)
    np.testing.assert_allclose(padded.state, plain.state, atol=2e-5)
    np.testing.assert_allclose(padded.conv_tail, plain.conv_tail, atol=1e-6)


# -- the model and its cache entries ------------------------------------------

def test_cache_spec_lists_the_layers_that_keep_something(model):
    """Six entries for eleven layers, in pattern order: the five `M`
    layers (0, 2, 4, 6, 9) a state and a tail, layer 7 a K/V ring, the
    five `E` layers nothing."""
    m, _ = model
    kinds = m.cache_spec()
    state = gcache.state(((8, 4, 16), (3, 96)), ("float32", "float32"))
    assert kinds == [state] * 4 + [gcache.kv(2, 8), state]
    assert [layer.kind for layer in m.layers] == list("MEMEMEM*EME")
    assert gcache.is_layer_kinds(kinds)
    kv = gcache.init_kinds_cache(kinds, 3, CACHE_LEN, "bfloat16")
    caches = gcache.kinds_layer_caches(kinds, kv)
    assert [type(c) for c in caches] == [RecurrentCache] * 4 + [
        StaticCache, RecurrentCache]
    assert gcache.kinds_slot_nbytes(kinds, CACHE_LEN, "bfloat16") \
        == 5 * (8 * 4 * 16 * 4 + 3 * 96 * 4) + CACHE_LEN * 2 * 2 * 8 * 2
    assert gcache.kinds_bytes_per_token(kinds, "bfloat16") == 2 * 2 * 8 * 2


def test_a_pattern_is_checked_against_the_depth_and_the_alphabet():
    with pytest.raises(InvalidArgumentError, match="11 layers"):
        NemotronHForCausalLM(_config(num_hidden_layers=12))
    with pytest.raises(InvalidArgumentError, match="M, \\* or E"):
        NemotronHForCausalLM(_config(hybrid_override_pattern="MEMEMEM-EME"))


def test_full_forward_matches_the_plain_reference(model):
    m, w = model
    toks = _tokens(37)
    want = np.asarray(REF.forward(w, jnp.asarray(toks), CFG))
    got = np.asarray(m(jnp.asarray(toks[None]))._array[0])
    assert want.std() > 0.5
    np.testing.assert_allclose(got, want, atol=2e-4)


def _cached_logits(m, toks, n_prompt, bucket):
    """Logits of positions ``n_prompt-1 ..`` of ``toks`` as the engine
    computes them: one right-padded prefill of the first ``n_prompt``
    into fresh caches (the last real row's logits; the chunked scan),
    then one cached decode step a token (the one-token step)."""
    kinds = m.cache_spec()

    @jax.jit
    def prefill(padded):
        fresh = gcache.init_kinds_cache(kinds, 1, CACHE_LEN, "float32")
        mask = jnp.where(jnp.arange(bucket) < n_prompt, 0.0,
                         gcache.NEG_INF).astype(jnp.float32)[None, None, None]
        logits, caches = m(padded[None], attention_mask=mask,
                           caches=gcache.kinds_layer_caches(kinds, fresh))
        assert logits._array.shape[1] == 1 and len(caches) == len(kinds)
        return logits._array[0], gcache.unzip_kinds_caches(caches)

    @jax.jit
    def step(tok, kv):
        mask = gcache.kinds_decode_mask(kinds, kv[-1], CACHE_LEN)
        logits, caches = m(tok[None, None], attention_mask=mask,
                           caches=gcache.kinds_layer_caches(kinds, kv))
        return logits._array[0], \
            gcache.unzip_kinds_caches(caches) + (kv[-1] + 1,)

    padded = np.full(bucket, 2, np.int32)
    padded[:n_prompt] = toks[:n_prompt]
    logits, kept = prefill(jnp.asarray(padded))
    out = [np.asarray(logits)]
    kv = kept + (jnp.asarray([n_prompt], jnp.int32),)
    for i in range(n_prompt, len(toks)):
        logits, kv = step(jnp.asarray(toks[i], jnp.int32), kv)
        out.append(np.asarray(logits))
    return np.concatenate(out)


@pytest.mark.parametrize("n_prompt,bucket", [(5, 8), (8, 8), (13, 16),
                                             (27, 32)])
def test_prefill_then_decode_matches_full_forward_past_the_rings_wrap(
        model, n_prompt, bucket):
    """A chunked prefill into states, tails and the K/V ring, then
    one-token steps to 80 tokens: the ring (64 rows) wraps, the states
    never grow. Every logit within 2e-4 of the reference's full forward
    pass (recurrence token by token, no cache)."""
    m, w = model
    toks = _tokens(80, seed=n_prompt)
    got = _cached_logits(m, toks, n_prompt, bucket)
    np.testing.assert_allclose(got, _want(w, toks)[n_prompt - 1:], atol=2e-4)


# -- (b) the engine and continuous batching -----------------------------------

def _served_gaps(w, prompt, out):
    """Per served token the reference's largest logit less its logit of
    the served token, teacher-forced on prompt + served."""
    seq = np.asarray(list(prompt) + list(out))
    logits = _want(w, seq)
    own = logits[np.arange(len(seq) - 1), seq[1:]]
    return (logits.max(-1)[:-1] - own)[len(prompt) - 1:]


def _serve_and_check(m, w, slots, lengths, budgets):
    """``len(lengths)`` requests through ContinuousBatcher on ``slots``
    slots: every served token is the reference's argmax at its position
    to 2e-4 of the largest logit, nothing compiles after warm-up, and
    some request was admitted mid-batch. Returns the engine."""
    from paddle_tpu import monitor

    eng = _engine(m, slots=slots).warmup()
    assert eng.expected_compiles() == 3 + 1 and eng.extra_compiles() == 0
    prompts = [_tokens(n, seed=n).tolist() for n in lengths]
    mid0 = monitor.counter("serving/gen_midbatch_admissions_total").value
    sched = ContinuousBatcher(eng, queue_capacity=32).start()
    try:
        reqs = [sched.submit(p, max_new_tokens=b, temperature=0.0)
                for p, b in zip(prompts, budgets)]
        outs = [r.wait(timeout=300) for r in reqs]
    finally:
        sched.stop(drain=False)
    assert sched.extra_compiles() == 0
    assert monitor.counter(
        "serving/gen_midbatch_admissions_total").value - mid0 >= 1
    for p, o, b in zip(prompts, outs, budgets):
        stop = o.index(1) + 1 if 1 in o else b     # EOS ends a request
        assert len(o) == stop
        assert _served_gaps(w, p, o).max() <= 2e-4
    return eng


def test_continuous_batching_serves_the_references_tokens_whatever_the_neighbours(
        model):
    """Six slots, fourteen requests of unlike lengths through
    ContinuousBatcher: slots fill at once, requests finish at different
    steps, the queue's rest is admitted mid-batch into slots that were
    used before. Every served token is the reference's argmax at its
    position to 2e-4 of the largest logit, so no request saw a
    neighbour's state, a previous tenant's state or tail, or a padded
    position; nothing compiles after warm-up; the engine needed nothing
    new for a cache with fewer entries than the model has layers."""
    m, w = model
    _serve_and_check(m, w, 6, [5, 13, 20, 8, 31, 3, 17, 9, 26, 4, 11, 16, 7,
                               22],
                     [9, 30, 4, 17, 6, 25, 12, 3, 20, 8, 28, 5, 14, 10])


def test_the_experts_kernel_serves_the_references_tokens_too(model,
                                                             monkeypatch):
    """The same through the non-gated experts' kernel, which a TPU takes
    and the CPU does not: its gate is opened here, so every expert layer
    of the decode step and of the prefill buckets is one interpreted
    `grouped_experts` call (rows that are no whole row tile, widths that
    are no whole lanes: the interpreter does not mind). The served
    tokens are still the reference's, and a decode step's statistics
    carry the rows the kernel's tiles multiplied, an expert layer a
    value, no fewer than the pairs that landed here."""
    from paddle_tpu import profiler
    from paddle_tpu.parallel import moe

    calls = []
    monkeypatch.setattr(moe, "can_emit_mosaic", lambda: True)
    monkeypatch.setattr(moe, "grouped_relu2_supported",
                        lambda *a: calls.append(a) or True)
    m, w = model
    eng = _serve_and_check(m, w, 3, [5, 13, 20, 8, 31, 3, 17],
                           [9, 12, 4, 17, 6, 10, 12])
    assert len(calls) == 5 * 4        # five expert layers, four programs
    assert "tile_rows" in m.routing_stats()
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    try:
        eng.reset()
        eng.admit(1, _tokens(20).tolist())
        eng.step(np.zeros(3, np.int32), np.zeros(3, np.float32))
        got = {ev["name"]: ev["args"]["value"]
               for ev in profiler.counter_samples()}
    finally:
        profiler.stop_profiler()
        profiler.reset_profiler()
    pairs, rows = got["moe::pairs_here"], got["moe::tile_rows"]
    assert len(pairs) == len(rows) == 5
    assert all(p <= r and r % 8 == 0 for p, r in zip(pairs, rows))


def test_the_state_is_most_of_a_slot_and_is_accounted(model):
    m, _ = model
    eng = _engine(m, slots=6, kv_cache_dtype="bfloat16")
    state = 5 * (8 * 4 * 16 * 4 + 3 * 96 * 4)
    ring = CACHE_LEN * 2 * 2 * 8 * 2
    assert eng.state_nbytes() == 6 * state
    assert eng.cache_bytes_by_kind() == (6 * ring, 0, 6 * state, 0)
    assert eng.cache_nbytes() == 6 * (state + ring) + 6 * 4
    weights = sum(int(np.prod(p._array.shape)) * 4
                  for _, p in m.named_parameters())
    assert eng.hbm_required_bytes() >= weights + 6 * (state + ring)


def test_the_caches_are_one_donated_pytree(model):
    m, _ = model
    eng = _engine(m).warmup()
    before = jax.tree_util.tree_leaves(eng._kv)
    assert len(before) == 5 * 2 + 2 + 1
    eng.admit(0, _tokens(13).tolist())
    assert all(a.is_deleted() for a in before)
    before = jax.tree_util.tree_leaves(eng._kv)
    eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
    assert all(a.is_deleted() for a in before)
    assert [int(p) for p in eng._kv[-1]] == [14, 1]
    assert eng.kv_rows_read() == (15 + 2, 0, 0)


def test_counters_are_sampled_only_while_the_profiler_is_on(model):
    from paddle_tpu import profiler

    m, _ = model
    eng = _engine(m).warmup()
    profiler.reset_profiler()
    eng.reset()
    eng.admit(0, _tokens(13).tolist())
    eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
    assert not profiler.counter_samples()
    profiler.start_profiler(state="CPU")
    try:
        eng.reset()
        eng.admit(1, _tokens(20).tolist())
        eng.step(np.zeros(2, np.int32), np.zeros(2, np.float32))
        got = {}
        for ev in profiler.counter_samples():
            got.setdefault(ev["name"], []).append(ev["args"]["value"])
    finally:
        profiler.stop_profiler()
        profiler.reset_profiler()
    assert got["generation::state_bytes"] == [eng.state_nbytes()]
    pairs, hit = got["moe::pairs_here"][0], got["moe::experts_hit"][0]
    assert len(pairs) == len(hit) == 5        # one value an expert layer
    assert all(0 <= p <= 2 * 6 and 0 <= h <= 8 for p, h in zip(pairs, hit))
    assert len(got["moe::expert_load"]) == 2  # the prompt's and the step's
    assert all(len(load) == 8 for load in got["moe::expert_load"])
    assert "moe::tile_rows" not in got        # no kernel off the chip


def test_a_long_prompts_expert_layers_run_in_chunks(model, monkeypatch):
    """Past `_MOE_CHUNK` tokens an expert layer takes the prompt a chunk
    at a time: the same logits and the same routing counts."""
    from paddle_tpu.models import nemotron_h

    m, _ = model
    toks = jnp.asarray(_tokens(32)[None])
    want = np.asarray(m(toks)._array)
    load = np.asarray(m.routing_stats()["load"])
    monkeypatch.setattr(nemotron_h, "_MOE_CHUNK", 8)
    np.testing.assert_allclose(np.asarray(m(toks)._array), want, atol=2e-5)
    np.testing.assert_array_equal(m.routing_stats()["load"], load)


def test_the_scopes_are_in_the_programs(model):
    """`ssm`, `ssm_scan` (prefill), `ssm_step` (decode) and `moe_latent`
    name the new work in the programs the engine compiles."""
    m, _ = model
    eng = _engine(m)

    def text(call):
        _, fn, make = call
        return fn.lower(*make()).as_text(debug_info=True)

    decode = text(eng._decode_call(np.zeros(2, np.int32),
                                   np.zeros(2, np.float32), 0))
    prefill = text(eng._prefill_call(0, np.zeros(16, np.int32), 12, 0.0, 0))
    assert "ssm/ssm_step" in decode and "ssm_scan" not in decode
    assert "ssm/ssm_scan" in prefill and "ssm_step" not in prefill
    assert "moe_experts/moe_latent" in decode
    assert "moe_experts/moe_latent" in prefill


# -- planted faults -----------------------------------------------------------

def _padding_advances_the_state(monkeypatch, m):
    real = Mamba2Mixer.forward
    monkeypatch.setattr(
        Mamba2Mixer, "forward",
        lambda self, x, cache=None, valid=None: real(self, x, cache=cache))


def _tail_off_by_a_step(monkeypatch, m):
    real = Mamba2Mixer.forward

    def shifted(self, x, cache=None, valid=None):
        out = real(self, x, cache=cache, valid=valid)
        if cache is None or x.shape[1] == 1:
            return out
        y, c = out
        return y, RecurrentCache(c.state, jnp.roll(c.conv_tail, 1, axis=1),
                                 c.pos)

    monkeypatch.setattr(Mamba2Mixer, "forward", shifted)


def _state_rounded_to_bfloat16(monkeypatch, m):
    real = state_space.ssm_step

    def rounded(s, *a):
        s, y = real(s, *a)
        return s.astype(jnp.bfloat16).astype(jnp.float32), y

    monkeypatch.setattr(state_space, "ssm_step", rounded)


def _latent_sum_not_projected_up(monkeypatch, m):
    for layer in m.layers:
        if layer.kind == "E":
            monkeypatch.setattr(
                layer.mixer.latent_up, "_array",
                jnp.roll(layer.mixer.latent_up._array, 1, axis=0))


@pytest.mark.parametrize("plant,least", [
    (_padding_advances_the_state, 1e-2), (_tail_off_by_a_step, 1e-2),
    (_state_rounded_to_bfloat16, 1e-3), (_latent_sum_not_projected_up, 1e-2)])
def test_a_planted_fault_is_far_outside_the_tolerance(plant, least,
                                                      monkeypatch):
    """What the tolerance of 2e-4 is for: each fault, planted in the
    program, moves the cached path's logits past it (the bfloat16 state
    least: a rounding, not a structure)."""
    m, w = _model()
    plant(monkeypatch, m)
    toks = _tokens(50, seed=1)
    got = _cached_logits(m, toks, 13, 16)
    assert np.abs(got - _want(w, toks)[12:]).max() > least

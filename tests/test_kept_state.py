"""The engine keeps its functional state and the signature of every
leaf no step changes (PR 28).

Pins the contract of ``generation/engine.py`` ``_KeptState`` and
``_signature``: between two calls nothing is derived again (the kept
signature is the same object, ``generation::state_rebuilt`` stays where
it was, each store holds the entries ``warmup()`` made); a parameter
rebound under a live engine is noticed by the engine itself, used by
the next call of every program, and costs one rebuild and no compile,
or one compile a program where its dtype changed; ``reset()`` and a
lost cache replace the cache's arrays and leave the state half alone;
the engine keeps no weight alive that its model has let go; and the key the ``CompiledStore`` sees is, for every call of every
layout, the one derived leaf by leaf from the arguments.
"""
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.generation import (
    COMPILE_COUNTER,
    CacheLostError,
    GenerationEngine,
)
from paddle_tpu.generation.engine import (
    CACHE_LOST_COUNTER,
    STATE_REBUILT_COUNTER,
)
from paddle_tpu.models import (
    GPTForCausalLM,
    HybridMoEConfig,
    HybridMoEForCausalLM,
    gpt_tiny_config,
    truncated_draft,
)

CACHE = 32
BUCKETS = (4, 8)
# engine options of each layout; "kinds" is the per-layer cache of a
# model whose layers keep different things (nn.RecurrentCache beside
# nn.StaticCache), "speculative" the draft / verify pair over two rings
LAYOUTS = {
    "ring": {},
    "int8": dict(kv_cache_dtype="int8"),
    "paged": dict(kv_cache_layout="paged", kv_page_size=8),
    "speculative": dict(draft_k=2),
    "kinds": dict(kv_cache_layout="ring", kv_cache_dtype="float32"),
}


def _gpt(seed=11):
    paddle.seed(seed)
    cfg = gpt_tiny_config()
    cfg.attention_window = CACHE
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _hybrid(seed=5):
    paddle.seed(seed)
    m = HybridMoEForCausalLM(HybridMoEConfig(
        vocab_size=97, vocab_held=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        gqa_layers=(0,),
        linear_attn_config=dict(short_conv_kernel_size=4, head_dim=8,
                                num_heads=4),
        kda_gate_rank=8, moe_intermediate_size=16, n_routed_experts=16,
        num_experts_per_tok=4, experts_held=(4, 8)))
    m.eval()
    return m


def _engine(layout):
    """A fresh model (the tests rebind its parameters) and its engine."""
    model = _hybrid() if layout == "kinds" else _gpt()
    kw = dict(LAYOUTS[layout])
    if layout == "speculative":
        kw["draft_model"] = truncated_draft(model, 1)
    eng = GenerationEngine(model, slots=3, cache_len=CACHE,
                           prefill_buckets=BUCKETS, seed=7,
                           temperature=0.0, top_k=0, **kw)
    return model, eng


def _count(name):
    return profiler.counters().get(name, 0)


def _kept(eng):
    """The kept signature objects of the engine's models."""
    states = [eng._state_kept] + (
        [eng._draft_state_kept] if eng.speculative else [])
    return [k._signature for k in states]


def _leaf_by_leaf(eng, args):
    """The store key as the engine derived it before it kept anything:
    this engine, then shape and dtype of every argument leaf."""
    return (eng._instance,) + tuple(
        (tuple(x.shape), str(x.dtype))
        for x in jax.tree_util.tree_leaves(args))


def _check_every_signature(eng, monkeypatch):
    """Every call's key must equal the leaf-by-leaf one; returns the
    list the checked calls are counted in."""
    real, seen = eng._signature, []

    def checked(args):
        sig = real(args)
        assert sig == _leaf_by_leaf(eng, args)
        seen.append(sig)
        return sig

    monkeypatch.setattr(eng, "_signature", checked)
    return seen


def _step(eng, tokens):
    """One decode step (a speculative round's first emitted token is
    the target's own choice at that position); slot 0's next token."""
    temps = np.zeros(eng.slots, np.float32)
    if eng.speculative:
        emitted, _ = eng.spec_step(tokens, temps)
        return int(emitted[0, 0])
    return int(eng.step(tokens, temps)[0])


def _argmax_next(model, ids):
    """The plain reference: greedy next token of a full forward."""
    logits = model(np.asarray(ids, "int32")[None])
    return int(np.argmax(np.asarray(logits.numpy())[0, -1]))


def _head_parameter(model, layout):
    """A parameter after the last layer (the cache's rows do not depend
    on it, so a full forward stays the reference across the swap) and a
    new value of its shape and dtype that moves the argmax."""
    named = dict(model.named_parameters())
    if layout == "kinds":
        p = named["lm_head"]
        return p, np.roll(np.asarray(p.numpy()), 7, axis=1)
    p = named["gpt.norm_f.bias"]
    wte = np.asarray(named["gpt.word_embeddings.weight"].numpy())
    return p, (40.0 * wte[123]).astype("float32")


# -- steady state: nothing is derived twice -----------------------------------

@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_steady_state_rebuilds_nothing_and_keeps_warmups_keys(
        layout, monkeypatch):
    _, eng = _engine(layout)
    eng.warmup()
    keys = {label: set(store.entries())
            for label, store in eng._stores.items()}
    assert sum(map(len, keys.values())) == eng.expected_compiles()
    kept, rebuilt0 = _kept(eng), _count(STATE_REBUILT_COUNTER)
    seen = _check_every_signature(eng, monkeypatch)
    # five admissions over three slots and some tens of steps
    prompts = [list(range(3, 6 + i)) for i in range(5)]
    out = eng.generate(prompts, max_new_tokens=12, temperature=0.0,
                       stop_at_eos=False)
    assert [len(o) for o in out] == [12] * 5
    assert len(seen) >= 5 + (12 if eng.speculative else 20)
    assert _count(STATE_REBUILT_COUNTER) == rebuilt0
    assert all(a is b for a, b in zip(_kept(eng), kept))  # not equal ones
    assert eng.extra_compiles() == 0
    assert {label: set(store.entries())
            for label, store in eng._stores.items()} == keys
    assert set(seen) <= set().union(*keys.values())


# -- a parameter rebound under a live engine ----------------------------------

@pytest.mark.parametrize("layout", ["ring", "paged", "speculative", "kinds"])
def test_rebound_parameter_is_used_by_the_next_step_and_admission(layout):
    model, eng = _engine(layout)
    eng.warmup()
    prompt, other = [5, 6, 7], [9, 8, 7, 6]
    tokens = np.zeros(eng.slots, np.int32)
    tokens[0] = eng.admit(0, prompt)
    ids = prompt + [int(tokens[0])]
    tokens[0] = _step(eng, tokens)
    assert tokens[0] == _argmax_next(model, ids)
    ids.append(int(tokens[0]))
    old = _argmax_next(model, ids), _argmax_next(model, other)

    p, value = _head_parameter(model, layout)
    p.set_value(value)
    new = _argmax_next(model, ids), _argmax_next(model, other)
    assert new[0] != old[0] and new[1] != old[1]

    rebuilt0, compiles0 = (_count(STATE_REBUILT_COUNTER),
                           _count(COMPILE_COUNTER))
    assert _step(eng, tokens) == new[0]
    assert _count(STATE_REBUILT_COUNTER) == rebuilt0 + 1
    assert eng.admit(1, other) == new[1]
    # one rebuild serves every program of the model; nothing compiled
    assert _count(STATE_REBUILT_COUNTER) == rebuilt0 + 1
    assert _count(COMPILE_COUNTER) == compiles0
    assert eng.extra_compiles() == 0


def test_rebound_draft_parameter_rebuilds_the_drafts_state_alone():
    _, eng = _engine("speculative")
    eng.warmup()
    tokens = np.zeros(eng.slots, np.int32)
    tokens[0] = eng.admit(0, [5, 6, 7])
    kept, rebuilt0 = _kept(eng), _count(STATE_REBUILT_COUNTER)
    p = dict(eng.draft_model.named_parameters())["gpt.norm_f.bias"]
    p.set_value(np.asarray(p.numpy()) + 1.0)
    _step(eng, tokens)
    assert _count(STATE_REBUILT_COUNTER) == rebuilt0 + 1
    target, draft = _kept(eng)
    assert target is kept[0] and draft is not kept[1] and draft == kept[1]
    assert eng.extra_compiles() == 0


def test_parameter_of_another_dtype_compiles_once_a_program(monkeypatch):
    model, eng = _engine("ring")
    eng.warmup()
    tokens = np.zeros(eng.slots, np.int32)
    tokens[0] = eng.admit(0, [5, 6, 7])
    ids = [5, 6, 7, int(tokens[0])]
    p = dict(model.named_parameters())["gpt.norm_f.bias"]
    # what a cast does: the same tensor, an array of another dtype
    p._array = (p._array + 40.0 * dict(model.named_parameters())[
        "gpt.word_embeddings.weight"]._array[123]).astype(jnp.bfloat16)
    want = _argmax_next(model, ids)
    rebuilt0, compiles0 = (_count(STATE_REBUILT_COUNTER),
                           _count(COMPILE_COUNTER))
    seen = _check_every_signature(eng, monkeypatch)
    tokens[0] = _step(eng, tokens)
    assert tokens[0] == want
    assert _count(STATE_REBUILT_COUNTER) == rebuilt0 + 1
    assert _count(COMPILE_COUNTER) == compiles0 + 1
    assert eng.extra_compiles() == 1  # CompileWatch saw it
    assert len(eng._stores["decode"]) == 2
    assert ((64,), "bfloat16") in seen[-1]
    assert seen[-1] in eng._stores["decode"].entries()
    # the new program is kept: the next step misses nothing
    _step(eng, tokens)
    assert _count(COMPILE_COUNTER) == compiles0 + 1
    assert _count(STATE_REBUILT_COUNTER) == rebuilt0 + 1
    # each prefill bucket is a program of its own and misses once too
    eng.admit(1, [9, 8, 7])
    eng.admit(2, [9, 8, 7])
    assert _count(COMPILE_COUNTER) == compiles0 + 2


# -- a new cache is not a new state -------------------------------------------

def _fail_after_consuming(eng, label):
    """The next dispatch of ``label`` runs (and consumes the donated
    cache), then raises: a device error that surfaces late."""
    store = eng._stores[label]
    real = store.dispatch

    def dispatch(entry, *args, **kw):
        store.dispatch = real
        real(entry, *args, **kw)
        raise RuntimeError("injected fault after launch")

    store.dispatch = dispatch


@pytest.mark.parametrize("layout", ["ring", "paged", "speculative", "kinds"])
def test_reset_and_a_lost_cache_leave_the_state_half_alone(
        layout, monkeypatch):
    _, eng = _engine(layout)
    eng.warmup()
    want = eng.generate([[5, 6, 7]], max_new_tokens=6, temperature=0.0)
    kept, rebuilt0 = _kept(eng), _count(STATE_REBUILT_COUNTER)
    seen = _check_every_signature(eng, monkeypatch)
    held = jax.tree_util.tree_leaves(eng._kv)
    eng.reset()
    assert jax.tree_util.tree_leaves(eng._kv)[0] is not held[0]
    if layout != "paged":  # the page pool's programs do not donate
        lost0 = _count(CACHE_LOST_COUNTER)
        eng.admit(0, [5, 6, 7])
        _fail_after_consuming(eng, "verify" if eng.speculative else "decode")
        with pytest.raises(CacheLostError, match="injected fault"):
            _step(eng, np.zeros(eng.slots, np.int32))
        assert _count(CACHE_LOST_COUNTER) == lost0 + 1
    assert eng.generate([[5, 6, 7]], max_new_tokens=6,
                        temperature=0.0) == want
    assert seen
    assert _count(STATE_REBUILT_COUNTER) == rebuilt0
    assert all(a is b for a, b in zip(_kept(eng), kept))
    assert eng.extra_compiles() == 0


def test_engine_keeps_no_weight_alive_that_its_model_let_go():
    """The tensors own the arrays: a caller that drops the cache and
    rebinds every parameter (the benchmark frees the chip for its
    reference this way) gets the memory back while the engine lives."""
    model, eng = _engine("ring")
    eng.warmup()
    eng.generate([[5, 6, 7]], max_new_tokens=4, temperature=0.0)
    weights = [weakref.ref(p._array) for _, p in model.named_parameters()]
    cache = [weakref.ref(a) for a in jax.tree_util.tree_leaves(eng._kv)]
    eng._kv = None
    for _, p in model.named_parameters():
        p._array = None
    gc.collect()
    assert not any(r() is not None for r in weights + cache)

"""Builds the system under test for `gpt2-large` through the program's
normal entry points: GPTForCausalLM -> GenerationEngine -> a started,
warmed GenerationServer. The weights are the benchmark's (reference.py's
``weights`` from the seed), made on the device in one jitted call and
handed to the model by parameter name."""
from __future__ import annotations

import os

import jax

from benchmark.lib import common

_HERE = os.path.dirname(os.path.abspath(__file__))
reference = common.load_module(os.path.join(_HERE, "reference.py"))

# program parameter name (under gpt.layers.<i>.) -> stacked reference key
_LAYER_NAMES = {
    "self_attn.q_proj.weight": "wq", "self_attn.q_proj.bias": "bq",
    "self_attn.k_proj.weight": "wk", "self_attn.k_proj.bias": "bk",
    "self_attn.v_proj.weight": "wv", "self_attn.v_proj.bias": "bv",
    "self_attn.out_proj.weight": "wo", "self_attn.out_proj.bias": "bo",
    "linear1.weight": "w1", "linear1.bias": "b1",
    "linear2.weight": "w2", "linear2.bias": "b2",
    "norm1.weight": "ln1_g", "norm1.bias": "ln1_b",
    "norm3.weight": "ln2_g", "norm3.bias": "ln2_b",
}
_TOP_NAMES = {
    "gpt.word_embeddings.weight": "wte",
    "gpt.position_embeddings.weight": "wpe",
    "gpt.norm_f.weight": "lnf_g", "gpt.norm_f.bias": "lnf_b",
}


def program_weights(cfg, seed):
    """{program parameter name: array}, one jitted call on the device."""
    def make(key):
        w = reference.weights(cfg, key)
        out = {name: w[k] for name, k in _TOP_NAMES.items()}
        for i in range(cfg["n_layer"]):
            for name, k in _LAYER_NAMES.items():
                out[f"gpt.layers.{i}.{name}"] = w[k][i]
        return out

    return jax.jit(make)(common.seed_key(seed))


def model(cfg, seed):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    m = GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_hidden_layers=cfg["n_layer"], num_attention_heads=cfg["n_head"],
        intermediate_size=4 * cfg["n_embd"],
        max_position_embeddings=cfg["n_positions"],
        initializer_range=cfg["initializer_range"],
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
    w = program_weights(cfg, seed)
    common.assign_weights(m, w)
    m.eval()
    return m


def server(cfg, mix, seed):
    """A started GenerationServer, every program compiled (warm-up)."""
    from paddle_tpu.generation import GenerationEngine
    from paddle_tpu.serving import GenerationServer

    e = dict(cfg["engine"])
    engine = GenerationEngine(
        model(cfg, seed), slots=e["slots"], cache_len=e["cache_len"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        temperature=e["temperature"], top_k=e["top_k"],
        kv_cache_layout=e["kv_cache_layout"],
        kv_cache_dtype=e["kv_cache_dtype"],
        max_new_tokens=mix.get("max_new_tokens_default", 64))
    srv = GenerationServer(engine, port=0,
                           queue_capacity=mix.get("queue_capacity"),
                           request_timeout_s=mix.get("request_timeout_s",
                                                     120.0))
    srv.start()
    return srv

"""Builds the system under test for `longcat-flash-omni` through the
program's normal entry points: LongcatFlashForCausalLM -> GenerationEngine
-> a started, warmed GenerationServer. The weights are the benchmark's
(reference.py's ``leaf`` from the seed), made on the device one leaf at a
time and handed to the model by parameter name: the reference's leaf
names are the program's parameter names."""
from __future__ import annotations

import os
from collections.abc import Mapping

from benchmark.lib import common

_HERE = os.path.dirname(os.path.abspath(__file__))
reference = common.load_module(os.path.join(_HERE, "reference.py"))


class _Leaves(Mapping):
    """{program parameter name: array}, each leaf made when it is asked
    for (one jitted call a shape), so that handing a new seed's weights
    to a model that holds the old ones never has both whole on the
    device (10.35 GB each): check_tolerances.py swaps a server's weights
    by seed."""

    def __init__(self, cfg, seed):
        self.cfg, self.key = cfg, common.seed_key(seed)
        self.shapes = reference.leaf_shapes(cfg)

    def __getitem__(self, name):
        return reference.make_leaf(self.cfg, self.key, name,
                                   self.shapes[name])

    def __iter__(self):
        return iter(self.shapes)

    def __len__(self):
        return len(self.shapes)


def program_weights(cfg, seed):
    return _Leaves(cfg, seed)


def model(cfg, seed):
    from paddle_tpu.models import LongcatFlashConfig, LongcatFlashForCausalLM

    if cfg["attention_method"] != "MLA" \
            or cfg["zero_expert_type"] != "identity" or cfg["attention_bias"]:
        raise RuntimeError("the program runs MLA without biases and "
                           "identity zero experts; the config says otherwise")
    m = LongcatFlashForCausalLM(LongcatFlashConfig(
        vocab_size=cfg["published"]["vocab_size"],
        vocab_held=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        ffn_hidden_size=cfg["ffn_hidden_size"],
        expert_ffn_hidden_size=cfg["expert_ffn_hidden_size"],
        num_layers=cfg["num_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=cfg["q_lora_rank"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        mla_scale_q_lora=cfg["mla_scale_q_lora"],
        mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        experts_held=tuple(cfg["experts_held"]),
        zero_expert_num=cfg["zero_expert_num"], moe_topk=cfg["moe_topk"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg["assumed_sizes"]["initializer_range"],
        dtype=cfg.get("program_dtype", "bfloat16"), init_weights=False))
    leaves = program_weights(cfg, seed)
    named = dict(m.named_parameters())
    if set(named) != set(leaves):
        raise RuntimeError("parameter names differ from the benchmark's: "
                           f"{sorted(set(named) ^ set(leaves))[:8]}")
    for name, p in named.items():
        if tuple(p._array.shape) != tuple(leaves.shapes[name]):
            raise RuntimeError(f"{name}: {p._array.shape} vs "
                               f"{leaves.shapes[name]}")
        p._array = leaves[name].astype(p._array.dtype)
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

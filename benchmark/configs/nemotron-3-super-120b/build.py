"""Builds the system under test for `nemotron-3-super-120b` through the
program's normal entry points: NemotronHForCausalLM -> GenerationEngine
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
    device (9.3 GB each): check_tolerances.py swaps a server's weights
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
    from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM

    if cfg["use_bias"] or cfg["mlp_bias"] or cfg["mamba_proj_bias"] \
            or cfg["attention_bias"] or not cfg["use_conv_bias"] \
            or cfg["n_group"] != 1 or cfg["mamba_hidden_act"] != "silu" \
            or cfg["expand"] * cfg["hidden_size"] \
            != cfg["mamba_num_heads"] * cfg["mamba_head_dim"]:
        raise RuntimeError("the program runs no bias but the convolution's, "
                           "ungrouped routing and SiLU mixers of expand x "
                           "hidden channels; the config says otherwise")
    m = NemotronHForCausalLM(NemotronHConfig(
        vocab_size=cfg["published"]["vocab_size"],
        vocab_held=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        hybrid_override_pattern=cfg["hybrid_override_pattern"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        experts_held=tuple(cfg["experts_held"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_latent_size=cfg["moe_latent_size"],
        moe_shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        mlp_hidden_act=cfg["mlp_hidden_act"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
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

"""Builds the system under test for `minicpm-sala-9b` through the
program's normal entry points: MiniCPMSALAForCausalLM -> GenerationEngine
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
    device (5.6 GB each): check_tolerances.py swaps a server's weights
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


def model_config(cfg, **kw):
    """The program's configuration object for this file's keys."""
    from paddle_tpu.models import MiniCPMSALAConfig

    if cfg["attention_bias"] or cfg["tie_word_embeddings"] \
            or cfg["hidden_act"] != "silu" \
            or cfg["lightning_scale"] != "1/sqrt(d)":
        raise RuntimeError("the program runs no bias, an untied head, SiLU "
                           "and a 1/sqrt(d) Lightning scale; the config "
                           "says otherwise")
    return MiniCPMSALAConfig(**dict(dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        mixer_types=tuple(cfg["mixer_types"]),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], qk_norm=cfg["qk_norm"],
        attn_use_rope=cfg["attn_use_rope"],
        attn_use_output_gate=cfg["attn_use_output_gate"],
        lightning_nh=cfg["lightning_nh"], lightning_nkv=cfg["lightning_nkv"],
        lightning_head_dim=cfg["lightning_head_dim"],
        lightning_use_rope=cfg["lightning_use_rope"],
        use_output_gate=cfg["use_output_gate"],
        use_output_norm=cfg["use_output_norm"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        scale_emb=cfg["scale_emb"], scale_depth=cfg["scale_depth"],
        dim_model_base=cfg["dim_model_base"],
        max_position_embeddings=cfg["max_position_embeddings"],
        sparse_config=dict(cfg["sparse_config"]),
        sparse_q_block=cfg["blocking"]["sparse_q_block"],
        sparse_key_chunk=cfg["blocking"]["sparse_key_chunk"],
        lightning_chunk=cfg["blocking"]["lightning_chunk"],
        layer_offset=cfg["layer_offset"],
        published_layers=cfg["published"]["num_hidden_layers"],
        initializer_range=cfg["assumed_sizes"]["initializer_range"],
        dtype=cfg.get("program_dtype", "bfloat16")), **kw))


def model(cfg, seed):
    from paddle_tpu.models import MiniCPMSALAForCausalLM

    m = MiniCPMSALAForCausalLM(model_config(cfg, init_weights=False))
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

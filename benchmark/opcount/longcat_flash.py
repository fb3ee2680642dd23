"""Operations and bytes of the `longcat_flash` serving cut, from shapes
(and, for the traced run's readers, which device events are whose):
what one chip of the expert-parallel group holds and reads. bfloat16
weights and latent rows (2 bytes)."""

import re

# the program's query blocks at prefill and its key chunks
# (models/longcat_flash.py _PREFILL_BLOCK / _KEY_CHUNK): the readers
# below go by shape
PREFILL_BLOCK, KEY_CHUNK = 256, 4096


def _n(cfg):
    return dict(
        h=cfg["hidden_size"], v=cfg["vocab_size"],
        hq=cfg["num_attention_heads"], qr=cfg["q_lora_rank"],
        kr=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
        row=cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
        ff=cfg["ffn_hidden_size"], f=cfg["expert_ffn_hidden_size"],
        held=cfg["experts_held"][1],
        routed=cfg["published"]["n_routed_experts"],
        zero=cfg["zero_expert_num"], k=cfg["moe_topk"],
        layers=cfg["num_layers"])


def expert_params(cfg):
    """Parameters of one routed expert (gate, up, down)."""
    n = _n(cfg)
    return 3 * n["h"] * n["f"]


def mla_params(cfg):
    """One latent attention: q_a, q_b, kv_a, kv_b, o and the two
    low-rank norms' gains."""
    n = _n(cfg)
    return (n["h"] * n["qr"] + n["qr"] * n["hq"] * (n["nope"] + n["rope"])
            + n["h"] * n["row"] + n["kr"] * n["hq"] * (n["nope"] + n["vd"])
            + n["hq"] * n["vd"] * n["h"] + n["qr"] + n["kr"])


def dense_params(cfg):
    """Everything a decode step reads whatever the routing: a layer's
    two attentions, two dense feed-forwards, four norms, router and
    selection bias; the final norm and the head (the embedding is read
    one row a token)."""
    n = _n(cfg)
    outputs = n["routed"] + n["zero"]
    per_layer = (2 * mla_params(cfg) + 2 * 3 * n["h"] * n["ff"]
                 + 4 * n["h"] + n["h"] * outputs + outputs)
    return n["layers"] * per_layer + n["h"] + n["h"] * n["v"]


def param_count(cfg):
    n = _n(cfg)
    return (dense_params(cfg) + n["v"] * n["h"]
            + n["layers"] * n["held"] * expert_params(cfg))


def expected_experts_hit(cfg, tokens):
    """Distinct held experts that get at least one of ``tokens`` tokens
    when each token's k choices fall evenly over the router's outputs,
    zero-compute ones included: held x (1 - (1 - k/outputs)^tokens)."""
    n = _n(cfg)
    return n["held"] * (1.0 - (1.0 - n["k"] / (n["routed"] + n["zero"]))
                        ** tokens)


def latent_row_bytes(cfg):
    """One ring row of one attention: the latent and the rotated key
    channels, no heads."""
    return 2 * _n(cfg)["row"]


def kv_bytes_per_token(cfg):
    """What one more token costs a slot: a row in each of a layer's two
    latent rings."""
    return 2 * _n(cfg)["layers"] * latent_row_bytes(cfg)


def expert_bytes(cfg, experts_hit):
    """Bytes of routed-expert weights a decode step has to read when its
    expert layers hit ``experts_hit`` held experts between them."""
    return 2 * experts_hit * expert_params(cfg)


def is_expert_kernel(name, text):
    """A device event that is one of the grouped products over the held
    experts: XLA:TPU's Mosaic kernel for `jax.lax.ragged_dot`."""
    return name.startswith("ragged-dot-none")


def is_expert_op(name, text):
    """The grouped products, their group metadata kernel, and what
    takes a kernel's result in."""
    return "ragged-dot" in text


def mla_shapes(cfg):
    """Shapes only the latent attention has: a latent ring or a key
    chunk of it (all slots or a prefill's one; the whole row or its
    latent part), a decode step's scores of all heads over the ring or
    a chunk, and a prefill's float32 score blocks of 256 queries (any
    number of keys)."""
    n, slots = _n(cfg), cfg["engine"]["slots"]
    ring, hq = cfg["engine"]["cache_len"], n["hq"]
    keys = f"({ring}|{min(KEY_CHUNK, ring)})"
    return [rf"\[(1|{slots}),(1,)?{keys},({n['row']}|{n['kr']})\]",
            rf"\[{slots},(1,)?{hq},(1,)?{keys}\]",
            rf"f32\[(1,)?{hq},(1,)?{PREFILL_BLOCK},\d+\]"]


def is_mla_op(text, cfg):
    """A device event whose instruction reads or writes a tensor of one
    of :func:`mla_shapes`."""
    return any(re.search(p, text) for p in mla_shapes(cfg))


def mla_decode_least_s(cfg, rows, peaks):
    """The least time the absorbed attention of one decode step could
    take over ``rows`` live latent rows (summed over slots and
    attentions): the larger of the rows' bytes over the HBM bandwidth
    and their operations over the peak: a row is read once for all
    heads, and each head multiplies it into its scores (`rank + rope`
    wide) and into its latent output (`rank` wide), 2 operations a
    multiply-add."""
    n = _n(cfg)
    ops = rows * n["hq"] * (n["row"] + n["kr"]) * 2
    return max(rows * latent_row_bytes(cfg) / peaks["hbm_bytes_per_s"],
               ops / peaks["flops_per_s"])


def decode_bytes(cfg, live_tokens, slots=None):
    """Least bytes of one decode step: the weights read whatever the
    routing, the experts expected to be hit under even routing, and the
    latent rows of the live tokens."""
    n = _n(cfg)
    slots = cfg["engine"]["slots"] if slots is None else slots
    return (2 * dense_params(cfg)
            + 2 * n["layers"] * expected_experts_hit(cfg, slots)
            * expert_params(cfg)
            + kv_bytes_per_token(cfg) * live_tokens)


def decode_flops(cfg, slots):
    """Two operations a parameter a token: the dense part and the held
    experts a token hits on average (k x held / router outputs)."""
    n = _n(cfg)
    active = dense_params(cfg) + n["layers"] * (
        n["k"] * n["held"] / (n["routed"] + n["zero"])) * expert_params(cfg)
    return 2.0 * active * slots

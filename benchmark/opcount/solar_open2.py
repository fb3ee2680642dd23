"""Operations and bytes of the `solar_open2` serving cut, from shapes
(and, for the traced run's readers, which device events are whose):
what one chip of the expert-parallel group holds and reads. bfloat16
weights and K/V (2 bytes), float32 recurrent state (4 bytes)."""

import re


def _n(cfg):
    lin = cfg["linear_attn_config"]
    a = cfg["assumed_sizes"]
    return dict(
        h=cfg["hidden_size"], v=cfg["vocab_size"],
        q=cfg["num_attention_heads"] * cfg["head_dim"],
        kv=cfg["num_key_value_heads"] * cfg["head_dim"],
        lh=lin["num_heads"], ld=lin["head_dim"],
        d=lin["num_heads"] * lin["head_dim"],
        conv=lin["short_conv_kernel_size"], rank=a["kda_gate_rank"],
        f=cfg["moe_intermediate_size"], fs=a["shared_expert_width"],
        held=cfg["experts_held"][1],
        routed=cfg["published"]["n_routed_experts"],
        k=cfg["num_experts_per_tok"], layers=cfg["num_hidden_layers"],
        gqa=sum(1 for i in range(cfg["num_hidden_layers"])
                if i in cfg["gqa_layers"]))


def expert_params(cfg):
    """Parameters of one routed expert (gate, up, down)."""
    n = _n(cfg)
    return 3 * n["h"] * n["f"]


def mixer_params(cfg, gqa):
    n = _n(cfg)
    if gqa:
        return 3 * n["h"] * n["q"] + 2 * n["h"] * n["kv"]
    return (4 * n["h"] * n["d"] + 2 * (n["h"] * n["rank"] + n["rank"] * n["d"])
            + n["h"] * n["lh"] + n["conv"] * 3 * n["d"] + n["lh"] + n["d"]
            + n["ld"])


def dense_params(cfg):
    """Everything a decode step reads whatever the routing: mixers,
    routers, shared experts, norms, the head (the embedding is read one
    row a token)."""
    n = _n(cfg)
    per_layer = (n["h"] * n["routed"] + 3 * n["h"] * n["fs"] + 2 * n["h"])
    lin = n["layers"] - n["gqa"]
    return (n["gqa"] * mixer_params(cfg, True)
            + lin * mixer_params(cfg, False) + n["layers"] * per_layer
            + n["h"] + n["h"] * n["v"])


def param_count(cfg):
    n = _n(cfg)
    return (dense_params(cfg) + n["v"] * n["h"]
            + n["layers"] * n["held"] * expert_params(cfg))


def expected_experts_hit(cfg, tokens):
    """Distinct held experts that get at least one of ``tokens`` tokens
    under uniform routing: held x (1 - (1 - k/routed)^tokens)."""
    n = _n(cfg)
    return n["held"] * (1.0 - (1.0 - n["k"] / n["routed"]) ** tokens)


def state_bytes_per_slot(cfg):
    """The linear layers' float32 state and bfloat16 convolution tail."""
    n = _n(cfg)
    lin = n["layers"] - n["gqa"]
    return lin * (n["lh"] * n["ld"] * n["ld"] * 4
                  + (n["conv"] - 1) * 3 * n["d"] * 2)


def kv_bytes_per_token(cfg):
    n = _n(cfg)
    return n["gqa"] * 2 * n["kv"] * 2


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


def is_state_op(text, cfg):
    """A device event whose instruction reads or writes a tensor of the
    recurrent state's shape, or of the chunked prefill's per-chunk
    shapes (chunks of 64: `nn/linear_attention.py` CHUNK)."""
    n = _n(cfg)
    lh, ld = n["lh"], n["ld"]
    return any(re.search(p, text) for p in (
        rf"f32\[\d+,{lh},{ld},{ld}\]",            # the state, any rows
        rf"f32\[(\d+,)?\d+,{lh},64,64(,{ld})?\]",  # chunk x chunk (x channel)
        rf"f32\[(\d+,)?\d+,{lh},64,{ld}\]"))      # a chunk's q, k, v, g


def decode_bytes(cfg, live_tokens, slots=None):
    """Least bytes of one decode step: the weights read whatever the
    routing, the experts expected to be hit under uniform routing, every
    slot's state read and written, and the K/V of the live tokens."""
    n = _n(cfg)
    slots = cfg["engine"]["slots"] if slots is None else slots
    return (2 * dense_params(cfg)
            + 2 * n["layers"] * expected_experts_hit(cfg, slots)
            * expert_params(cfg)
            + 2 * slots * state_bytes_per_slot(cfg)
            + kv_bytes_per_token(cfg) * live_tokens)


def decode_flops(cfg, slots):
    """Two operations a parameter a token: the dense part and the
    held experts a token hits on average (k x held / routed)."""
    n = _n(cfg)
    active = dense_params(cfg) + n["layers"] * (
        n["k"] * n["held"] / n["routed"]) * expert_params(cfg)
    return 2.0 * active * slots

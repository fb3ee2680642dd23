"""Operations and bytes of the `nemotron_h` serving cut, from shapes
(and, for the traced run's readers, which device events are whose):
what one chip of the expert-parallel group holds and reads. bfloat16
weights, K/V and convolution tail (2 bytes), float32 recurrent state (4
bytes). `cfg["hybrid_override_pattern"]` says what each layer is: `M` a
Mamba-2 mixer, `*` grouped-query attention, `E` latent-width routed
experts."""

import re


def _n(cfg):
    pattern = cfg["hybrid_override_pattern"]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    return dict(
        h=cfg["hidden_size"], v=cfg["vocab_size"],
        q=cfg["num_attention_heads"] * cfg["head_dim"],
        kv=cfg["num_key_value_heads"] * cfg["head_dim"],
        mh=heads, mp=p, mg=groups, mn=state, inner=heads * p,
        conv_dim=heads * p + 2 * groups * state, conv=cfg["conv_kernel"],
        chunk=cfg["chunk_size"], l=cfg["moe_latent_size"],
        f=cfg["moe_intermediate_size"],
        fs=cfg["moe_shared_expert_intermediate_size"],
        held=cfg["experts_held"][1],
        routed=cfg["published"]["n_routed_experts"],
        k=cfg["num_experts_per_tok"], layers=len(pattern),
        m=pattern.count("M"), a=pattern.count("*"), e=pattern.count("E"))


def expert_params(cfg):
    """Parameters of one routed expert: two matrices at the latent
    width (relu2 has no gate)."""
    n = _n(cfg)
    return 2 * n["l"] * n["f"]


def mixer_params(cfg, kind):
    """Parameters of an `M` or `*` layer's module (its norm aside)."""
    n = _n(cfg)
    if kind == "*":
        return 2 * n["h"] * n["q"] + 2 * n["h"] * n["kv"]
    return (n["h"] * (n["inner"] + n["conv_dim"] + n["mh"])
            + (n["conv"] + 1) * n["conv_dim"] + 3 * n["mh"] + n["inner"]
            + n["inner"] * n["h"])


def expert_layer_dense_params(cfg):
    """What an `E` layer reads whatever the routing: router, selection
    bias, the latent's two projections, the shared expert."""
    n = _n(cfg)
    return (n["h"] * n["routed"] + n["routed"] + 2 * n["h"] * n["l"]
            + 2 * n["h"] * n["fs"])


def dense_params(cfg):
    """Everything a decode step reads whatever the routing: mixers,
    attention, routers, latent projections, shared experts, norms, the
    head (the embedding is read one row a token)."""
    n = _n(cfg)
    return (n["m"] * mixer_params(cfg, "M") + n["a"] * mixer_params(cfg, "*")
            + n["e"] * expert_layer_dense_params(cfg)
            + (n["layers"] + 1) * n["h"] + n["h"] * n["v"])


def param_count(cfg):
    n = _n(cfg)
    return (dense_params(cfg) + n["v"] * n["h"]
            + n["e"] * n["held"] * expert_params(cfg))


def active_params(cfg):
    """Parameters one token passes through: the dense part with one
    embedding row's worth of head, and top-k experts a layer."""
    n = _n(cfg)
    return dense_params(cfg) + n["e"] * n["k"] * expert_params(cfg)


def expected_experts_hit(cfg, tokens):
    """Distinct held experts that get at least one of ``tokens`` tokens
    under uniform routing: held x (1 - (1 - k/routed)^tokens)."""
    n = _n(cfg)
    return n["held"] * (1.0 - (1.0 - n["k"] / n["routed"]) ** tokens)


def state_bytes_per_slot(cfg, tail=True):
    """The `M` layers' float32 states and, with ``tail``, their bfloat16
    convolution tails."""
    n = _n(cfg)
    return n["m"] * (n["mh"] * n["mp"] * n["mn"] * 4
                     + (n["conv"] - 1) * n["conv_dim"] * 2 * bool(tail))


def kv_bytes_per_token(cfg):
    n = _n(cfg)
    return n["a"] * 2 * n["kv"] * 2


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
    """A device event whose instruction reads or writes a float32 tensor
    of the recurrent state's shape `[rows, heads, head_dim, state]` (the
    decode step's pass over every slot's state, the admission's write of
    one, the chunk borders of a prompt's scan, in heads or in groups x
    heads-a-group) or of the chunked scan's chunk shapes: the chunk x
    chunk decay and scores, a chunk's outputs a head (as the compiler
    keeps them: my AOT compile for a described v5e, PR 40)."""
    n = _n(cfg)
    h, p, s, g, c = n["mh"], n["mp"], n["mn"], n["mg"], n["chunk"]
    r = h // g
    return any(re.search(pat, text) for pat in (
        rf"f32\[(\d+,)*({h}|{g},{r}),{p},{s}\]",       # states
        rf"f32\[(\d+,)*{c},{c},({h}|{g},{r})\]",       # decay, t x s a head
        rf"f32\[(\d+,)*{g},{c},{c}\]",                 # C B^T a group
        rf"f32\[(\d+,)*{c},({h}|{g},{r}),{p}\]"))     # a chunk's outputs


def decode_bytes(cfg, live_tokens, slots=None):
    """Least bytes of one decode step: the weights read whatever the
    routing, the experts expected to be hit under uniform routing, every
    slot's state and tail read and written, and the K/V of the live
    tokens."""
    n = _n(cfg)
    slots = cfg["engine"]["slots"] if slots is None else slots
    return (2 * dense_params(cfg)
            + 2 * n["e"] * expected_experts_hit(cfg, slots)
            * expert_params(cfg)
            + 2 * slots * state_bytes_per_slot(cfg)
            + kv_bytes_per_token(cfg) * live_tokens)


def decode_flops(cfg, slots):
    """Two operations a parameter a token: the dense part and the
    held experts a token hits on average (k x held / routed)."""
    n = _n(cfg)
    active = dense_params(cfg) + n["e"] * (
        n["k"] * n["held"] / n["routed"]) * expert_params(cfg)
    return 2.0 * active * slots

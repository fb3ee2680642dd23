"""Operations and bytes of the `exaone_moe` serving cut, from shapes
(and, for the traced run's readers, which device events are whose):
what one chip of the expert-parallel group holds and reads. bfloat16
weights and K/V rows (2 bytes)."""

import re

# the program's query blocks at prefill (models/exaone_moe.py
# _FULL_BLOCK / _WINDOW_BLOCK): the readers below go by shape
FULL_BLOCK, WINDOW_BLOCK = 128, 512


def _n(cfg):
    layers = cfg["num_hidden_layers"]
    types = cfg["layer_types"][:layers]
    return dict(
        h=cfg["hidden_size"], v=cfg["vocab_size"],
        hq=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
        hd=cfg["head_dim"],
        q=cfg["num_attention_heads"] * cfg["head_dim"],
        kv=cfg["num_key_value_heads"] * cfg["head_dim"],
        ff=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
        fs=cfg["assumed_sizes"]["shared_expert_width"],
        held=cfg["experts_held"][1],
        routed=cfg["published"]["num_experts"],
        k=cfg["num_experts_per_tok"], layers=layers,
        dense=min(cfg["first_k_dense_replace"], layers),
        full=sum(t == "full_attention" for t in types),
        sliding=sum(t == "sliding_attention" for t in types),
        window=cfg["sliding_window"])


def expert_params(cfg):
    """Parameters of one routed expert (gate, up, down)."""
    n = _n(cfg)
    return 3 * n["h"] * n["f"]


def mixer_params(cfg):
    """One attention block: q, o, k, v and the two per-head gains."""
    n = _n(cfg)
    return 2 * n["h"] * n["q"] + 2 * n["h"] * n["kv"] + 2 * n["hd"]


def dense_params(cfg):
    """Everything a decode step reads whatever the routing: mixers, the
    leading dense feed-forward, routers, shared experts, norms, the head
    (the embedding is read one row a token)."""
    n = _n(cfg)
    sparse = n["layers"] - n["dense"]
    return (n["layers"] * (mixer_params(cfg) + 2 * n["h"])
            + n["dense"] * 3 * n["h"] * n["ff"]
            + sparse * (n["h"] * n["routed"] + 3 * n["h"] * n["fs"])
            + n["h"] + n["h"] * n["v"])


def param_count(cfg):
    n = _n(cfg)
    return (dense_params(cfg) + n["v"] * n["h"]
            + (n["layers"] - n["dense"]) * n["held"] * expert_params(cfg))


def expected_experts_hit(cfg, tokens):
    """Distinct held experts that get at least one of ``tokens`` tokens
    under uniform routing: held x (1 - (1 - k/routed)^tokens)."""
    n = _n(cfg)
    return n["held"] * (1.0 - (1.0 - n["k"] / n["routed"]) ** tokens)


def kv_row_bytes(cfg):
    """One ring row of one layer: K and V for the K/V heads."""
    return 2 * _n(cfg)["kv"] * 2


def kv_bytes_per_token(cfg):
    """What one more token costs a slot: a row in each full layer (a
    sliding layer's ring is full after 128)."""
    return _n(cfg)["full"] * kv_row_bytes(cfg)


def window_bytes_per_slot(cfg):
    """The sliding layers' rings, `sliding_window` rows each."""
    n = _n(cfg)
    return n["sliding"] * n["window"] * kv_row_bytes(cfg)


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


def _attention_shapes(cfg, ring, block):
    """Shapes only one kind of attention layer has: its ring (all slots
    or a prefill's one), and a prefill's float32 score blocks of
    ``block`` queries (any number of keys; a bfloat16 ``[8, 8, 128,
    hidden]`` is the q projection's weight as the compiler views it,
    and is left out)."""
    n, slots = _n(cfg), cfg["engine"]["slots"]
    g = n["hq"] // n["hkv"]
    return [rf"\[(1|{slots}),{n['hkv']},{ring},{n['hd']}\]",
            rf"f32\[(1,)?{n['hkv']},{g},{block},\d+\]",
            rf"f32\[(1,)?{n['hq']},{block},\d+\]"]


def is_full_attn_op(text, cfg):
    """A device event whose instruction reads or writes a tensor of the
    full-length ring's shape, of a decode step's scores over it, or of a
    full layer's prefill score blocks (128 queries a block)."""
    n, slots, ring = _n(cfg), cfg["engine"]["slots"], \
        cfg["engine"]["cache_len"]
    # a decode step's scores over the full ring, as the compiler keeps
    # them: [slots, 8, 8, 16384] (my AOT compile, PR 33)
    scores = rf"\[{slots},{n['hkv']},{n['hq'] // n['hkv']},(1,)?{ring}\]"
    return any(re.search(p, text) for p in [scores] + _attention_shapes(
        cfg, ring, FULL_BLOCK))


def is_window_attn_op(text, cfg):
    """The same for the sliding layers: rings of `sliding_window` rows
    and the banded blocks of 512 queries, and no full-length shape
    beside them. A decode step's scores over a window ring are not
    looked for: `[slots, 8, 8, 128]` is also a query of one token, the
    head being as wide as the window is long; the products that make and
    use them read the ring and are counted by its shape."""
    return not is_full_attn_op(text, cfg) and any(
        re.search(p, text) for p in _attention_shapes(
            cfg, cfg["sliding_window"], WINDOW_BLOCK))


def decode_bytes(cfg, live_tokens, slots=None):
    """Least bytes of one decode step: the weights read whatever the
    routing, the experts expected to be hit under uniform routing, the
    full layers' rows of the live tokens, and the sliding layers' rings
    of every slot (full after 128 tokens; every prompt is longer)."""
    n = _n(cfg)
    slots = cfg["engine"]["slots"] if slots is None else slots
    return (2 * dense_params(cfg)
            + 2 * (n["layers"] - n["dense"])
            * expected_experts_hit(cfg, slots) * expert_params(cfg)
            + kv_bytes_per_token(cfg) * live_tokens
            + slots * window_bytes_per_slot(cfg))


def decode_flops(cfg, slots):
    """Two operations a parameter a token: the dense part and the held
    experts a token hits on average (k x held / routed)."""
    n = _n(cfg)
    active = dense_params(cfg) + (n["layers"] - n["dense"]) * (
        n["k"] * n["held"] / n["routed"]) * expert_params(cfg)
    return 2.0 * active * slots

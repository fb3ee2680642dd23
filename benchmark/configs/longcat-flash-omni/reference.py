"""The language model of `LongCat-Flash-Omni` in plain jax.numpy, written
from the public config.json of `meituan-longcat/LongCat-Flash-Omni` and
the equations of ISSUE 36 (Tentpole 1): float32, `highest` matmul
precision, no cache, no ring, no kernels, no batching, and of the two
attention paths the EXPANDED one only. It imports nothing of the program
and makes the weights both sides get.

One sequence at a time. RMSNorm eps 1e-5, no biases, untied head, final
RMSNorm. A layer is a double layer:

    a  = x + Attn0(RMSNorm_in0(x))
    u  = RMSNorm_post0(a)
    s  = MoE(u)                       # the shortcut branch
    b  = a + FFN0(u)
    c  = b + Attn1(RMSNorm_in1(b))
    x' = c + FFN1(RMSNorm_post1(c)) + s

Attention is multi-head latent attention: `q = RMSNorm(x Wqa) Wqb *
sqrt(hidden / q_lora_rank)`, a head `[q_nope 128 ; q_rot 64]`; `[c_raw ;
k_rot] = x Wkva`, `c = RMSNorm(c_raw) * sqrt(hidden / kv_lora_rank)`;
`q_rot` and `k_rot` rotated by the position over their 64 channels in
interleaved pairs (2i, 2i+1), `theta_i = rope_theta^(-2i/64)`; `k_rot`
is one vector for all heads; `[k_nope_h ; v_h] = c Wkvb[h]`; scores
`(q_nope_h . k_nope_h + q_rot_h . k_rot) / sqrt(192)`, causal, softmax,
`o_h = sum p v_h`, output `concat(o_h) Wo`. The mask is built from its
definition over all T keys (`_keep`): nothing here knows of a ring, of a
latent cache or of an absorbed product.

The expert branch: `r = softmax(u Wr)` over `n_routed_experts +
zero_expert_num` outputs, the `moe_topk` largest of `r + select_bias`
chosen, weights `routed_scaling_factor * r` of the chosen (not
renormalised); a routed expert is a SwiGLU of `expert_ffn_hidden_size`,
a zero-compute expert returns the token. This chip's share: the router
scores all `published.n_routed_experts + zero_expert_num`, only the
experts `experts_held` add their terms, here by a plain loop over them,
and the zero experts' term `(sum of their weights) * u` is added whole
(the token's own chip computes it); the embedding and the head are rows
`0 .. vocab_size-1` of the published vocabulary. That partial result is
what goes on to the next layer, as in the program.

What the published config leaves open is listed in config.json under
`assumed`. Departures from a plain reading, each because memory forces
it and none changing a value: attention is computed by blocks of queries
(the whole score tensor of 8,192 tokens is 17 GB), the weights are kept
at the bfloat16 values both sides are given and widened to float32 where
they are used (whole, float32 weights are 20.7 GB; every value is
exactly a bfloat16, so nothing is rounded by that), and `rows` lets the
check ask for the logits of the positions it reads only.

``weights`` draws every leaf from its own `fold_in` of the seed's key and
rounds it to bfloat16: program and reference compute with the same
values, so only the arithmetic differs. With ``control`` every matrix
product's operands are rounded to float8 e4m3 first (per-tensor scale):
the model one precision below the bfloat16 the configuration states.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128


def _widths(cfg):
    return {
        "h": cfg["hidden_size"], "v": cfg["vocab_size"],
        "hq": cfg["num_attention_heads"], "qr": cfg["q_lora_rank"],
        "kr": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "vd": cfg["v_head_dim"],
        "ff": cfg["ffn_hidden_size"], "f": cfg["expert_ffn_hidden_size"],
        "held": cfg["experts_held"][1],
        "routed": cfg["published"]["n_routed_experts"],
        "zero": cfg["zero_expert_num"],
    }


def _layer_shapes(n, p):
    """One double layer's leaves under the prefix ``p``."""
    out = {p + name: (n["h"],) for name in (
        "input_norm_0", "post_norm_0", "input_norm_1", "post_norm_1")}
    for j in (0, 1):
        a, m = f"{p}attn.{j}.", f"{p}mlp.{j}."
        out.update({
            a + "wq_a": (n["h"], n["qr"]), a + "q_norm": (n["qr"],),
            a + "wq_b": (n["qr"], n["hq"] * (n["nope"] + n["rope"])),
            a + "wkv_a": (n["h"], n["kr"] + n["rope"]),
            a + "kv_norm": (n["kr"],),
            a + "wkv_b": (n["kr"], n["hq"] * (n["nope"] + n["vd"])),
            a + "wo": (n["hq"] * n["vd"], n["h"]),
            m + "w_gate": (n["h"], n["ff"]), m + "w_up": (n["h"], n["ff"]),
            m + "w_down": (n["ff"], n["h"])})
    out.update({
        p + "moe.router": (n["h"], n["routed"] + n["zero"]),
        p + "moe.select_bias": (n["routed"] + n["zero"],),
        p + "moe.w_gate": (n["held"], n["h"], n["f"]),
        p + "moe.w_up": (n["held"], n["h"], n["f"]),
        p + "moe.w_down": (n["held"], n["f"], n["h"])})
    return out


def leaf_shapes(cfg):
    """{leaf name: shape}, every leaf of the cut model. Linear weights
    are [in, out]; an expert stack is [held, in, out]."""
    n = _widths(cfg)
    out = {"embed_tokens": (n["v"], n["h"]), "lm_head": (n["h"], n["v"]),
           "norm": (n["h"],)}
    for i in range(cfg["num_layers"]):
        out.update(_layer_shapes(n, f"layers.{i}."))
    return out


def leaf_tag(name):
    """The number a leaf's key is folded with: a hash of its name."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def _leaf_kind(name):
    last = name.rsplit(".", 1)[-1]
    return "bias" if last == "select_bias" else \
        "norm" if "norm" in last else "matrix"


def leaf(cfg, key, name, shape=None, tag=None):
    """One leaf, bfloat16: normal(0, initializer_range) for matrices and
    embeddings, 1 + that for norm gains; the router's selection bias is
    float32 zeros (config.json `assumed`). ``tag`` is
    ``leaf_tag(name)``; a caller that compiles one maker for all leaves
    of a kind and shape passes it as an argument."""
    shape = leaf_shapes(cfg)[name] if shape is None else shape
    kind = _leaf_kind(name)
    if kind == "bias":
        return jnp.zeros(shape, jnp.float32)
    k = jax.random.fold_in(key, leaf_tag(name) if tag is None else tag)
    x = jax.random.normal(k, shape, jnp.float32) \
        * cfg["assumed_sizes"]["initializer_range"]
    if kind == "norm":
        x = 1.0 + x
    return x.astype(jnp.bfloat16)


_MAKERS = {}


def make_leaf(cfg, key, name, shape=None):
    """`leaf`, compiled: one program for all leaves of a kind and shape,
    the leaf's own tag an argument. Made one at a time, a set of weights
    never needs more room than itself and one leaf."""
    shape = leaf_shapes(cfg)[name] if shape is None else shape
    std = cfg["assumed_sizes"]["initializer_range"]
    kind = (_leaf_kind(name), shape, std)
    if kind not in _MAKERS:
        _MAKERS[kind] = jax.jit(
            lambda key, tag: leaf(cfg, key, name, shape, tag=tag))
    return _MAKERS[kind](key, leaf_tag(name))


def weights(cfg, key):
    """Every leaf (`make_leaf`), by name."""
    return {name: make_leaf(cfg, key, name, shape)
            for name, shape in leaf_shapes(cfg).items()}


def _fp8(x):
    s = 224.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rotate(x, theta):
    """``x [T, ..., D]`` rotated by its row's position: channel 2i pairs
    with 2i + 1, the angle position x theta^(-2i/D)."""
    t, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x2 * jnp.cos(ang) + x1 * jnp.sin(ang)],
                     -1).reshape(x.shape)


def _keep(rows, cols, context):
    """The mask's definition: query ``rows`` sees key ``cols`` iff the
    key is not ahead of it and, where a ring of ``context`` rows is being
    described (tests), fewer than ``context`` positions behind."""
    keep = cols[None, :] <= rows[:, None]
    if context is not None:
        keep = keep & (cols[None, :] > rows[:, None] - context)
    return keep


def _attention(x, w, n, cfg, mm, context):
    t, hq, eps = x.shape[0], n["hq"], cfg["rms_norm_eps"]
    theta = cfg["rope_theta"]
    q = mm(_rms(mm(x, w["wq_a"]), w["q_norm"], eps), w["wq_b"])
    if cfg["mla_scale_q_lora"]:
        q = q * (n["h"] / n["qr"]) ** 0.5
    q = q.reshape(t, hq, n["nope"] + n["rope"])
    kv = mm(x, w["wkv_a"])
    c = _rms(kv[:, :n["kr"]], w["kv_norm"], eps)
    if cfg["mla_scale_kv_lora"]:
        c = c * (n["h"] / n["kr"]) ** 0.5
    k_rot = _rotate(kv[:, n["kr"]:], theta)                    # [T, rope]
    q = jnp.concatenate([q[..., :n["nope"]],
                         _rotate(q[..., n["nope"]:], theta)], -1)
    kvh = mm(c, w["wkv_b"]).reshape(t, hq, n["nope"] + n["vd"])
    k = jnp.concatenate([
        kvh[..., :n["nope"]],
        jnp.broadcast_to(k_rot[:, None], (t, hq, n["rope"]))], -1)
    v = kvh[..., n["nope"]:]
    pad = -t % QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, hq, n["nope"] + n["rope"])
    rows = jnp.arange(t + pad).reshape(-1, QUERY_BLOCK)
    cols = jnp.arange(t)

    def block(args):
        qi, ri = args
        s = mm(qi.transpose(1, 0, 2), k.transpose(1, 2, 0)) \
            * (n["nope"] + n["rope"]) ** -0.5               # [hq, Q, t]
        p = jax.nn.softmax(jnp.where(_keep(ri, cols, context), s, -1e30),
                           axis=-1)
        return mm(p, v.transpose(1, 0, 2)).transpose(1, 0, 2)

    o = jax.lax.map(block, (qb, rows)).reshape(t + pad, -1)[:t]
    return mm(o, w["wo"])


def _swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def route(x, w, n, cfg):
    """``(idx [T, k], weight [T, k])``: the chosen outputs of the router
    (a routed expert below ``published.n_routed_experts``, a zero-compute
    one from there on) and their weights. float32 in the program and in
    the control alike: a choice of experts is no matmul operand to
    round."""
    r = jax.nn.softmax(jnp.matmul(x, w["router"]), axis=-1)
    _, idx = jax.lax.top_k(r + w["select_bias"], cfg["moe_topk"])
    return idx, jnp.take_along_axis(r, idx, -1) \
        * cfg["routed_scaling_factor"]


def moe(x, w, n, cfg, mm, zero_term=True):
    """This share's part of the expert branch: the held experts' terms,
    and (``zero_term``) the zero-compute experts' ``(sum of their
    weights) * x``."""
    first = cfg["experts_held"][0]
    idx, top = route(x, w, n, cfg)

    def expert(y, e):
        i, wg, wu, wd = e
        share = jnp.where(idx == i + first, top, 0.0).sum(-1)
        return y + share[:, None] * _swiglu(x, wg, wu, wd, mm), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        jnp.arange(n["held"]), w["w_gate"], w["w_up"], w["w_down"]))
    if zero_term:
        y = y + jnp.where(idx >= n["routed"], top, 0.0).sum(
            -1, keepdims=True) * x
    return y


def _sub(w, prefix, widen):
    """The leaves under ``prefix``, by the rest of their name."""
    return {k[len(prefix):]: (v.astype(jnp.float32) if widen else v)
            for k, v in w.items() if k.startswith(prefix)}


def layer(x, w, p, n, cfg, mm, context=None):
    """One double layer, the six lines of the module's docstring."""
    eps, f32 = cfg["rms_norm_eps"], jnp.float32

    def norm(y, name):
        return _rms(y, w[p + name].astype(f32), eps)

    def ffn(y, j):
        d = _sub(w, f"{p}mlp.{j}.", True)
        return _swiglu(y, d["w_gate"], d["w_up"], d["w_down"], mm)

    def attn(y, j):
        return _attention(y, _sub(w, f"{p}attn.{j}.", True), n, cfg, mm,
                          context)

    a = x + attn(norm(x, "input_norm_0"), 0)
    u = norm(a, "post_norm_0")
    # the expert stacks stay bfloat16 until an expert is used
    e = _sub(w, p + "moe.", False)
    e["router"] = e["router"].astype(f32)
    e["select_bias"] = e["select_bias"].astype(f32)
    s = moe(u, e, n, cfg, mm)
    b = a + ffn(u, 0)
    c = b + attn(norm(b, "input_norm_1"), 1)
    return c + ffn(norm(c, "post_norm_1"), 1) + s


def _mm(control):
    def mm(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if control:
            a, b = _fp8(a), _fp8(b)
        return jnp.matmul(a, b)
    return mm


def hidden(w, tokens, cfg, control=False, context=None):
    """The stack's output before the final norm, [T, hidden]."""
    n, mm = _widths(cfg), _mm(control)
    x = w["embed_tokens"][tokens].astype(jnp.float32)
    for i in range(cfg["num_layers"]):
        x = layer(x, w, f"layers.{i}.", n, cfg, mm, context)
    return x


def forward(w, tokens, cfg, control=False, context=None, rows=None):
    """Logits [T, vocab_size] in float32 for token ids [T]. ``context``:
    what a ring of that many rows computes past its length (tests); None
    is plain causal attention. ``rows = (start, count)``: the logits of
    positions start .. start+count-1 only."""
    with jax.default_matmul_precision("highest"):
        x = hidden(w, tokens, cfg, control, context)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], 0)
        x = _rms(x, w["norm"].astype(jnp.float32), cfg["rms_norm_eps"])
        return _mm(control)(x, w["lm_head"])

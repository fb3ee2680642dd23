"""The `exaone_moe` architecture in plain jax.numpy, written from the
public config.json of `LGAI-EXAONE/K-EXAONE-236B-A23B` and the equations
of ISSUE 33 (Tentpole 1): float32, `highest` matmul precision, no cache,
no ring, no kernels, no batching. It imports nothing of the program and
makes the weights both sides get.

One sequence at a time. Pre-norm residual blocks with RMSNorm and no
biases. Every mixer is grouped-query softmax attention, q and k
RMS-normalised per head with a learned gain. A `sliding_attention` layer
rotates q and k by their absolute position (full rotary, half-split
pairs, theta_i = rope_theta^(-2i/128)) and query t sees keys t-127 .. t;
a `full_attention` layer is causal over the whole context and has no
position signal. Both masks are built from those definitions over all T
keys (`_keep`): nothing here knows of a ring or of a band of blocks.
Layer 0's feed-forward is a dense SwiGLU; the others are routed experts
with a sigmoid router, the 8 largest renormalised and scaled by 2.5,
plus one shared expert, here a plain loop over the experts held. This
chip's share: the router scores all `published.num_experts`, the weights
are normalised over all 8 chosen, and only the experts `experts_held`
(and the shared one) add to the result; the embedding and the head are
rows `0 .. vocab_size-1` of the published vocabulary. That partial
result is what goes on to the next layer, as in the program. With
`num_nextn_predict_layers` 1, `predict_ahead` is the family's
multi-token-prediction module, teacher-forced.

What the published config leaves open is listed in config.json under
`assumed`. Departures from a plain reading, each because memory forces
it and none changing a value: attention is computed by blocks of queries
(the whole score tensor of 16,384 tokens is 69 GB), the weights are kept
at the bfloat16 values both sides are given and widened to float32 where
they are used (whole, float32 weights are 14.8 GB; every value is
exactly a bfloat16, so nothing is rounded by that), and `rows` lets the
check ask for the logits of the positions it reads only (all of them at
16,384 x 19,200 are 1.3 GB).

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
        "hq": cfg["num_attention_heads"], "hkv": cfg["num_key_value_heads"],
        "d": cfg["head_dim"], "ff": cfg["intermediate_size"],
        "f": cfg["moe_intermediate_size"],
        "fs": cfg["assumed_sizes"]["shared_expert_width"],
        "held": cfg["experts_held"][1],
        "routed": cfg["published"]["num_experts"],
    }


def _block_shapes(n, p, dense):
    """One decoder block's leaves under the prefix ``p``."""
    q, kv = n["hq"] * n["d"], n["hkv"] * n["d"]
    out = {p + "input_norm": (n["h"],), p + "post_norm": (n["h"],),
           p + "mixer.wq": (n["h"], q), p + "mixer.wk": (n["h"], kv),
           p + "mixer.wv": (n["h"], kv), p + "mixer.wo": (q, n["h"]),
           p + "mixer.q_norm": (n["d"],), p + "mixer.k_norm": (n["d"],)}
    if dense:
        out.update({p + "mlp.w_gate": (n["h"], n["ff"]),
                    p + "mlp.w_up": (n["h"], n["ff"]),
                    p + "mlp.w_down": (n["ff"], n["h"])})
    else:
        out.update({
            p + "moe.router": (n["h"], n["routed"]),
            p + "moe.w_gate": (n["held"], n["h"], n["f"]),
            p + "moe.w_up": (n["held"], n["h"], n["f"]),
            p + "moe.w_down": (n["held"], n["f"], n["h"]),
            p + "moe.shared_gate": (n["h"], n["fs"]),
            p + "moe.shared_up": (n["h"], n["fs"]),
            p + "moe.shared_down": (n["fs"], n["h"])})
    return out


def leaf_shapes(cfg):
    """{leaf name: shape}, every leaf of the cut model. Linear weights
    are [in, out]; an expert stack is [held, in, out]."""
    n = _widths(cfg)
    out = {"embed_tokens": (n["v"], n["h"]), "lm_head": (n["h"], n["v"]),
           "norm": (n["h"],)}
    for i in range(cfg["num_hidden_layers"]):
        out.update(_block_shapes(n, f"layers.{i}.",
                                 i < cfg["first_k_dense_replace"]))
    if cfg["num_nextn_predict_layers"]:
        out.update({"mtp_hidden_norm": (n["h"],),
                    "mtp_embed_norm": (n["h"],),
                    "mtp_proj": (2 * n["h"], n["h"])})
        out.update(_block_shapes(n, "mtp_block.", False))
    return out


def leaf_tag(name):
    """The number a leaf's key is folded with: a hash of its name."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf(cfg, key, name, shape=None, tag=None):
    """One leaf, bfloat16: normal(0, initializer_range) for matrices and
    embeddings, 1 + that for norm gains. ``tag`` is ``leaf_tag(name)``;
    a caller that compiles one maker for all leaves of a kind and shape
    passes it as an argument."""
    shape = leaf_shapes(cfg)[name] if shape is None else shape
    k = jax.random.fold_in(key, leaf_tag(name) if tag is None else tag)
    x = jax.random.normal(k, shape, jnp.float32) \
        * cfg["assumed_sizes"]["initializer_range"]
    if name.rsplit(".", 1)[-1].endswith("norm"):
        x = 1.0 + x
    return x.astype(jnp.bfloat16)


_MAKERS = {}


def make_leaf(cfg, key, name, shape=None):
    """`leaf`, compiled: one program for all leaves of a kind (a norm
    gain or not) and shape, the leaf's own tag an argument. Made one at
    a time, a set of weights never needs more room than itself and one
    leaf."""
    shape = leaf_shapes(cfg)[name] if shape is None else shape
    std = cfg["assumed_sizes"]["initializer_range"]
    kind = (name.rsplit(".", 1)[-1].endswith("norm"), shape, std)
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
    """``x [T, ..., D]`` rotated by its row's position: channel i < D/2
    pairs with i + D/2, the angle position x theta^(-2i/D)."""
    t, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _keep(rows, cols, window):
    """The mask's definition: query ``rows`` sees key ``cols`` iff the
    key is not ahead of it and, in a layer that sees ``window`` keys,
    fewer than ``window`` positions behind."""
    keep = cols[None, :] <= rows[:, None]
    if window is not None:
        keep = keep & (cols[None, :] > rows[:, None] - window)
    return keep


def _attention(x, w, n, mm, eps, window, theta):
    t = x.shape[0]
    g = n["hq"] // n["hkv"]
    q = _rms(mm(x, w["wq"]).reshape(t, n["hkv"], g, n["d"]), w["q_norm"], eps)
    k = _rms(mm(x, w["wk"]).reshape(t, n["hkv"], n["d"]), w["k_norm"], eps)
    v = mm(x, w["wv"]).reshape(t, n["hkv"], n["d"])
    if theta is not None:
        q, k = _rotate(q, theta), _rotate(k, theta)
    pad = -t % QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, n["hkv"], g, n["d"])
    rows = jnp.arange(t + pad).reshape(-1, QUERY_BLOCK)
    cols = jnp.arange(t)

    def block(args):
        qi, ri = args
        s = mm(qi.transpose(1, 2, 0, 3), k.transpose(1, 2, 0)[:, None]) \
            * n["d"] ** -0.5                            # [hkv, g, Q, t]
        p = jax.nn.softmax(jnp.where(_keep(ri, cols, window), s, -1e30),
                           axis=-1)
        return mm(p, v.transpose(1, 0, 2)[:, None]).transpose(2, 0, 1, 3)

    o = jax.lax.map(block, (qb, rows)).reshape(t + pad, -1)[:t]
    return mm(o, w["wo"])


def _swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def _moe(x, w, n, cfg, mm):
    first = cfg["experts_held"][0]
    # the router is float32 in the program and in the control alike: a
    # choice of experts is no matmul operand to round
    s = jax.nn.sigmoid(jnp.matmul(x, w["router"]))
    top, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    top = top / top.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]

    def expert(y, e):
        i, wg, wu, wd = e
        share = jnp.where(idx == i + first, top, 0.0).sum(-1)
        return y + share[:, None] * _swiglu(x, wg, wu, wd, mm), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        jnp.arange(n["held"]), w["w_gate"], w["w_up"], w["w_down"]))
    return y + _swiglu(x, w["shared_gate"], w["shared_up"],
                       w["shared_down"], mm)


def _sub(w, prefix, widen):
    """The leaves under ``prefix``, by the rest of their name."""
    return {k[len(prefix):]: (v.astype(jnp.float32) if widen else v)
            for k, v in w.items() if k.startswith(prefix)}


def _block(x, w, p, n, cfg, mm, sliding, dense, context=None):
    eps = cfg["rms_norm_eps"]
    f32 = jnp.float32
    y = _rms(x, w[p + "input_norm"].astype(f32), eps)
    x = x + _attention(
        y, _sub(w, p + "mixer.", True), n, mm, eps,
        cfg["sliding_window"] if sliding else context,
        cfg["rope_parameters"]["rope_theta"] if sliding else None)
    y = _rms(x, w[p + "post_norm"].astype(f32), eps)
    if dense:
        d = _sub(w, p + "mlp.", True)
        return x + _swiglu(y, d["w_gate"], d["w_up"], d["w_down"], mm)
    # the expert stacks stay bfloat16 until an expert is used
    moe = _sub(w, p + "moe.", False)
    moe["router"] = moe["router"].astype(f32)
    return x + _moe(y, moe, n, cfg, mm)


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
    for i in range(cfg["num_hidden_layers"]):
        x = _block(x, w, f"layers.{i}.", n, cfg, mm,
                   cfg["layer_types"][i] == "sliding_attention",
                   i < cfg["first_k_dense_replace"], context)
    return x


def forward(w, tokens, cfg, control=False, context=None, rows=None):
    """Logits [T, vocab_size] in float32 for token ids [T]. ``context``:
    what a full layer's ring of that many rows computes past its length
    (tests); None is plain causal attention. ``rows = (start, count)``:
    the logits of positions start .. start+count-1 only."""
    with jax.default_matmul_precision("highest"):
        x = hidden(w, tokens, cfg, control, context)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], 0)
        x = _rms(x, w["norm"].astype(jnp.float32), cfg["rms_norm_eps"])
        return _mm(control)(x, w["lm_head"])


def predict_ahead(w, tokens, cfg):
    """The prediction module, teacher-forced: logits [T-1, vocab_size]
    for token t+2 from h_t and token t+1."""
    eps, f32 = cfg["rms_norm_eps"], jnp.float32
    n, mm = _widths(cfg), _mm(False)
    with jax.default_matmul_precision("highest"):
        h = hidden(w, tokens, cfg)
        joined = jnp.concatenate([
            _rms(h[:-1], w["mtp_hidden_norm"].astype(f32), eps),
            _rms(w["embed_tokens"][tokens[1:]].astype(f32),
                 w["mtp_embed_norm"].astype(f32), eps)], -1)
        x = _block(mm(joined, w["mtp_proj"]), w, "mtp_block.", n, cfg, mm,
                   sliding=False, dense=False)
        return mm(_rms(x, w["norm"].astype(f32), eps), w["lm_head"])

"""The `solar_open2` architecture in plain jax.numpy, written from the
public config.json of `upstage/Solar-Open2-250B` and the equations of
ISSUE 27 (section A): float32, `highest` matmul precision, no cache, no
kernels, no chunks, no batching. It imports nothing of the program and
makes the weights both sides get.

One sequence at a time. Pre-norm residual blocks with RMSNorm and no
biases; a layer listed in `gqa_layers` is softmax grouped-query attention
without positions whose output is gated by sigmoid(x Wg); every other
layer is gated-delta-rule linear attention, here the recurrence itself,
token by token; every feed-forward is a mixture of routed experts with a
sigmoid router, the 8 largest renormalised, plus one shared expert, here
a plain loop over the experts held. This chip's share: the router scores
all `published.n_routed_experts`, the weights are normalised over all 8
chosen, and only the experts `experts_held` (and the shared one) add to
the result; the embedding and the head are rows `0 .. vocab_size-1` of
the published vocabulary. That partial result is what goes on to the
next layer, as in the program.

What the published config leaves open is listed in config.json under
`assumed`. Departures from a plain reading, each because memory forces
it and none changing a value: attention is computed by blocks of queries
(the whole score tensor of 8,192 tokens is 17 GB), and the weights are
kept at the bfloat16 values both sides are given and widened to float32
where they are used (whole, float32 weights are 13.2 GB; every value is
exactly a bfloat16, so nothing is rounded by that).

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

QUERY_BLOCK = 256


def _widths(cfg):
    lin = cfg["linear_attn_config"]
    return {
        "h": cfg["hidden_size"], "v": cfg["vocab_size"],
        "hq": cfg["num_attention_heads"], "hkv": cfg["num_key_value_heads"],
        "d": cfg["head_dim"], "lh": lin["num_heads"], "ld": lin["head_dim"],
        "conv": lin["short_conv_kernel_size"],
        "rank": cfg["assumed_sizes"]["kda_gate_rank"],
        "f": cfg["moe_intermediate_size"],
        "fs": cfg["assumed_sizes"]["shared_expert_width"],
        "held": cfg["experts_held"][1],
        "routed": cfg["published"]["n_routed_experts"],
    }


def leaf_shapes(cfg):
    """{leaf name: shape}, every leaf of the cut model. Linear weights
    are [in, out]; an expert stack is [held, in, out]."""
    n = _widths(cfg)
    out = {"embed_tokens": (n["v"], n["h"]), "lm_head": (n["h"], n["v"]),
           "norm": (n["h"],)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        out[p + "input_norm"] = out[p + "post_norm"] = (n["h"],)
        if i in cfg["gqa_layers"]:
            q, kv = n["hq"] * n["d"], n["hkv"] * n["d"]
            out.update({p + "mixer.wq": (n["h"], q),
                        p + "mixer.wk": (n["h"], kv),
                        p + "mixer.wv": (n["h"], kv),
                        p + "mixer.wg": (n["h"], q),
                        p + "mixer.wo": (q, n["h"])})
        else:
            d = n["lh"] * n["ld"]
            out.update({
                p + "mixer.wq": (n["h"], d), p + "mixer.wk": (n["h"], d),
                p + "mixer.wv": (n["h"], d),
                p + "mixer.conv_w": (n["conv"], 3 * d),
                p + "mixer.a_log": (n["lh"],), p + "mixer.dt_bias": (d,),
                p + "mixer.wa_down": (n["h"], n["rank"]),
                p + "mixer.wa_up": (n["rank"], d),
                p + "mixer.wb": (n["h"], n["lh"]),
                p + "mixer.wg_down": (n["h"], n["rank"]),
                p + "mixer.wg_up": (n["rank"], d),
                p + "mixer.o_norm": (n["ld"],), p + "mixer.wo": (d, n["h"])})
        out.update({
            p + "moe.router": (n["h"], n["routed"]),
            p + "moe.w_gate": (n["held"], n["h"], n["f"]),
            p + "moe.w_up": (n["held"], n["h"], n["f"]),
            p + "moe.w_down": (n["held"], n["f"], n["h"]),
            p + "moe.shared_gate": (n["h"], n["fs"]),
            p + "moe.shared_up": (n["h"], n["fs"]),
            p + "moe.shared_down": (n["fs"], n["h"])})
    return out


def leaf_tag(name):
    """The number a leaf's key is folded with: a hash of its name."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf(cfg, key, name, shape=None, tag=None):
    """One leaf, bfloat16: normal(0, initializer_range) for matrices and
    embeddings, 1 + that for norm gains, and for the linear layers'
    small vectors the family's usual draws (config.json `assumed`).
    ``tag`` is ``leaf_tag(name)``; a caller that compiles one maker for
    all leaves of a kind and shape passes it as an argument."""
    shape = leaf_shapes(cfg)[name] if shape is None else shape
    k = jax.random.fold_in(key, leaf_tag(name) if tag is None else tag)
    std = cfg["assumed_sizes"]["initializer_range"]
    last = name.rsplit(".", 1)[-1]
    if last == "a_log":      # decay rates log-uniform over 1 .. 16
        x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    elif last == "dt_bias":  # softplus^-1 of steps log-uniform 1e-3 .. 1e-1
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif last == "conv_w":   # four taps a channel, unit gain in all
        x = jax.random.normal(k, shape, jnp.float32) * 0.5
    elif last.endswith("norm"):
        x = 1.0 + jax.random.normal(k, shape, jnp.float32) * std
    else:
        x = jax.random.normal(k, shape, jnp.float32) * std
    return x.astype(jnp.bfloat16)


_MAKERS = {}


def make_leaf(cfg, key, name, shape=None):
    """`leaf`, compiled: one program for all leaves of a kind (the
    name's last part decides the distribution) and shape, the leaf's own
    tag an argument. Made one at a time, a set of weights never needs
    more room than itself and one leaf."""
    shape = leaf_shapes(cfg)[name] if shape is None else shape
    std = cfg["assumed_sizes"]["initializer_range"]
    kind = (name.rsplit(".", 1)[-1], shape, std)
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


def _gqa(x, w, n, mm, window):
    t = x.shape[0]
    g = n["hq"] // n["hkv"]
    q = mm(x, w["wq"]).reshape(t, n["hkv"], g, n["d"])
    k = mm(x, w["wk"]).reshape(t, n["hkv"], n["d"])
    v = mm(x, w["wv"]).reshape(t, n["hkv"], n["d"])
    pad = -t % QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, n["hkv"], g, n["d"])
    rows = jnp.arange(t + pad).reshape(-1, QUERY_BLOCK)
    cols = jnp.arange(t)

    def block(args):
        qi, ri = args
        s = mm(qi.transpose(1, 2, 0, 3), k.transpose(1, 2, 0)[:, None]) \
            * n["d"] ** -0.5                            # [hkv, g, Q, t]
        keep = cols[None, :] <= ri[:, None]
        if window is not None:  # a ring of `window` rows keeps no more
            keep = keep & (cols[None, :] > ri[:, None] - window)
        p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        return mm(p, v.transpose(1, 0, 2)[:, None]).transpose(2, 0, 1, 3)

    o = jax.lax.map(block, (qb, rows)).reshape(t + pad, -1)[:t]
    o = o * jax.nn.sigmoid(mm(x, w["wg"]))
    return mm(o, w["wo"])


def _linear(x, w, n, mm, eps):
    t = x.shape[0]
    nh, hd, kc = n["lh"], n["ld"], n["conv"]
    d = nh * hd

    def conv_silu(name, i):
        u = jnp.pad(mm(x, w[name]), ((kc - 1, 0), (0, 0)))
        cw = w["conv_w"][:, i * d:(i + 1) * d]
        y = sum(u[j:j + t] * cw[j] for j in range(kc))
        return jax.nn.silu(y).reshape(t, nh, hd)

    q, k, v = (conv_silu(m, i) for i, m in enumerate(("wq", "wk", "wv")))
    # L2 norm a head; the 1e-6 under the root is the program's too
    q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + 1e-6) * hd ** -0.5
    k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    a = jnp.exp(-jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(
        (mm(mm(x, w["wa_down"]), w["wa_up"]) + w["dt_bias"])
        .reshape(t, nh, hd)))                                # (0, 1)
    b = 2.0 * jax.nn.sigmoid(mm(x, w["wb"]))                 # kda_allow_neg_eigval

    def step(s, xs):
        q, k, v, a, b = xs
        s = a[..., None] * s
        s = s + (b[:, None] * k)[..., None] \
            * (v - (k[..., None] * s).sum(-2))[:, None, :]
        return s, (q[..., None] * s).sum(-2)

    _, o = jax.lax.scan(step, jnp.zeros((nh, hd, hd), jnp.float32),
                        (q, k, v, a, b))
    o = _rms(o, w["o_norm"], eps)
    o = o * jax.nn.sigmoid(mm(mm(x, w["wg_down"]), w["wg_up"])
                           .reshape(t, nh, hd))
    return mm(o.reshape(t, d), w["wo"])


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
        out = mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)
        return y + share[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        jnp.arange(n["held"]), w["w_gate"], w["w_up"], w["w_down"]))
    return y + mm(jax.nn.silu(mm(x, w["shared_gate"]))
                  * mm(x, w["shared_up"]), w["shared_down"])


def forward(w, tokens, cfg, control=False, window=None):
    """Logits [T, vocab_size] in float32 for token ids [T]. ``window``:
    what a K/V ring of that many rows computes past its length (tests);
    None is plain causal attention."""
    n = _widths(cfg)
    eps = cfg["rms_norm_eps"]
    f32 = jnp.float32

    def mm(a, b):
        a, b = a.astype(f32), b.astype(f32)
        if control:
            a, b = _fp8(a), _fp8(b)
        return jnp.matmul(a, b)

    with jax.default_matmul_precision("highest"):
        x = w["embed_tokens"][tokens].astype(f32)
        for i in range(cfg["num_hidden_layers"]):
            p = f"layers.{i}."
            mix = {k[len(p) + 6:]: v.astype(f32) for k, v in w.items()
                   if k.startswith(p + "mixer.")}
            y = _rms(x, w[p + "input_norm"].astype(f32), eps)
            if i in cfg["gqa_layers"]:
                x = x + _gqa(y, mix, n, mm, window)
            else:
                x = x + _linear(y, mix, n, mm, eps)
            # the expert stacks stay bfloat16 until an expert is used
            moe = {k[len(p) + 4:]: v for k, v in w.items()
                   if k.startswith(p + "moe.")}
            moe["router"] = moe["router"].astype(f32)
            y = _rms(x, w[p + "post_norm"].astype(f32), eps)
            x = x + _moe(y, moe, n, cfg, mm)
        x = _rms(x, w["norm"].astype(f32), eps)
        return mm(x, w["lm_head"])

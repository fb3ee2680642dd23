"""The `nemotron_h` architecture in plain jax.numpy, written from the
public config.json of `nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16`
and the equations of ISSUE 40: float32, `highest` matmul precision, no
cache, no kernels, no chunks, no batching. It imports nothing of the
program and makes the weights both sides get.

One sequence at a time. `hybrid_override_pattern` has one character a
layer and a layer is `x + F(RMSNorm(x))`, no biases but the
convolution's, no position signal anywhere:

- `M`, Mamba-2: `[z | xBC | dt] = u W_in`; `xBC = SiLU(conv_4(xBC) +
  b)`, split into `x [H, P]`, `B [G, N]`, `C [G, N]` (head h uses group
  h // (H / G)); `dt = softplus(dt + dt_bias)`, `a = exp(-exp(A_log)
  dt)` a head; the state `S_h [P, N]` goes token by token, `S_h = a_h
  S_h + dt_h x_h B_g^T`, `y_h = S_h C_g + D_h x_h`: here the recurrence
  itself, a scan over the tokens; then `y SiLU(z)`, RMS statistics over
  each of the G groups of channels, a gain, `W_out`;
- `*`: softmax grouped-query attention, causal, scale head_dim^-0.5,
  no gate, no q/k norm, no rotary;
- `E`, LatentMoE: `s = sigmoid(u W_r)` over all the published experts,
  the 22 largest of `s + bias` chosen, `w_i = 5 s_i / sum_chosen s_j`;
  `l = u W_dn` (hidden -> latent); `E_i(l) = relu(l W1_i)^2 W2_i`; `r =
  (sum_i w_i E_i(l)) W_up` (latent -> hidden); plus the shared expert
  `relu(u V1)^2 V2` at the full width: here a plain loop over the
  experts held.

This chip's share: the router scores all `published.n_routed_experts`,
the weights are normalised over all 22 chosen, and only the experts
`experts_held` (and the shared one) add to the result; the embedding and
the head are rows `0 .. vocab_size-1` of the published vocabulary. That
partial result is what goes on to the next layer, as in the program.

What the published config leaves open is listed in config.json under
`assumed`. Departures from a plain reading, each because memory forces
it and none changing a value: attention is computed by blocks of
queries, the weights are kept at the bfloat16 values both sides are
given and widened to float32 where they are used (every value is exactly
a bfloat16, so nothing is rounded by that), and `rows` lets the head run
over the positions that are read and no others.

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
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    return {
        "h": cfg["hidden_size"], "v": cfg["vocab_size"],
        "hq": cfg["num_attention_heads"], "hkv": cfg["num_key_value_heads"],
        "d": cfg["head_dim"], "mh": heads, "mp": p, "mg": groups,
        "mn": state, "inner": heads * p,
        "conv_dim": heads * p + 2 * groups * state,
        "conv": cfg["conv_kernel"], "l": cfg["moe_latent_size"],
        "f": cfg["moe_intermediate_size"],
        "fs": cfg["moe_shared_expert_intermediate_size"],
        "held": cfg["experts_held"][1],
        "routed": cfg["published"]["n_routed_experts"],
    }


def leaf_shapes(cfg):
    """{leaf name: shape}, every leaf of the cut model. Linear weights
    are [in, out]; an expert stack is [held, in, out]; the convolution's
    taps are [tap, channel]."""
    n = _widths(cfg)
    out = {"embed_tokens": (n["v"], n["h"]), "lm_head": (n["h"], n["v"]),
           "norm": (n["h"],)}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = f"layers.{i}."
        out[p + "norm"] = (n["h"],)
        p += "mixer."
        if kind == "M":
            out.update({
                p + "in_proj": (n["h"], n["inner"] + n["conv_dim"] + n["mh"]),
                p + "conv_w": (n["conv"], n["conv_dim"]),
                p + "conv_b": (n["conv_dim"],),
                p + "dt_bias": (n["mh"],), p + "a_log": (n["mh"],),
                p + "d_skip": (n["mh"],), p + "norm": (n["inner"],),
                p + "out_proj": (n["inner"], n["h"])})
        elif kind == "*":
            q, kv = n["hq"] * n["d"], n["hkv"] * n["d"]
            out.update({p + "wq": (n["h"], q), p + "wk": (n["h"], kv),
                        p + "wv": (n["h"], kv), p + "wo": (q, n["h"])})
        else:
            out.update({
                p + "router": (n["h"], n["routed"]),
                p + "select_bias": (n["routed"],),
                p + "latent_down": (n["h"], n["l"]),
                p + "latent_up": (n["l"], n["h"]),
                p + "w_up": (n["held"], n["l"], n["f"]),
                p + "w_down": (n["held"], n["f"], n["l"]),
                p + "shared_up": (n["h"], n["fs"]),
                p + "shared_down": (n["fs"], n["h"])})
    return out


def leaf_tag(name):
    """The number a leaf's key is folded with: a hash of its name."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf(cfg, key, name, shape=None, tag=None):
    """One leaf, bfloat16: normal(0, initializer_range) for matrices,
    embeddings and the convolution's bias, 1 + that for norm gains, and
    for the state-space layers' small vectors Mamba-2's own draws from
    the config's keys (config.json `assumed`); the router's selection
    bias is float32 zeros, the program's own type for it. ``tag`` is
    ``leaf_tag(name)``; a caller that compiles one maker for all leaves
    of a kind and shape passes it as an argument."""
    shape = leaf_shapes(cfg)[name] if shape is None else shape
    if name.endswith("select_bias"):
        return jnp.zeros(shape, jnp.float32)
    k = jax.random.fold_in(key, leaf_tag(name) if tag is None else tag)
    std = cfg["assumed_sizes"]["initializer_range"]
    last = name.rsplit(".", 1)[-1]
    if last == "a_log":      # decay rates uniform over 1 .. 16
        x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    elif last == "dt_bias":  # softplus^-1 of steps log-uniform min .. max
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, jnp.log(cfg["time_step_min"]),
            jnp.log(cfg["time_step_max"]))), cfg["time_step_floor"])
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif last == "d_skip":
        x = jnp.ones(shape, jnp.float32)
    elif last == "conv_w":   # four taps a channel, unit gain in all
        x = jax.random.normal(k, shape, jnp.float32) * 0.5
    elif last == "norm":
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
    return mm(o, w["wo"])


def _mamba(x, w, n, mm, eps):
    t = x.shape[0]
    nh, hp, g, ns, kc = n["mh"], n["mp"], n["mg"], n["mn"], n["conv"]
    d = n["inner"]
    proj = mm(x, w["in_proj"])
    z, xbc, dt = proj[:, :d], proj[:, d:d + n["conv_dim"]], \
        proj[:, d + n["conv_dim"]:]
    u = jnp.pad(xbc, ((kc - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(u[j:j + t] * w["conv_w"][j] for j in range(kc))
                      + w["conv_b"])
    xs = xbc[:, :d].reshape(t, nh, hp)
    bs = jnp.repeat(xbc[:, d:d + g * ns].reshape(t, g, ns), nh // g, axis=1)
    cs = jnp.repeat(xbc[:, d + g * ns:].reshape(t, g, ns), nh // g, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # [t, heads]
    a = jnp.exp(-jnp.exp(w["a_log"]) * dt)                   # (0, 1)

    def step(s, xs):
        x, b, c, dt, a = xs
        s = a[:, None, None] * s + (dt[:, None] * x)[..., None] * b[:, None]
        return s, (s * c[:, None]).sum(-1) + w["d_skip"][:, None] * x

    _, y = jax.lax.scan(step, jnp.zeros((nh, hp, ns), jnp.float32),
                        (xs, bs, cs, dt, a))
    # the gate first, then the statistics of each group of channels
    y = (y.reshape(t, d) * jax.nn.silu(z)).reshape(t, g, d // g)
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + eps)
    return mm(y.reshape(t, d) * w["norm"], w["out_proj"])


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _moe(x, w, n, cfg, mm):
    first = cfg["experts_held"][0]
    # the router is float32 in the program and in the control alike: a
    # choice of experts is no matmul operand to round
    s = jax.nn.sigmoid(jnp.matmul(x, w["router"]))
    _, idx = jax.lax.top_k(s + w["select_bias"], cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    top = top * cfg["routed_scaling_factor"]
    low = mm(x, w["latent_down"])

    def expert(y, e):
        i, w1, w2 = e
        share = jnp.where(idx == i + first, top, 0.0).sum(-1)
        return y + share[:, None] * mm(_relu2(mm(low, w1)), w2), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(low), (
        jnp.arange(n["held"]), w["w_up"], w["w_down"]))
    return mm(y, w["latent_up"]) \
        + mm(_relu2(mm(x, w["shared_up"])), w["shared_down"])


def _mm(control):
    def mm(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if control:
            a, b = _fp8(a), _fp8(b)
        return jnp.matmul(a, b)
    return mm


def hidden(w, tokens, cfg, control=False, window=None):
    """The stack's output before the final norm, [T, hidden]."""
    n, mm, f32 = _widths(cfg), _mm(control), jnp.float32
    eps = cfg["layer_norm_epsilon"]
    x = w["embed_tokens"][tokens].astype(f32)
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = f"layers.{i}.mixer."
        # the expert stacks stay bfloat16 until an expert is used
        sub = {k[len(p):]: v if k.endswith((".w_up", ".w_down"))
               else v.astype(f32) for k, v in w.items() if k.startswith(p)}
        y = _rms(x, w[f"layers.{i}.norm"].astype(f32), eps)
        if kind == "M":
            x = x + _mamba(y, sub, n, mm, eps)
        elif kind == "*":
            x = x + _gqa(y, sub, n, mm, window)
        else:
            x = x + _moe(y, sub, n, cfg, mm)
    return x


def forward(w, tokens, cfg, control=False, window=None, rows=None):
    """Logits [T, vocab_size] in float32 for token ids [T]. ``window``:
    what a K/V ring of that many rows computes past its length (tests);
    None is plain causal attention. ``rows = (start, count)``: the
    logits of positions start .. start+count-1 only."""
    with jax.default_matmul_precision("highest"):
        x = hidden(w, tokens, cfg, control, window)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], 0)
        x = _rms(x, w["norm"].astype(jnp.float32),
                 cfg["layer_norm_epsilon"])
        return _mm(control)(x, w["lm_head"])

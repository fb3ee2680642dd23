"""The `minicpm_sala` architecture in plain jax.numpy, written from the
public config.json of `openbmb/MiniCPM-SALA` and the equations of
ISSUE 49: float32, `highest` matmul precision, no cache, no kernels, no
chunks, no batching. It imports nothing of the program and makes the
weights both sides get.

One sequence at a time. `x = scale_emb * embed[ids]`; every layer is `x
+= c * M(RMSNorm(x))` then `x += c * SwiGLU(RMSNorm(x))` with `c =
scale_depth / sqrt(published depth)`; a final RMSNorm, the hidden state
divided by `hidden_size / dim_model_base`, an untied head. No biases. A
`minicpm4` mixer is InfLLM-V2 over grouped-query attention without
positions, q and k RMS-normalised a head, the output gated by sigmoid(x
Wg); here the six steps as the issue writes them, query by query:

1. a query at position t of n = t + 1 positions attends 0 .. t plainly
   if n < dense_len; else, per K/V head,
2. pooled keys c_j = mean(k[stride j .. stride j + kernel - 1]) for
   every j whose last position is <= t,
3. p_h = softmax_j(q_h . c_j scale) for each head of the group, P = the
   heads' sum,
4. block b (positions block b .. block b + block - 1) scores the largest
   P_j among the pooled rows that overlap it,
5. the first init_blocks blocks and the window / block most recent are
   forced; the topk highest among 0 .. t // block are kept, forced ones
   included, ties to the lower index (`jax.lax.top_k`'s order),
6. softmax over the kept blocks' positions <= t.

A `lightning-attn` mixer is the recurrence itself, token by token: q
and k RMS-normalised a head and rotated (half-split pairs, angle
position x theta^(-2i/d)), `S = exp(-s) S + k v^T` in float32 with `s`
Lightning Attention's constant a head and published layer, `o = d^-0.5
S^T q`, an RMSNorm over all heads' channels, a sigmoid gate, Wo.

What the published config leaves open is listed in config.json under
`assumed`. Departures from a plain reading, each because memory forces
it and none changing a value: the queries of a sparse layer are taken
`QUERY_BLOCK` at a time (each still makes its own choice), the
feed-forward `FFN_BLOCK` tokens at a time, the weights are kept at the
bfloat16 values both sides are given and widened to float32 where they
are used, and `rows` lets the head run over the positions that are read.

``weights`` draws every leaf from its own `fold_in` of the seed's key and
rounds it to bfloat16: program and reference compute with the same
values, so only the arithmetic differs. With ``control`` every matrix
product's operands are rounded to float8 e4m3 first (per-tensor scale):
the model one precision below the bfloat16 the configuration states;
norm statistics, both softmaxes, decay and state stay float32.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128
FFN_BLOCK = 2048


def _widths(cfg):
    return {
        "h": cfg["hidden_size"], "v": cfg["vocab_size"],
        "f": cfg["intermediate_size"],
        "hq": cfg["num_attention_heads"], "hkv": cfg["num_key_value_heads"],
        "d": cfg["head_dim"], "lh": cfg["lightning_nh"],
        "ld": cfg["lightning_head_dim"],
    }


def leaf_shapes(cfg):
    """{leaf name: shape}, every leaf of the cut model. Linear weights
    are [in, out]."""
    n = _widths(cfg)
    out = {"embed_tokens": (n["v"], n["h"]), "lm_head": (n["h"], n["v"]),
           "norm": (n["h"],)}
    for i, kind in enumerate(cfg["mixer_types"]):
        p = f"layers.{i}."
        out[p + "input_norm"] = out[p + "post_norm"] = (n["h"],)
        out.update({p + "w_gate": (n["h"], n["f"]),
                    p + "w_up": (n["h"], n["f"]),
                    p + "w_down": (n["f"], n["h"])})
        if kind == "minicpm4":
            q, kv = n["hq"] * n["d"], n["hkv"] * n["d"]
            out.update({p + "mixer.wq": (n["h"], q),
                        p + "mixer.wk": (n["h"], kv),
                        p + "mixer.wv": (n["h"], kv),
                        p + "mixer.wg": (n["h"], q),
                        p + "mixer.wo": (q, n["h"]),
                        p + "mixer.q_norm": (n["d"],),
                        p + "mixer.k_norm": (n["d"],)})
        else:
            d = n["lh"] * n["ld"]
            out.update({p + "mixer.wq": (n["h"], d),
                        p + "mixer.wk": (n["h"], d),
                        p + "mixer.wv": (n["h"], d),
                        p + "mixer.wz": (n["h"], d),
                        p + "mixer.wo": (d, n["h"]),
                        p + "mixer.q_norm": (n["ld"],),
                        p + "mixer.k_norm": (n["ld"],),
                        p + "mixer.o_norm": (d,)})
    return out


def leaf_tag(name):
    """The number a leaf's key is folded with: a hash of its name."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf(cfg, key, name, shape=None, tag=None):
    """One leaf, bfloat16: normal(0, initializer_range) for matrices and
    embeddings, 1 + that for norm gains. ``tag`` is ``leaf_tag(name)``; a
    caller that compiles one maker for all leaves of a kind and shape
    passes it as an argument."""
    shape = leaf_shapes(cfg)[name] if shape is None else shape
    k = jax.random.fold_in(key, leaf_tag(name) if tag is None else tag)
    std = cfg["assumed_sizes"]["initializer_range"]
    x = jax.random.normal(k, shape, jnp.float32) * std
    if name.rsplit(".", 1)[-1].endswith("norm"):
        x = 1.0 + x
    return x.astype(jnp.bfloat16)


_MAKERS = {}


def make_leaf(cfg, key, name, shape=None):
    """`leaf`, compiled: one program for all leaves of a kind (a gain or
    a matrix) and shape, the leaf's own tag an argument. Made one at a
    time, a set of weights never needs more room than itself and one
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


def slopes(cfg, layer):
    """Lightning Attention's decay rates of one layer of the cut, by its
    PUBLISHED index: 2^(-8 (h+1) / heads) (1 - l / (L - 1 + 1e-5) +
    1e-5), float32 [heads]."""
    nh, total = cfg["lightning_nh"], cfg["published"]["num_hidden_layers"]
    l = cfg.get("layer_offset", 0) + layer
    h = np.arange(1, nh + 1, dtype=np.float64)
    return (2.0 ** (-8.0 * h / nh) * (1.0 - l / (total - 1 + 1e-5) + 1e-5)
            ).astype(np.float32)


def _fp8(x):
    s = 224.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(control):
    def mm(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if control:
            a, b = _fp8(a), _fp8(b)
        return jnp.matmul(a, b)
    return mm


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rotate(x, theta):
    """x [T, heads, d] rotated by its row's position: channel i < d/2
    pairs with i + d/2, angle position x theta^(-2i/d)."""
    t, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def overlaps(sc, blocks):
    """[blocks, m] int: the pooled rows whose positions overlap each
    block (row j covers stride j .. stride j + kernel - 1), -1 where a
    block has fewer than the most."""
    lo = [-(-(sc["block_size"] * b - sc["kernel_size"] + 1)
            // sc["kernel_stride"]) for b in range(blocks)]
    hi = [(sc["block_size"] * (b + 1) - 1) // sc["kernel_stride"]
          for b in range(blocks)]
    m = max(h - l + 1 for l, h in zip(lo, hi))
    return np.asarray([[j if l <= j <= h and j >= 0 else -1
                        for j in range(l, l + m)]
                       for l, h in zip(lo, hi)], np.int32)


def choose(q, k, rows, sc, scale, mm):
    """Steps 1-5 for the queries `q [Q, hkv, g, d]` at positions `rows
    [Q]` against the sequence's keys `k [T, hkv, d]`: the blocks each
    attends, [hkv, Q, blocks] bool."""
    t = k.shape[0]
    kernel, stride, block = (sc["kernel_size"], sc["kernel_stride"],
                             sc["block_size"])
    nb = -(-t // block)
    j = max((t - kernel) // stride + 1, 0)
    b = jnp.arange(nb)
    newest = rows[:, None] // block                          # [Q, 1]
    live = b[None, :] <= newest                              # [Q, nb]
    if j == 0:
        return jnp.broadcast_to(live, (k.shape[1],) + live.shape)
    span = np.arange(j)[:, None] * stride + np.arange(kernel)[None, :]
    c = k[span].mean(1)                                      # [J, hkv, d]
    s = mm(q.transpose(1, 2, 0, 3), c.transpose(1, 2, 0)[:, None]) * scale
    valid = (jnp.arange(j) * stride + kernel - 1)[None, :] <= rows[:, None]
    p = jax.nn.softmax(jnp.where(valid, s, -jnp.inf), axis=-1)
    p = jnp.where(valid, p, 0.0).sum(1)                      # [hkv, Q, J]
    over = overlaps(sc, nb)                                  # [nb, m]
    cand = p[:, :, np.clip(over, 0, j - 1)]                  # [hkv,Q,nb,m]
    ok = (over >= 0) & (over < j)
    ok = ok[None] & valid[:, np.clip(over, 0, j - 1)]        # [Q, nb, m]
    score = jnp.where(ok, cand, -1.0).max(-1)                # [hkv, Q, nb]
    forced = (b[None, :] < sc["init_blocks"]) | (
        b[None, :] > newest - sc["window_size"] // block)
    score = jnp.where(live, jnp.where(forced, jnp.inf, score), -jnp.inf)
    _, idx = jax.lax.top_k(score, min(sc["topk"], nb))
    chosen = (idx[..., None] == b).any(-2) & live
    return jnp.where(rows[:, None] + 1 < sc["dense_len"], live, chosen)


def _sparse(x, w, n, sc, mm, eps, keep=None):
    t = x.shape[0]
    g, d = n["hq"] // n["hkv"], n["d"]
    q = _rms(mm(x, w["wq"]).reshape(t, n["hkv"], g, d), w["q_norm"], eps)
    k = _rms(mm(x, w["wk"]).reshape(t, n["hkv"], d), w["k_norm"], eps)
    v = mm(x, w["wv"]).reshape(t, n["hkv"], d)
    pad = -t % QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, n["hkv"], g, d)
    rows = jnp.arange(t + pad).reshape(-1, QUERY_BLOCK)
    cols = jnp.arange(t)

    def block(args):
        qi, ri = args
        chosen = choose(qi, k, ri, sc, d ** -0.5, mm)        # [hkv, Q, nb]
        s = mm(qi.transpose(1, 2, 0, 3), k.transpose(1, 2, 0)[:, None]) \
            * d ** -0.5                                      # [hkv, g, Q, t]
        kept = chosen[:, :, cols // sc["block_size"]] \
            & (cols[None, :] <= ri[:, None])
        p = jax.nn.softmax(jnp.where(kept[:, None], s, -1e30), axis=-1)
        o = mm(p, v.transpose(1, 0, 2)[:, None]).transpose(2, 0, 1, 3)
        return o, chosen

    o, chosen = jax.lax.map(block, (qb, rows))
    o = o.reshape(t + pad, -1)[:t] * jax.nn.sigmoid(mm(x, w["wg"]))
    if keep is not None:
        # what the selection was made from and what it chose, for the
        # positions `keep = (start, count)`
        nb = chosen.shape[-1]
        chosen = chosen.transpose(0, 2, 1, 3).reshape(t + pad, n["hkv"], nb)
        keep_rows = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, keep[0], keep[1], 0)
        return mm(o, w["wo"]), {"q": keep_rows(q), "k": k,
                                "chosen": keep_rows(chosen)}
    return mm(o, w["wo"]), None


def _lightning(x, w, n, mm, eps, rates, theta):
    t = x.shape[0]
    nh, hd = n["lh"], n["ld"]
    q = _rotate(_rms(mm(x, w["wq"]).reshape(t, nh, hd), w["q_norm"], eps),
                theta)
    k = _rotate(_rms(mm(x, w["wk"]).reshape(t, nh, hd), w["k_norm"], eps),
                theta)
    v = mm(x, w["wv"]).reshape(t, nh, hd)
    decay = jnp.exp(-jnp.asarray(rates, jnp.float32))[:, None, None]

    def step(s, xs):
        q, k, v = xs
        s = decay * s + k[:, :, None] * v[:, None, :]
        return s, (q[..., None] * s).sum(-2)

    _, o = jax.lax.scan(step, jnp.zeros((nh, hd, hd), jnp.float32), (q, k, v))
    o = _rms((o * hd ** -0.5).reshape(t, nh * hd), w["o_norm"], eps)
    return mm(o * jax.nn.sigmoid(mm(x, w["wz"])), w["wo"])


def _ffn(x, w, mm):
    def swiglu(u):
        return mm(jax.nn.silu(mm(u, w["w_gate"])) * mm(u, w["w_up"]),
                  w["w_down"])

    t = x.shape[0]
    if t <= FFN_BLOCK or t % FFN_BLOCK:
        return swiglu(x)
    return jax.lax.map(swiglu, x.reshape(-1, FFN_BLOCK, x.shape[1])).reshape(
        x.shape)


def hidden(w, tokens, cfg, control=False, keep=None):
    """The stack's output [T, hidden] before the final norm, and (with
    ``keep = (start, count)``) per sparse layer what its selection saw
    at those positions."""
    n = _widths(cfg)
    mm = _mm(control)
    eps, f32 = cfg["rms_norm_eps"], jnp.float32
    c = cfg["scale_depth"] / cfg["published"]["num_hidden_layers"] ** 0.5
    x = w["embed_tokens"][tokens].astype(f32) * cfg["scale_emb"]
    seen = []
    for i, kind in enumerate(cfg["mixer_types"]):
        p = f"layers.{i}."
        sub = {k[len(p) + 6:]: v for k, v in w.items()
               if k.startswith(p + "mixer.")}
        sub = {k: v.astype(f32) if v.ndim == 1 else v for k, v in sub.items()}
        y = _rms(x, w[p + "input_norm"].astype(f32), eps)
        if kind == "minicpm4":
            out, saw = _sparse(y, sub, n, cfg["sparse_config"], mm, eps, keep)
            seen.append(saw)
        else:
            out = _lightning(y, sub, n, mm, eps, slopes(cfg, i),
                             cfg["rope_theta"])
        x = x + c * out
        ffn = {k: w[p + k] for k in ("w_gate", "w_up", "w_down")}
        x = x + c * _ffn(_rms(x, w[p + "post_norm"].astype(f32), eps), ffn, mm)
    return x, seen


def forward(w, tokens, cfg, control=False, rows=None, detail=False):
    """Logits [T, vocab_size] in float32 for token ids [T]. ``rows =
    (start, count)``: the logits of positions start .. start+count-1
    only. ``detail``: also, per sparse layer, {q, k, chosen}: the
    normalised queries of those positions, the sequence's normalised
    keys and the blocks each position chose."""
    with jax.default_matmul_precision("highest"):
        x, seen = hidden(w, tokens, cfg, control,
                         (rows or (0, len(tokens))) if detail else None)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], 0)
        x = _rms(x, w["norm"].astype(jnp.float32), cfg["rms_norm_eps"])
        x = x / (cfg["hidden_size"] / cfg["dim_model_base"])
        logits = _mm(control)(x, w["lm_head"])
        return (logits, seen) if detail else logits

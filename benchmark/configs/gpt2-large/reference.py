"""GPT-2 in plain jax.numpy, written from the published description
(Radford et al. 2019; the `openai-community/gpt2-large` config.json):
learned token and position embeddings, pre-norm blocks of causal
multi-head self-attention and a GELU MLP, a final layer norm, and a head
tied to the token embedding. float32, `highest` matmul precision, no
cache, no kernels, no batching tricks. It imports nothing of the program
and makes its own weights from the seed.

Departures from the published model, because the program under test
makes them: GELU is the exact erf form (published: the tanh form
`gelu_new`), and the layers are scanned over stacked weights so that one
program compiles in seconds.

``weights`` is also what the benchmark gives the program to serve: the
benchmark makes the weights, the program and the reference both get them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LAYER_KEYS = ("ln1_g", "ln1_b", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
              "bo", "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")


def weights(cfg, key):
    """Seeded random weights, float32, made on the device: normal with
    the config's initializer_range for matrices, embeddings and biases,
    1 + the same for layer-norm gains. Linear weights are [in, out].
    Per-layer tensors are stacked on a leading layer axis."""
    h, f, nl = cfg["n_embd"], 4 * cfg["n_embd"], cfg["n_layer"]
    std = cfg["initializer_range"]
    shapes = {
        "wte": (cfg["vocab_size"], h), "wpe": (cfg["n_positions"], h),
        "lnf_g": (h,), "lnf_b": (h,),
        "ln1_g": (nl, h), "ln1_b": (nl, h), "ln2_g": (nl, h),
        "ln2_b": (nl, h),
        "wq": (nl, h, h), "wk": (nl, h, h), "wv": (nl, h, h),
        "wo": (nl, h, h), "bq": (nl, h), "bk": (nl, h), "bv": (nl, h),
        "bo": (nl, h), "w1": (nl, h, f), "b1": (nl, f), "w2": (nl, f, h),
        "b2": (nl, h),
    }
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * std
        out[name] = 1.0 + x if name.endswith("_g") else x
    return out


def _layer_norm(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def forward(w, tokens, cfg, dtype=jnp.float32):
    """Logits [B, T, V] in float32 for token ids [B, T]. With ``dtype``
    bfloat16 this is the control: weights and activations rounded to
    bfloat16, matmuls accumulating in float32, layer-norm and softmax
    statistics in float32 — the careful way to run the model one
    precision lower."""
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    b, t = tokens.shape
    w = jax.tree_util.tree_map(lambda a: a.astype(dtype), w)
    hd = w["wte"].shape[1] // heads

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32
                          ).astype(dtype)

    x = w["wte"][tokens] + w["wpe"][jnp.arange(t)][None]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, lw):
        y = _layer_norm(x, lw["ln1_g"], lw["ln1_b"], eps)
        q, k, v = (
            (mm(y, lw["w" + n]) + lw["b" + n]).reshape(b, t, heads, hd)
            .transpose(0, 2, 1, 3) for n in "qkv")
        s = jnp.matmul(q, k.transpose(0, 1, 3, 2),
                       preferred_element_type=jnp.float32) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
        a = mm(p.astype(dtype), v).transpose(0, 2, 1, 3).reshape(b, t, -1)
        x = x + mm(a, lw["wo"]) + lw["bo"]
        y = _layer_norm(x, lw["ln2_g"], lw["ln2_b"], eps)
        y = jax.nn.gelu(mm(y, lw["w1"]) + lw["b1"], approximate=False)
        return x + mm(y.astype(dtype), lw["w2"]) + lw["b2"], None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(block, x, {k: w[k] for k in LAYER_KEYS})
        x = _layer_norm(x, w["lnf_g"], w["lnf_b"], eps)
        return jnp.matmul(x, w["wte"].T, preferred_element_type=jnp.float32)

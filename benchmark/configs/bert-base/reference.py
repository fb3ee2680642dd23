"""BERT pretraining (masked LM + next sentence) in plain jax.numpy,
written from the published description (Devlin et al. 2018; the
`google-bert/bert-base-uncased` config.json): token + position + type
embeddings under a layer norm, post-norm encoder blocks of multi-head
self-attention and a GELU MLP, a tanh pooler, an MLM head (dense, GELU,
layer norm, decoder tied to the token embedding, bias) and an NSP head.
float32, `highest` matmul precision, no kernels. It imports nothing of
the program and makes its own weights from the seed; the benchmark hands
the same weights to the program.

Dropout (0.1 on the embeddings, on the attention probabilities and on
each sub-layer's output before its residual, as published) is part of
the timed step, so the reference draws the same masks: `step_keys` and
`dropout_masks` repeat, with jax.random alone, the draws the program's
documented protocol makes (`paddle.seed(s)`, then one key split per
step and per dropout in the order of the forward pass). A program that
draws its masks otherwise computes another step, and `grad_diff` says so.

Departures, because the program under test makes them: layer-norm
epsilon 1e-5 (published 1e-12); the key mask is additive -1e4 on pad
(id 0) keys; layers are scanned over stacked weights so that the program
compiles in seconds."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

LAYER = {  # stacked key -> program parameter name under encoder.layers.<i>
    "wq": "self_attn.q_proj.weight", "bq": "self_attn.q_proj.bias",
    "wk": "self_attn.k_proj.weight", "bk": "self_attn.k_proj.bias",
    "wv": "self_attn.v_proj.weight", "bv": "self_attn.v_proj.bias",
    "wo": "self_attn.out_proj.weight", "bo": "self_attn.out_proj.bias",
    "w1": "linear1.weight", "b1": "linear1.bias",
    "w2": "linear2.weight", "b2": "linear2.bias",
    "ln1_g": "norm1.weight", "ln1_b": "norm1.bias",
    "ln2_g": "norm2.weight", "ln2_b": "norm2.bias",
}
TOP = {
    "wte": "bert.embeddings.word_embeddings.weight",
    "wpe": "bert.embeddings.position_embeddings.weight",
    "wtt": "bert.embeddings.token_type_embeddings.weight",
    "lne_g": "bert.embeddings.layer_norm.weight",
    "lne_b": "bert.embeddings.layer_norm.bias",
    "wp": "bert.pooler.dense.weight", "bp": "bert.pooler.dense.bias",
    "wt": "cls.transform.weight", "bt": "cls.transform.bias",
    "lnh_g": "cls.layer_norm.weight", "lnh_b": "cls.layer_norm.bias",
    "bdec": "cls.decoder_bias",
    "wn": "seq_relationship.weight", "bn": "seq_relationship.bias",
}
EPS = 1e-5


def shapes(cfg):
    h, f, nl = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_hidden_layers"])
    return {
        "wte": (cfg["vocab_size"], h),
        "wpe": (cfg["max_position_embeddings"], h),
        "wtt": (cfg["type_vocab_size"], h), "lne_g": (h,), "lne_b": (h,),
        "wp": (h, h), "bp": (h,), "wt": (h, h), "bt": (h,),
        "lnh_g": (h,), "lnh_b": (h,), "bdec": (cfg["vocab_size"],),
        "wn": (h, 2), "bn": (2,),
        "wq": (nl, h, h), "wk": (nl, h, h), "wv": (nl, h, h),
        "wo": (nl, h, h), "bq": (nl, h), "bk": (nl, h), "bv": (nl, h),
        "bo": (nl, h), "w1": (nl, h, f), "b1": (nl, f), "w2": (nl, f, h),
        "b2": (nl, h), "ln1_g": (nl, h), "ln1_b": (nl, h),
        "ln2_g": (nl, h), "ln2_b": (nl, h),
    }


def weights(cfg, key):
    """Seeded random weights, float32, on the device: normal with the
    config's initializer_range everywhere (biases too, so that none is
    blind), 1 + that for layer-norm gains. Linear weights are [in, out]."""
    std = cfg["initializer_range"]
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * std
        out[name] = 1.0 + x if name.endswith("_g") else x
    return out


def by_program_name(tree):
    """{program parameter name: array}: stacked leaves split per layer."""
    out = {TOP[k]: v for k, v in tree.items() if k in TOP}
    for k, name in LAYER.items():
        for i in range(tree[k].shape[0]):
            out[f"bert.encoder.layers.{i}.{name}"] = tree[k][i]
    return out


def leaf_sq_norms(tree):
    """{program parameter name: squared L2 norm}, without unstacking."""
    out = {TOP[k]: jnp.sum(jnp.square(v)) for k, v in tree.items()
           if k in TOP}
    for k, name in LAYER.items():
        sq = jnp.sum(jnp.square(tree[k]).reshape(tree[k].shape[0], -1), 1)
        for i in range(tree[k].shape[0]):
            out[f"bert.encoder.layers.{i}.{name}"] = sq[i]
    return out


def step_keys(rng, n):
    """The dropout keys of a trainer's first ``n`` steps, from
    ``rng`` = {"seed", "impl"}: the generator is seeded, the step takes
    the second half of one split of it as it is built, and every call
    splits that again and hands the second half to the step."""
    k = jax.random.split(jax.random.key(rng["seed"], impl=rng["impl"]))[1]
    keys = []
    for _ in range(n):
        k, sub = jax.random.split(k)
        keys.append(sub)
    return keys


def dropout_masks(cfg, key, b, s):
    """The keep-masks of one step over the whole batch, layer-stacked:
    one split of the step's key per dropout, in the order of the forward
    pass (embeddings; then per layer the attention probabilities, the
    attention output, the MLP output); a rate of 0 draws nothing."""
    ph, pa = cfg["hidden_dropout_prob"], cfg["attention_probs_dropout_prob"]
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]

    def draw(p, shape):
        nonlocal key
        if not p:
            return jnp.ones(shape, bool)
        key, sub = jax.random.split(key)
        return jax.random.bernoulli(sub, 1.0 - p, shape)

    emb = draw(ph, (b, s, h))
    layers = [(draw(pa, (b, heads, s, s)), draw(ph, (b, s, h)),
               draw(ph, (b, s, h)))
              for _ in range(cfg["num_hidden_layers"])]
    attn, h1, h2 = (jnp.stack(m) for m in zip(*layers))
    return {"emb": emb, "attn": attn, "h1": h1, "h2": h2}


def _drop(x, keep_mask, p):
    return jnp.where(keep_mask, x / (1.0 - p), 0.0) if p else x


def _ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * g + b


def _fp8(x):
    """Round to float8_e4m3fn with a per-tensor scale (the careful way
    to run a matmul operand one precision below bfloat16)."""
    s = 224.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(q - x)  # the gradient passes straight


def loss_sums(w, block, cfg, control=False, masks=None):
    """(sum of MLM losses, sum of NSP losses) over a block of rows:
    ids [b, s], types [b, s], masked positions [b, p] within the row,
    MLM labels [b, p], NSP labels [b]. With ``control`` every matmul
    operand is rounded to fp8 first. ``masks``: these rows' part of
    `dropout_masks`; None is no dropout."""
    ids, tt, pos, mlm, nsp = block
    ph, pa = ((cfg["hidden_dropout_prob"],
               cfg["attention_probs_dropout_prob"]) if masks else (0, 0))
    if masks is None:  # the scan still wants a leaf per layer
        masks = {k: jnp.zeros((cfg["num_hidden_layers"],), bool)
                 for k in ("attn", "h1", "h2")}
    heads = cfg["num_attention_heads"]
    b, s = ids.shape
    hd = cfg["hidden_size"] // heads
    r = _fp8 if control else (lambda a: a)

    def mm(x, y):
        return jnp.matmul(r(x), r(y))

    x = _ln(w["wte"][ids] + w["wpe"][jnp.arange(s)][None] + w["wtt"][tt],
            w["lne_g"], w["lne_b"])
    x = _drop(x, masks.get("emb"), ph)
    bias = jnp.where(ids != cfg["pad_token_id"], 0.0, -1e4)[:, None, None, :]

    def layer(x, lw_m):
        lw, m = lw_m
        q, k, v = ((mm(x, lw["w" + n]) + lw["b" + n])
                   .reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
                   for n in "qkv")
        p = jax.nn.softmax(mm(q, k.transpose(0, 1, 3, 2)) * hd ** -0.5
                           + bias, axis=-1)
        a = mm(_drop(p, m["attn"], pa), v
               ).transpose(0, 2, 1, 3).reshape(b, s, -1)
        x = _ln(x + _drop(mm(a, lw["wo"]) + lw["bo"], m["h1"], ph),
                lw["ln1_g"], lw["ln1_b"])
        f = jax.nn.gelu(mm(x, lw["w1"]) + lw["b1"], approximate=False)
        x = _ln(x + _drop(mm(f, lw["w2"]) + lw["b2"], m["h2"], ph),
                lw["ln2_g"], lw["ln2_b"])
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, ({k: w[k] for k in LAYER},
                                       {k: masks[k]
                                        for k in ("attn", "h1", "h2")}))
        pooled = jnp.tanh(mm(x[:, 0], w["wp"]) + w["bp"])
        hsel = jnp.take_along_axis(x, pos[:, :, None], axis=1)
        t = _ln(jax.nn.gelu(mm(hsel, w["wt"]) + w["bt"], approximate=False),
                w["lnh_g"], w["lnh_b"])
        logits = mm(t, w["wte"].T) + w["bdec"]
        lse = jax.nn.logsumexp(logits, -1)
        mlm_sum = (lse - jnp.take_along_axis(
            logits, mlm[..., None], -1)[..., 0]).sum()
        nl = mm(pooled, w["wn"]) + w["bn"]
        nsp_sum = (jax.nn.logsumexp(nl, -1) - jnp.take_along_axis(
            nl, nsp[:, None], -1)[:, 0]).sum()
    return mlm_sum, nsp_sum


@functools.lru_cache(maxsize=None)
def _programs(cfg_json, control, n_masked, n_rows, seq):
    """The jitted block step, the gradient sum and the mask draw, built
    once."""
    cfg = json.loads(cfg_json)

    def block_loss(w, blk, masks):
        a, c = loss_sums(w, blk, cfg, control, masks)
        return a / n_masked + c / n_rows

    return (jax.jit(jax.value_and_grad(block_loss)),
            jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)),
            jax.jit(lambda key: dropout_masks(cfg, key, n_rows, seq)))


def value_and_grad(w, batch, cfg, control=False, rows=16, key=None):
    """Loss (mean MLM + mean NSP, as the program's criterion) and its
    gradient over the whole batch, accumulated over blocks of ``rows``
    rows so that the float32 activations fit beside nothing else.
    ``key``: the step's dropout key (`step_keys`); None is no dropout."""
    ids, tt, pos, mlm, nsp = batch
    n, s = ids.shape
    npred = pos.shape[0] // n
    pos = pos.reshape(n, npred) - (jnp.arange(n) * s)[:, None]
    mlm = mlm.reshape(n, npred)
    nsp = nsp.reshape(n)

    step, add, draw = _programs(json.dumps(cfg, sort_keys=True), control,
                                n * npred, n, s)
    masks = None if key is None else draw(key)
    loss, grad = 0.0, None
    for i in range(0, n, rows):
        blk = tuple(jnp.asarray(a[i:i + rows])
                    for a in (ids, tt, pos, mlm, nsp))
        mblk = masks and {k: m[i:i + rows] if k == "emb"
                          else m[:, i:i + rows] for k, m in masks.items()}
        v, g = step(w, blk, mblk)
        loss = loss + v
        grad = g if grad is None else add(grad, g)
    return loss, grad

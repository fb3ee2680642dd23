"""Builds the BERT-base pretraining step through the program's normal
entry points (the recipe of examples/train_bert_pretrain.py --full and of
chip_smoke.py's `_bert_trainer`): BertForPretraining, AdamW, bf16 AMP,
`framework.jit.train_step`, dropout as published. The weights are the
benchmark's (reference.py's ``weights`` from the seed), made on the
device in one jitted call; the step's dropout keys come from the seed
that `paddle.seed` is given right before the step is built."""
from __future__ import annotations

import os
from types import SimpleNamespace

import jax
import numpy as np

from benchmark.lib import common

_HERE = os.path.dirname(os.path.abspath(__file__))
reference = common.load_module(os.path.join(_HERE, "reference.py"))


def feed(cfg, mix, seed):
    """batch(i) -> the i-th host batch of the seeded, learnable stream:
    token ids and MLM labels from one Zipf unigram distribution (ids
    1..V-1: no pad), random segment halves, masked positions without
    replacement, NSP labels at random."""
    b, s, npred = mix["batch"], mix["seq"], mix["masked"]
    v = cfg["vocab_size"]
    ranks = np.arange(1, v, dtype=np.float64)
    prob = ranks ** -float(mix.get("zipf_s", 1.0))
    cdf = np.cumsum(prob / prob.sum())

    def batch(i):
        rng = common.host_rng(seed, 100 + i)
        ids = (1 + np.searchsorted(cdf, rng.random((b, s)))).astype("int32")
        ids = np.minimum(ids, v - 1)
        tt = (np.arange(s)[None, :] >= rng.integers(s // 4, s, (b, 1))
              ).astype("int32")
        pos = np.argsort(rng.random((b, s)), axis=1)[:, :npred]
        pos = (np.sort(pos, axis=1) + np.arange(b)[:, None] * s
               ).ravel().astype("int32")
        mlm = np.minimum(1 + np.searchsorted(cdf, rng.random(b * npred)),
                         v - 1).astype("int32")
        nsp = rng.integers(0, 2, (b, 1)).astype("int32")
        return ids, tt, pos, mlm, nsp

    return batch


def trainer(cfg, mix, seed, devices):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import amp
    from paddle_tpu.framework import jit as fjit
    from paddle_tpu.framework.random import prng_impl
    from paddle_tpu.models import (BertConfig, BertForPretraining,
                                   BertPretrainingCriterion)

    bc = BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        hidden_act=cfg["hidden_act"],
        hidden_dropout_prob=cfg["hidden_dropout_prob"],
        attention_probs_dropout_prob=cfg["attention_probs_dropout_prob"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        initializer_range=cfg["initializer_range"],
        pad_token_id=cfg["pad_token_id"],
        use_flash_attention=cfg["use_flash_attention"])
    rng_seed = int(seed) % (2 ** 31 - 1)
    paddle.seed(rng_seed)
    model = BertForPretraining(bc)
    w = jax.jit(lambda k: reference.by_program_name(
        reference.weights(cfg, k)))(common.seed_key(seed))
    common.assign_weights(model, w)
    del w
    crit = BertPretrainingCriterion(bc.vocab_size)
    o = cfg["optimizer"]
    optimizer = opt.AdamW(learning_rate=o["lr"], beta1=o["beta1"],
                          beta2=o["beta2"], epsilon=o["eps"],
                          weight_decay=o["weight_decay"],
                          parameters=model.parameters())

    def loss_fn(m, ids, tt, pos, mlm, nsp):
        with amp.auto_cast():
            pred, rel = m(ids, tt, masked_positions=pos)
        return crit(pred.astype("float32"), rel.astype("float32"), mlm, nsp)

    # the step takes its key chain from the generator as it is built:
    # seeded here, so that the chain is a function of the seed alone
    paddle.seed(rng_seed)
    step = fjit.train_step(model, optimizer, loss_fn)
    ids = {id(p): n for n, p in model.named_parameters()}
    return SimpleNamespace(
        step=step, feed=feed(cfg, mix, seed),
        rng={"seed": rng_seed, "impl": prng_impl()},
        samples_per_step=mix["batch"],
        accum_names=[ids[id(p)] for p in optimizer._parameter_list],
        first_moment="moment1", first_moment_scale=1.0 - o["beta1"])


def reference_batch(batch):
    return batch

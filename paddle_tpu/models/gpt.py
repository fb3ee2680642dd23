"""GPT-style decoder-only causal language model.

The autoregressive counterpart of ``models/bert.py``: the same
``nn/transformer.py`` building blocks, assembled pre-norm and
decoder-only (``TransformerDecoderLayer(with_cross_attention=False)``),
with the LM head weight-tied to the token embedding.

Designed for the generation stack (``paddle_tpu/generation/``): the
forward takes an optional list of per-layer :class:`nn.StaticCache`
entries and then runs the INCREMENTAL attention path — functional
ring-buffer K/V writes, shapes static across steps — so one jitted
decode step serves the whole life of every sequence.

``attention_window`` gives the model sliding-window attention (each
token sees at most the last W tokens). Serving sets it to the KV-cache
capacity, which is exactly what a ring cache of that capacity computes —
the full forward and the cached decode agree numerically even after the
ring wraps (golden-tested).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import ops
from ..framework.tensor import Tensor
from ..nn.layer_base import Layer
from ..nn.layers import Dropout, Embedding, LayerList, LayerNorm
from ..nn.transformer import TransformerDecoderLayer, causal_mask
from .bert import _init_bert_weights

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "gpt_tiny_config",
           "save_gpt_model", "load_gpt_model", "truncated_draft"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_act: str = "gelu"
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 1024
    initializer_range: float = 0.02
    bos_token_id: int = 0
    eos_token_id: int = 1
    pad_token_id: int = 2
    # sliding-window attention width (None = full causal). The serving
    # engine sets this to the KV-cache capacity so the compiled full
    # forward and the O(1) ring-cache decode compute the same function.
    attention_window: int | None = None


def gpt_tiny_config() -> GPTConfig:
    """For tests / smokes: 2 layers, 64 hidden."""
    return GPTConfig(
        vocab_size=211, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0,
    )


class GPTModel(Layer):
    """Embeddings + pre-norm decoder-only stack + final LayerNorm."""

    def __init__(self, cfg: GPTConfig | None = None, **kwargs):
        super().__init__()
        self.config = cfg or GPTConfig(**kwargs)
        cfg = self.config
        self.word_embeddings = Embedding(cfg.vocab_size, cfg.hidden_size)
        self.position_embeddings = Embedding(
            cfg.max_position_embeddings, cfg.hidden_size
        )
        self.dropout = Dropout(cfg.hidden_dropout_prob)
        self.layers = LayerList([
            TransformerDecoderLayer(
                cfg.hidden_size, cfg.num_attention_heads,
                cfg.intermediate_size, dropout=cfg.hidden_dropout_prob,
                activation=cfg.hidden_act,
                attn_dropout=cfg.attention_probs_dropout_prob,
                act_dropout=0.0, normalize_before=True,
                with_cross_attention=False,
            )
            for _ in range(cfg.num_hidden_layers)
        ])
        self.norm_f = LayerNorm(cfg.hidden_size)
        _init_bert_weights(self, cfg.initializer_range)

    @staticmethod
    def _wrap(x, dtype=None):
        if isinstance(x, Tensor):
            return x
        arr = jnp.asarray(x)
        if dtype is not None:
            arr = arr.astype(dtype)
        return Tensor._from_array(arr)

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                caches=None):
        """Hidden states ``[B, T, H]``; with ``caches`` (a list of
        per-layer ``StaticCache``) also the updated caches."""
        input_ids = self._wrap(input_ids)
        t = input_ids.shape[1]
        if position_ids is None:
            position_ids = ops.expand(
                ops.unsqueeze(ops.arange(t, dtype="int64"), 0),
                [input_ids.shape[0], t],
            )
        else:
            position_ids = self._wrap(position_ids)
        if attention_mask is None:
            attention_mask = causal_mask(
                t, window=self.config.attention_window)
        else:
            attention_mask = self._wrap(attention_mask)
        x = self.dropout(
            self.word_embeddings(input_ids)
            + self.position_embeddings(position_ids)
        )
        if caches is None:
            for layer in self.layers:
                x = layer(x, tgt_mask=attention_mask)
            return self.norm_f(x)
        if isinstance(x._array, jax.core.Tracer) and self._layers_alike():
            x, new_caches = self._cached_stack_traced_once(
                x, attention_mask, caches)
        else:
            new_caches = []
            for layer, cache in zip(self.layers, caches):
                x, c = layer(x, tgt_mask=attention_mask, cache=cache)
                new_caches.append(c)
        return self.norm_f(x), new_caches

    def _layers_alike(self):
        """Is every layer layer 0 again, so that one trace of its
        forward stands for all? Same sublayer classes in eval mode,
        same parameter shapes, no buffer and no hook (either may carry
        what a shared trace would take from layer 0 alone)."""
        def shape(layer):
            subs = list(layer.named_sublayers(include_self=True))
            if any(s.training or s._forward_pre_hooks
                   or s._forward_post_hooks for _, s in subs) \
                    or any(True for _ in layer.named_buffers()):
                return None
            return ([(n, type(s)) for n, s in subs],
                    [(n, tuple(p.shape), str(p.dtype))
                     for n, p in layer.named_parameters()])

        first = shape(self.layers[0])
        return first is not None and all(
            shape(layer) == first for layer in self.layers[1:])

    def _cached_stack_traced_once(self, x, mask, caches):
        """The cached forward through every layer, inside a trace:
        layer 0's forward is traced once, as a function of a layer's
        parameters, and called once per layer, so a program of N equal
        layers traces and lowers one of them (gpt2-large's 36: 1.3-1.6 s
        a program became 0.4-0.7 on the v5e's host, and a server warms
        six; PERF.md, PR 26).
        XLA inlines the calls: the compiled program is what the loop
        over the layers gives."""
        first = self.layers[0]
        slots = [p for _, p in first.named_parameters()]

        @jax.jit  # a new one per outer trace: the trace's side effects
        def one(arrays, x, mask, cache):  # (schedule log) happen in each
            saved = [p._array for p in slots]
            try:
                for p, a in zip(slots, arrays):
                    p._array = a
                y, c = first(Tensor._from_array(x),
                             tgt_mask=Tensor._from_array(mask), cache=cache)
            finally:
                for p, a in zip(slots, saved):
                    p._array = a
            return y._array, c

        x, mask, new_caches = x._array, mask._array, []
        for layer, cache in zip(self.layers, caches):
            x, c = one([p._array for _, p in layer.named_parameters()],
                       x, mask, cache)
            new_caches.append(c)
        return Tensor._from_array(x), new_caches


class GPTForCausalLM(Layer):
    """GPTModel + weight-tied LM head: logits over the vocabulary."""

    def __init__(self, cfg: GPTConfig | None = None, **kwargs):
        super().__init__()
        self.gpt = GPTModel(cfg, **kwargs)
        self.config = self.gpt.config

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                caches=None):
        out = self.gpt(input_ids, position_ids, attention_mask, caches)
        hidden = out[0] if caches is not None else out
        logits = ops.matmul(hidden, self.gpt.word_embeddings.weight,
                            transpose_y=True)
        return logits if caches is None else (logits, out[1])

    # -- generation-engine contract ------------------------------------------

    def cache_spec(self):
        """(num_layers, num_heads, head_dim) for KV-cache allocation."""
        cfg = self.config
        return (cfg.num_hidden_layers, cfg.num_attention_heads,
                cfg.hidden_size // cfg.num_attention_heads)


# ---------------------------------------------------------------------------
# persistence + draft construction (serving fleets)
# ---------------------------------------------------------------------------


def save_gpt_model(model: "GPTForCausalLM", dirname):
    """Persist a causal LM as ``config.json`` + ``model.pdparams`` —
    the unit a generation backend process boots from
    (``python -m paddle_tpu.serving.backend --kind generate --gpt-dir
    DIR``), and the shape a draft-model directory takes
    (``--draft-dir``)."""
    import dataclasses
    import json
    import os

    from ..framework.serialization import save

    os.makedirs(dirname, exist_ok=True)
    cfg = dataclasses.asdict(model.config)
    with open(os.path.join(dirname, "config.json"), "w") as f:
        json.dump(cfg, f, indent=1, sort_keys=True)
    save(model.state_dict(), os.path.join(dirname, "model.pdparams"))
    return dirname


def load_gpt_model(dirname) -> "GPTForCausalLM":
    """Rebuild a :func:`save_gpt_model` directory into a ready
    :class:`GPTForCausalLM` (eval mode)."""
    import json
    import os

    from ..framework.serialization import load

    with open(os.path.join(dirname, "config.json")) as f:
        cfg = GPTConfig(**json.load(f))
    model = GPTForCausalLM(cfg)
    model.set_state_dict(load(os.path.join(dirname, "model.pdparams")))
    model.eval()
    return model


def truncated_draft(model: "GPTForCausalLM",
                    num_layers: int = 1) -> "GPTForCausalLM":
    """A layer-skip draft for speculative decoding: the target's
    embeddings, FIRST ``num_layers`` decoder layers, final norm, and
    (tied) LM head, copied into a shallower GPT.

    Because the residual stream is dominated by the embedding path, the
    truncated stack's argmax agrees with the full model's far more
    often than chance — a distillation-free draft in the
    self-speculative-decoding spirit, and the default draft the smoke
    uses. For production the draft is any separately trained
    small GPT sharing the vocab (``--draft-dir``).
    """
    import dataclasses

    cfg = dataclasses.replace(model.config,
                              num_hidden_layers=int(num_layers))
    draft = GPTForCausalLM(cfg)
    src = model.state_dict()
    own = draft.state_dict()
    draft.set_state_dict({
        k: src[k] for k, v in own.items()
        if k in src and tuple(src[k].shape) == tuple(v.shape)})
    draft.eval()
    return draft

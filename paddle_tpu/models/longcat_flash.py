"""A decoder of double layers: two latent attentions and two dense
feed-forwards a layer, and a routed-expert branch that runs beside the
dense path (shortcut-connected mixture of experts).

The language model of the ``LongCat-Flash`` family as its public
``config.json`` describes it, pre-norm with RMSNorm and no biases. Layer
``l`` holds ``attn[0]``, ``attn[1]`` (:class:`nn.mla.CachedLatentAttention`:
multi-head latent attention, a one-plane latent ring each), ``mlp[0]``,
``mlp[1]`` (dense SwiGLU of ``ffn_hidden_size``), ``moe``
(:class:`parallel.moe.RoutedExperts`: softmax router over
``n_routed_experts + zero_expert_num`` outputs, ``moe_topk`` chosen by
score plus selection bias, weights the scores times
``routed_scaling_factor`` and not renormalised, zero-compute experts that
return the token) and four norms::

    a  = x + attn[0](input_norm_0(x))
    u  = post_norm_0(a)
    s  = moe(u)                  # the shortcut branch: mlp[0]'s input
    b  = a + mlp[0](u)
    c  = b + attn[1](input_norm_1(b))
    x' = c + mlp[1](post_norm_1(c)) + s

then a final RMSNorm and an untied head. The branch ``s`` depends on the
first half of the layer only, so in a deployment its exchange between
chips runs while the second attention and feed-forward compute; on one
chip it is simply added at the layer's end.

:class:`LongcatFlashConfig` takes the published keys by their names,
plus what one member of an expert-parallel group holds: ``experts_held =
(first, count)`` of the routed experts and ``vocab_held`` rows of the
embedding and head.

For the generation engine :meth:`LongcatFlashForCausalLM.cache_spec`
lists TWO latent kinds a layer, in the order the forward consumes them.
``forward(input_ids, position_ids, attention_mask, caches)`` is the
engine's contract. With caches, one token a row is a decode step (the
absorbed attention path, ``attention_mask`` the additive decode mask);
more than one is a prefill from position 0 into fresh caches (the
expanded path, ``attention_mask`` the additive key-padding mask ``[B, 1,
1, T]``), and the logits are those of the last real position alone,
``[B, 1, vocab_held]``. Parameters and activations are ``dtype``
(bfloat16 when served); norm statistics, softmax, rotary angles and
router scores are float32.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ..framework.tensor import Parameter, Tensor
from ..generation import cache as _cache
from ..nn.gqa import rms_norm
from ..nn.layer_base import Layer
from ..nn.layers import LayerList
from ..nn.linear_attention import normal_or_zeros
from ..nn.mla import CachedLatentAttention
from ..parallel.moe import RoutedExperts, routing_stats
from .exaone_moe import DenseSwiGLU

__all__ = ["LongcatFlashConfig", "LongcatFlashForCausalLM"]

# a prompt's expanded attention: 256 queries a block (a float32 score
# block of 64 heads x 256 x 4,096 keys is 0.27 GB); a decode step's
# 8,192 ring rows and a longer prompt's keys go 4,096 at a time
# (XLA:TPU's reductions over rows of 4,300-8,192 scores: nn/gqa.py)
_PREFILL_BLOCK, _KEY_CHUNK = 256, 4096
# a prompt's expert branch runs over this many tokens at a time: the
# layer gathers top_k rows a token and combines them in float32 (at
# 4,096 tokens x 12: 0.6 GB in, 0.6 GB out, 1.2 GB on the way back,
# beside 12.8 GB of weights and rings); 1,024 tokens are 0.6 GB in all
_MOE_CHUNK = 1024


@dataclass
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_nope_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    bos_token_id: int = 0
    eos_token_id: int = 1
    pad_token_id: int = 2
    dtype: str = "float32"
    # False: parameters start as zeros, for a caller that assigns every one
    init_weights: bool = True
    # one member's share of an expert-parallel group
    experts_held: tuple | None = None  # (first, count); None: all
    vocab_held: int | None = None      # rows 0 .. vocab_held-1; None: all


class LongcatDecoderLayer(Layer):
    def __init__(self, cfg: LongcatFlashConfig):
        super().__init__()
        dtype = cfg.dtype
        std = cfg.initializer_range if cfg.init_weights else None
        self.eps = cfg.rms_norm_eps
        self.attn = LayerList([CachedLatentAttention(
            cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, rope_theta=cfg.rope_theta,
            scale_q=cfg.mla_scale_q_lora, scale_kv=cfg.mla_scale_kv_lora,
            prefill_block=_PREFILL_BLOCK, key_chunk=_KEY_CHUNK,
            norm_eps=cfg.rms_norm_eps, initializer_range=std, dtype=dtype)
            for _ in range(2)])
        self.mlp = LayerList([DenseSwiGLU(
            cfg.hidden_size, cfg.ffn_hidden_size, std, dtype)
            for _ in range(2)])
        self.moe = RoutedExperts(
            cfg.hidden_size, cfg.expert_ffn_hidden_size,
            cfg.n_routed_experts, cfg.moe_topk, held=cfg.experts_held,
            score="softmax", norm_topk_prob=False,
            routed_scaling_factor=cfg.routed_scaling_factor,
            zero_experts=cfg.zero_expert_num, selection_bias=True,
            initializer_range=std, dtype=dtype)
        ones = jnp.ones((cfg.hidden_size,), dtype)
        for name in ("input_norm_0", "post_norm_0", "input_norm_1",
                     "post_norm_1"):
            setattr(self, name, Parameter.from_array(ones, name=name))

    def forward(self, x, caches=None, mask=None, positions=None, valid=None):
        """``caches``: this layer's two, or None. Returns ``x'`` or
        ``(x', [cache_0, cache_1])``."""
        new = []

        def attend(i, x, norm):
            out = self.attn[i](
                rms_norm(x, norm._array, self.eps), mask=mask,
                cache=None if caches is None else caches[i],
                positions=positions)
            if caches is None:
                return x + out
            new.append(out[1])
            return x + out[0]

        a = attend(0, x, self.input_norm_0)
        u = rms_norm(a, self.post_norm_0._array, self.eps)
        s = self.moe.in_chunks(u, valid, _MOE_CHUNK)
        b = a + self.mlp[0](u)
        c = attend(1, b, self.input_norm_1)
        x = c + self.mlp[1](rms_norm(c, self.post_norm_1._array, self.eps)) \
            + s
        return x if caches is None else (x, new)


class LongcatFlashForCausalLM(Layer):
    """Embedding slice + the double layers + final RMSNorm + untied head
    over the same slice."""

    def __init__(self, cfg: LongcatFlashConfig | None = None, **kwargs):
        super().__init__()
        self.config = cfg = cfg or LongcatFlashConfig(**kwargs)
        rows = int(cfg.vocab_held or cfg.vocab_size)
        h = cfg.hidden_size
        std = cfg.initializer_range if cfg.init_weights else None
        for name, shape in (("embed_tokens", (rows, h)),
                            ("lm_head", (h, rows))):
            setattr(self, name, Parameter.from_array(
                normal_or_zeros(shape, std, cfg.dtype), name=name))
        self.layers = LayerList([LongcatDecoderLayer(cfg)
                                 for _ in range(cfg.num_layers)])
        self.norm = Parameter.from_array(jnp.ones((h,), cfg.dtype),
                                         name="norm")
        self._stats = None

    # -- generation-engine contract ------------------------------------------

    def cache_spec(self):
        """Two latent rings a layer (``kv_lora_rank`` latent channels +
        ``qk_rope_head_dim`` rotated key channels a row), in the order
        the forward consumes them: layer 0's first attention, its
        second, layer 1's first, ..."""
        cfg = self.config
        return [_cache.latent(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
                for _ in range(2 * len(self.layers))]

    def routing_stats(self):
        """What the last forward routed here, per expert layer: token-
        expert pairs that landed on held experts (``pairs [L]``),
        distinct held experts that got at least one (``hit [L]``), pairs
        that chose a zero-compute expert (``zero_pairs [L]``), and per
        held expert its pairs over all layers (``load [held]``); where
        the experts' kernel ran, also the rows its row tiles multiplied
        for those pairs (``tile_rows [L]``). Inside a trace these are
        traced values of that trace."""
        return self._stats

    def _head(self, x):
        x = rms_norm(x, self.norm._array, self.config.rms_norm_eps)
        return jnp.matmul(x, self.lm_head._array,
                          preferred_element_type=jnp.float32)

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                caches=None):
        ids = input_ids._array if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        mask = attention_mask._array if isinstance(attention_mask, Tensor) \
            else attention_mask
        b, t = ids.shape
        if position_ids is None:
            position_ids = jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.int32)[None], (b, t))
        elif isinstance(position_ids, Tensor):
            position_ids = position_ids._array
        valid = None
        if mask is not None and t > 1:
            valid = mask[:, 0, 0, :] == 0
        x = self.embed_tokens._array[ids]
        new_caches = []
        for i, layer in enumerate(self.layers):
            out = layer(x, mask=mask, positions=position_ids, valid=valid,
                        caches=None if caches is None
                        else caches[2 * i:2 * i + 2])
            if caches is None:
                x = out
            else:
                x, pair = out
                new_caches.extend(pair)
        self._stats = routing_stats([layer.moe for layer in self.layers])
        if caches is not None and t > 1:
            # a prefill is read at its last real position only
            last = (t if valid is None else valid.sum(-1)) - 1
            x = jnp.take_along_axis(
                x, jnp.broadcast_to(jnp.asarray(last, jnp.int32),
                                    (b,))[:, None, None], axis=1)
        logits = Tensor._from_array(self._head(x))
        return logits if caches is None else (logits, new_caches)

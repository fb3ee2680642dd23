"""A hybrid decoder: gated-delta-rule linear attention beside NoPE
grouped-query attention, every feed-forward a mixture of routed experts.

The architecture of the ``solar_open2`` family as its public
``config.json`` describes it: pre-norm residual blocks with RMSNorm and
no biases; layers listed in ``gqa_layers`` are softmax grouped-query
attention without any position signal and with an elementwise sigmoid
output gate (:class:`nn.gqa.CachedGQAttention`, ``gated``), the layers
between them :class:`nn.GatedDeltaAttention`;
every layer's feed-forward is :class:`parallel.moe.RoutedExperts`
(sigmoid router, top-k renormalised, one shared expert); a final
RMSNorm and an untied head. :class:`HybridMoEConfig` takes the published
keys by their names, plus what one member of an expert-parallel group
holds: ``experts_held = (first, count)`` of the routed experts and
``vocab_held`` rows of the embedding and head.

For the generation engine the layers are of two kinds
(:meth:`HybridMoEForCausalLM.cache_spec`): a GQA layer keeps a K/V ring
for its K/V heads, a linear layer a constant state and convolution tail.
``forward(input_ids, position_ids, attention_mask, caches)`` is the
engine's contract; positions are not used. With caches, one token a row
is a decode step over the ring (``attention_mask`` the additive ``[B, 1,
1, store]`` decode mask); more than one is a prefill from position 0
into fresh caches, causal by construction and computed by query blocks,
``attention_mask`` then the additive key-padding mask ``[B, 1, 1, T]``
(right-padding neither is attended by real tokens nor advances a
state). Parameters and activations are ``dtype`` (bfloat16 when
served); norm statistics, softmax, router scores, decay and state are
float32.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp

from ..framework.tensor import Parameter, Tensor
from ..generation import cache as _cache
from ..nn.layer_base import Layer
from ..nn.layers import LayerList
from ..nn.gqa import CachedGQAttention, rms_norm as _rms_norm
from ..nn.linear_attention import GatedDeltaAttention, normal_or_zeros
from ..parallel.moe import RoutedExperts, routing_stats

__all__ = ["HybridMoEConfig", "HybridMoEForCausalLM"]


@dataclass
class HybridMoEConfig:
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    gqa_layers: tuple = tuple(range(0, 48, 4))
    use_gqa_gate: bool = True
    linear_attn_config: dict = field(default_factory=lambda: {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64})
    kda_allow_neg_eigval: bool = True
    kda_gate_rank: int | None = None  # None: the linear head dim
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02
    bos_token_id: int = 0
    eos_token_id: int = 1
    pad_token_id: int = 2
    dtype: str = "float32"
    # False: parameters start as zeros, for a caller that assigns every
    # one (drawing 3.3 G values only to replace them costs a server's
    # start seconds)
    init_weights: bool = True
    # one member's share of an expert-parallel group
    experts_held: tuple | None = None  # (first, count); None: all
    vocab_held: int | None = None      # rows 0 .. vocab_held-1; None: all


class HybridDecoderLayer(Layer):
    def __init__(self, cfg: HybridMoEConfig, index: int):
        super().__init__()
        dtype = cfg.dtype
        std = cfg.initializer_range if cfg.init_weights else None
        self.eps = cfg.rms_norm_eps
        self.is_gqa = index in tuple(cfg.gqa_layers)
        if self.is_gqa:
            self.mixer = CachedGQAttention(
                cfg.hidden_size, cfg.num_attention_heads,
                cfg.num_key_value_heads, cfg.head_dim,
                gated=cfg.use_gqa_gate, initializer_range=std, dtype=dtype)
        else:
            lin = cfg.linear_attn_config
            self.mixer = GatedDeltaAttention(
                cfg.hidden_size, lin["num_heads"], lin["head_dim"],
                conv_size=lin["short_conv_kernel_size"],
                gate_rank=cfg.kda_gate_rank,
                allow_neg_eigval=cfg.kda_allow_neg_eigval,
                norm_eps=cfg.rms_norm_eps, initializer_range=std,
                dtype=dtype)
        self.moe = RoutedExperts(
            cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.n_routed_experts, cfg.num_experts_per_tok,
            held=cfg.experts_held,
            shared_width=cfg.moe_intermediate_size * cfg.n_shared_experts,
            score=cfg.scoring_func, norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            initializer_range=std, dtype=dtype)
        ones = jnp.ones((cfg.hidden_size,), dtype)
        self.input_norm = Parameter.from_array(ones, name="input_norm")
        self.post_norm = Parameter.from_array(ones, name="post_norm")

    def forward(self, x, cache=None, mask=None, valid=None):
        y = _rms_norm(x, self.input_norm._array, self.eps)
        if self.is_gqa:
            out = self.mixer(y, cache=cache, mask=mask)
        else:
            out = self.mixer(y, cache=cache, valid=valid)
        if cache is not None:
            out, cache = out
        x = x + out
        x = x + self.moe(_rms_norm(x, self.post_norm._array, self.eps),
                         valid=valid)
        return x if cache is None else (x, cache)


class HybridMoEForCausalLM(Layer):
    """Embedding slice + hybrid stack + final RMSNorm + untied head over
    the same slice: logits ``[B, T, vocab_held]``."""

    def __init__(self, cfg: HybridMoEConfig | None = None, **kwargs):
        super().__init__()
        self.config = cfg = cfg or HybridMoEConfig(**kwargs)
        rows = int(cfg.vocab_held or cfg.vocab_size)
        std = cfg.initializer_range if cfg.init_weights else None
        for name, shape in (("embed_tokens", (rows, cfg.hidden_size)),
                            ("lm_head", (cfg.hidden_size, rows))):
            setattr(self, name, Parameter.from_array(
                normal_or_zeros(shape, std, cfg.dtype), name=name))
        self.layers = LayerList([HybridDecoderLayer(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = Parameter.from_array(
            jnp.ones((cfg.hidden_size,), cfg.dtype), name="norm")
        self._stats = None

    # -- generation-engine contract ------------------------------------------

    def cache_spec(self):
        """One storage kind a layer: K/V rows for the K/V heads of a GQA
        layer, state and convolution tail for a linear layer."""
        cfg = self.config
        return [
            _cache.kv(cfg.num_key_value_heads, cfg.head_dim) if layer.is_gqa
            else _cache.state(*layer.mixer.cache_shapes())
            for layer in self.layers]

    def routing_stats(self):
        """What the last forward routed here, per expert layer: token-
        expert pairs that landed on held experts (``pairs [L]``),
        distinct held experts that got at least one (``hit [L]``), and
        per held expert its pairs over all layers (``load [held]``);
        where the experts' kernel ran, also the rows its row tiles
        multiplied for those pairs (``tile_rows [L]``). Inside a trace
        these are traced values of that trace."""
        return self._stats

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                caches=None):
        ids = input_ids._array if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        mask = attention_mask._array if isinstance(attention_mask, Tensor) \
            else attention_mask
        t = ids.shape[1]
        valid = None
        if mask is not None and t > 1:
            valid = mask[:, 0, 0, :] == 0
        x = self.embed_tokens._array[ids]
        new_caches = []
        for i, layer in enumerate(self.layers):
            if caches is None:
                x = layer(x, mask=mask, valid=valid)
            else:
                x, c = layer(x, cache=caches[i], mask=mask, valid=valid)
                new_caches.append(c)
        self._stats = routing_stats([layer.moe for layer in self.layers])
        x = _rms_norm(x, self.norm._array, self.config.rms_norm_eps)
        logits = Tensor._from_array(jnp.matmul(
            x, self.lm_head._array, preferred_element_type=jnp.float32))
        return logits if caches is None else (logits, new_caches)

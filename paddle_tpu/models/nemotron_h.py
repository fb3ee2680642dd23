"""A decoder whose layers are a mixer or a feed-forward alone, in the
order a pattern string gives: Mamba-2 state-space layers beside NoPE
grouped-query attention and latent-width routed experts.

The architecture of the ``nemotron_h`` family as its public
``config.json`` describes it. ``hybrid_override_pattern`` has one
character a layer, and a layer is one RMSNorm, one module and one
residual add, ``x = x + F(RMSNorm(x))``:

- ``M``: :class:`nn.state_space.Mamba2Mixer` (a selective state-space
  recurrence with a scalar decay a head, ``B`` / ``C`` shared inside a
  group of heads, a short causal convolution, a gated grouped RMSNorm);
- ``*``: :class:`nn.gqa.CachedGQAttention`, softmax grouped-query
  attention without gate, q/k norm or any position signal (the
  state-space layers carry order);
- ``E``: :class:`parallel.moe.RoutedExperts` with non-gated ``relu2``
  experts that work in ``moe_latent_size`` channels (one down- and one
  up-projection a layer), a sigmoid router with a selection bias over
  all the published experts, and one ``relu2`` shared expert at the
  full width;

then a final RMSNorm and an untied head. No biases but the
convolution's. :class:`NemotronHConfig` takes the published keys by
their names, plus what one member of an expert-parallel group holds:
``experts_held = (first, count)`` of the routed experts and
``vocab_held`` rows of the embedding and head.

For the generation engine only the layers that keep something per slot
have a cache entry (:meth:`NemotronHForCausalLM.cache_spec`), in
pattern order: an ``M`` layer a constant state and convolution tail, a
``*`` layer a K/V ring for its K/V heads, an ``E`` layer nothing; the
model maps cache entries to layers itself. ``forward(input_ids,
position_ids, attention_mask, caches)`` is the engine's contract;
positions are not used. With caches, one token a row is a decode step
(``attention_mask`` the additive ``[B, 1, 1, store]`` decode mask); more
than one is a prefill from position 0 into fresh caches, attention
causal by construction and computed by query blocks, the recurrence by
chunks, ``attention_mask`` then the additive key-padding mask ``[B, 1,
1, T]`` (right padding neither is attended by real tokens nor advances
a state), and the logits those of the last real position only.
Parameters and activations are ``dtype`` (bfloat16 when served); norm
statistics, softmax, router scores, step, decay and state are float32.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ..errors import InvalidArgumentError
from ..framework.tensor import Parameter, Tensor
from ..generation import cache as _cache
from ..nn.layer_base import Layer
from ..nn.layers import LayerList
from ..nn.gqa import CachedGQAttention, rms_norm
from ..nn.linear_attention import normal_or_zeros
from ..nn.state_space import Mamba2Mixer
from ..parallel.moe import RoutedExperts, routing_stats

__all__ = ["NemotronHConfig", "NemotronHForCausalLM"]

# a prompt's expert layer takes this many tokens at a time, so that the
# sorted token-expert pairs of a 4,096-token bucket (22 a token) and
# their hidden rows are never all alive at once
_MOE_CHUNK = 1024


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int | None = 1024
    moe_shared_expert_intermediate_size: int = 5376
    mlp_hidden_act: str = "relu2"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
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


def _module(cfg: NemotronHConfig, kind: str):
    std = cfg.initializer_range if cfg.init_weights else None
    if kind == "M":
        return Mamba2Mixer(
            cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_head_dim,
            cfg.ssm_state_size, groups=cfg.n_groups,
            conv_size=cfg.conv_kernel, chunk=cfg.chunk_size,
            norm_eps=cfg.layer_norm_epsilon,
            dt_limits=(cfg.time_step_min, cfg.time_step_max),
            dt_floor=cfg.time_step_floor,
            initializer_range=std, dtype=cfg.dtype)
    if kind == "*":
        return CachedGQAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim,
            initializer_range=std, dtype=cfg.dtype)
    if kind == "E":
        if cfg.mlp_hidden_act != "relu2":
            raise InvalidArgumentError(
                f"mlp_hidden_act {cfg.mlp_hidden_act!r}: the family's "
                "experts are relu2")
        return RoutedExperts(
            cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.n_routed_experts, cfg.num_experts_per_tok,
            held=cfg.experts_held,
            shared_width=cfg.moe_shared_expert_intermediate_size,
            score="sigmoid", norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            selection_bias=True, activation="relu2",
            latent_size=cfg.moe_latent_size, initializer_range=std,
            dtype=cfg.dtype)
    raise InvalidArgumentError(
        f"hybrid_override_pattern holds {kind!r}; a layer is M, * or E")


class NemotronHLayer(Layer):
    """One norm, one module (``mixer``, whatever its kind), one add."""

    def __init__(self, cfg: NemotronHConfig, kind: str):
        super().__init__()
        self.kind, self.eps = kind, cfg.layer_norm_epsilon
        self.mixer = _module(cfg, kind)
        self.norm = Parameter.from_array(
            jnp.ones((cfg.hidden_size,), cfg.dtype), name="norm")

    def forward(self, x, cache=None, mask=None, valid=None):
        """``x'``, or ``(x', new_cache)`` where a cache was handed in."""
        y = rms_norm(x, self.norm._array, self.eps)
        if self.kind == "E":
            return x + self.mixer.in_chunks(y, valid, _MOE_CHUNK)
        if self.kind == "M":
            out = self.mixer(y, cache=cache, valid=valid)
        else:
            out = self.mixer(y, cache=cache, mask=mask)
        if cache is None:
            return x + out
        return x + out[0], out[1]


class NemotronHForCausalLM(Layer):
    """Embedding slice + the patterned stack + final RMSNorm + untied
    head over the same slice."""

    def __init__(self, cfg: NemotronHConfig | None = None, **kwargs):
        super().__init__()
        self.config = cfg = cfg or NemotronHConfig(**kwargs)
        pattern = cfg.hybrid_override_pattern
        if len(pattern) != cfg.num_hidden_layers:
            raise InvalidArgumentError(
                f"hybrid_override_pattern has {len(pattern)} layers, "
                f"num_hidden_layers is {cfg.num_hidden_layers}")
        rows = int(cfg.vocab_held or cfg.vocab_size)
        h = cfg.hidden_size
        std = cfg.initializer_range if cfg.init_weights else None
        for name, shape in (("embed_tokens", (rows, h)),
                            ("lm_head", (h, rows))):
            setattr(self, name, Parameter.from_array(
                normal_or_zeros(shape, std, cfg.dtype), name=name))
        self.layers = LayerList([NemotronHLayer(cfg, kind)
                                 for kind in pattern])
        self.norm = Parameter.from_array(jnp.ones((h,), cfg.dtype),
                                         name="norm")
        self._stats = None

    # -- generation-engine contract ------------------------------------------

    def cache_spec(self):
        """One storage kind for each layer that keeps something per
        slot, in pattern order: state and convolution tail for an ``M``
        layer, K/V rows for the K/V heads of a ``*`` layer; an ``E``
        layer keeps nothing and has no entry."""
        cfg = self.config
        return [
            _cache.state(*layer.mixer.cache_shapes()) if layer.kind == "M"
            else _cache.kv(cfg.num_key_value_heads, cfg.head_dim)
            for layer in self.layers if layer.kind != "E"]

    def routing_stats(self):
        """What the last forward routed here, per expert layer: token-
        expert pairs that landed on held experts (``pairs [L]``),
        distinct held experts that got at least one (``hit [L]``), and
        per held expert its pairs over all layers (``load [held]``);
        where the experts' kernel ran, also the rows its row tiles
        multiplied for those pairs (``tile_rows [L]``). Inside a trace
        these are traced values of that trace; ``None`` for a pattern
        without expert layers."""
        return self._stats

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                caches=None):
        ids = input_ids._array if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        mask = attention_mask._array if isinstance(attention_mask, Tensor) \
            else attention_mask
        b, t = ids.shape
        valid = None
        if mask is not None and t > 1:
            valid = mask[:, 0, 0, :] == 0
        x = self.embed_tokens._array[ids]
        kept = iter(caches or ())
        new_caches = []
        for layer in self.layers:
            if layer.kind == "E":
                x = layer(x, valid=valid)
            elif caches is None:
                x = layer(x, mask=mask, valid=valid)
            else:
                x, c = layer(x, cache=next(kept), mask=mask, valid=valid)
                new_caches.append(c)
        experts = [layer.mixer for layer in self.layers if layer.kind == "E"]
        if experts:
            self._stats = routing_stats(experts)
        if caches is not None and t > 1:
            # a prefill is read at its last real position only
            last = (t if valid is None else valid.sum(-1)) - 1
            x = jnp.take_along_axis(
                x, jnp.broadcast_to(jnp.asarray(last, jnp.int32),
                                    (b,))[:, None, None], axis=1)
        x = rms_norm(x, self.norm._array, self.config.layer_norm_epsilon)
        logits = Tensor._from_array(jnp.matmul(
            x, self.lm_head._array, preferred_element_type=jnp.float32))
        return logits if caches is None else (logits, new_caches)

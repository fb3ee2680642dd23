"""A decoder whose mixers are block-sparse softmax attention or Lightning
linear attention, in the order a list gives, with the MiniCPM family's
three scalings.

The architecture of the ``minicpm_sala`` family as its public
``config.json`` describes it. ``mixer_types`` has one name a layer:

- ``minicpm4``: :class:`nn.SparseGQAttention`, grouped-query attention
  without a position signal (``attn_use_rope`` false), q and k
  RMS-normalised a head, an elementwise sigmoid output gate, every
  query choosing the blocks it attends from a ring of pooled keys
  (InfLLM-V2; the sizes are ``sparse_config``, the family's own, which
  the published file does not carry);
- ``lightning-attn``: :class:`nn.LightningAttention`, a linear
  recurrence with a decay fixed a head and a layer, q and k
  RMS-normalised and rotated by their position (these layers carry the
  order), an RMSNorm over all output channels and a sigmoid gate;

every layer's feed-forward is a dense SwiGLU. The scalings (muP): the
embedding times ``scale_emb``; every residual branch times ``scale_depth
/ sqrt(depth)``; the final hidden state divided by ``hidden_size /
dim_model_base`` under an untied head. A cut of the depth keeps the
published layers' numbers: ``layer_offset`` is the published index of
layer 0 and ``published_layers`` the published depth, which the
residual scale and the Lightning decays are made from.

For the generation engine (:meth:`MiniCPMSALAForCausalLM.cache_spec`) a
``minicpm4`` layer keeps K and V rings and a ring of pooled keys, a
``lightning-attn`` layer one float32 state. ``forward(input_ids,
position_ids, attention_mask, caches)`` is the engine's contract;
positions ARE used, by the Lightning layers. With caches, one token a
row is a decode step (``attention_mask`` is not read: a sparse layer
masks by ``pos``); more than one is a prefill from position 0 into fresh
caches, ``attention_mask`` the additive key-padding mask ``[B, 1, 1,
T]`` (right padding is causally behind every real token and does not
advance a state), and the logits those of the last real position only.
A prompt's feed-forward takes ``_FFN_CHUNK`` tokens at a time.
Parameters and activations are ``dtype`` (bfloat16 when served); norm
statistics, both softmaxes, decay and state are float32.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from ..errors import InvalidArgumentError
from ..framework.tensor import Parameter, Tensor
from ..generation import cache as _cache
from ..nn.gqa import rms_norm
from ..nn.layer_base import Layer
from ..nn.layers import LayerList
from ..nn.linear_attention import (LightningAttention, lightning_slopes,
                                   normal_or_zeros)
from ..nn.sparse_attention import SparseConfig, SparseGQAttention
from ..nn.transformer import StateCache

__all__ = ["MiniCPMSALAConfig", "MiniCPMSALAForCausalLM"]

# a prompt's feed-forward takes this many tokens at a time: the three
# [T, intermediate] tensors of a 32,768-token bucket are 3.2 GB whole
_FFN_CHUNK = 8192

_PUBLISHED_MIXERS = tuple(
    "minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31) else "lightning-attn"
    for i in range(32))


@dataclass
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    mixer_types: tuple = _PUBLISHED_MIXERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    qk_norm: bool = True
    attn_use_rope: bool = False
    attn_use_output_gate: bool = True
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_use_rope: bool = True
    use_output_gate: bool = True
    use_output_norm: bool = True
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    max_position_embeddings: int = 524288
    sparse_config: dict = field(default_factory=lambda: dict(
        kernel_size=32, kernel_stride=16, block_size=64, init_blocks=1,
        window_size=2048, topk=64, dense_len=8192))
    # how the program blocks a prompt (no shape of a weight, no output):
    # a sparse layer's queries and keys at a time, a Lightning layer's
    # chunk. The benchmark's readers find the prompt's device events by
    # these, from the same configuration file
    sparse_q_block: int = 512
    sparse_key_chunk: int = 2048
    lightning_chunk: int = 256
    # a cut of the depth: the published index of layer 0 and the
    # published depth (None: this model is the whole of it)
    layer_offset: int = 0
    published_layers: int | None = None
    initializer_range: float = 0.02
    bos_token_id: int = 0
    eos_token_id: int = 1
    pad_token_id: int = 2
    dtype: str = "float32"
    # False: parameters start as zeros, for a caller that assigns every one
    init_weights: bool = True

    def sparse(self):
        c = self.sparse_config
        return SparseConfig(c["kernel_size"], c["kernel_stride"],
                            c["block_size"], c["init_blocks"],
                            c["window_size"], c["topk"], c["dense_len"])


def _mixer(cfg: MiniCPMSALAConfig, index: int):
    kind = cfg.mixer_types[index]
    std = cfg.initializer_range if cfg.init_weights else None
    if kind == "minicpm4":
        if cfg.attn_use_rope or not (cfg.qk_norm
                                     and cfg.attn_use_output_gate):
            raise InvalidArgumentError(
                "the sparse mixer is written for qk_norm and an output "
                "gate, and without rotary")
        return SparseGQAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, sparse=cfg.sparse(),
            q_block=cfg.sparse_q_block, key_chunk=cfg.sparse_key_chunk,
            norm_eps=cfg.rms_norm_eps, initializer_range=std,
            dtype=cfg.dtype)
    if kind == "lightning-attn":
        if not (cfg.qk_norm and cfg.lightning_use_rope
                and cfg.use_output_gate and cfg.use_output_norm
                and cfg.lightning_nkv == cfg.lightning_nh):
            raise InvalidArgumentError(
                "the Lightning mixer is written for qk_norm, rotary, an "
                "output norm and gate, and as many K/V heads as heads")
        return LightningAttention(
            cfg.hidden_size, cfg.lightning_nh, cfg.lightning_head_dim,
            lightning_slopes(cfg.lightning_nh, cfg.layer_offset + index,
                             cfg.published_layers or cfg.num_hidden_layers),
            rope_theta=cfg.rope_theta, norm_eps=cfg.rms_norm_eps,
            chunk=cfg.lightning_chunk, initializer_range=std,
            dtype=cfg.dtype)
    raise InvalidArgumentError(
        f"mixer_types holds {kind!r}; a mixer is minicpm4 or lightning-attn")


class MiniCPMSALALayer(Layer):
    def __init__(self, cfg: MiniCPMSALAConfig, index: int):
        super().__init__()
        self.sparse = cfg.mixer_types[index] == "minicpm4"
        self.eps = cfg.rms_norm_eps
        self.branch = cfg.scale_depth / (
            cfg.published_layers or cfg.num_hidden_layers) ** 0.5
        self.mixer = _mixer(cfg, index)
        h, f = cfg.hidden_size, cfg.intermediate_size
        std = cfg.initializer_range if cfg.init_weights else None
        for name, shape in (("w_gate", (h, f)), ("w_up", (h, f)),
                            ("w_down", (f, h))):
            setattr(self, name, Parameter.from_array(
                normal_or_zeros(shape, std, cfg.dtype), name=name))
        ones = jnp.ones((h,), cfg.dtype)
        self.input_norm = Parameter.from_array(ones, name="input_norm")
        self.post_norm = Parameter.from_array(ones, name="post_norm")

    def _ffn(self, x):
        """The scaled feed-forward branch, a prompt's ``_FFN_CHUNK``
        tokens at a time."""
        def swiglu(u):
            return self._scaled(jnp.matmul(
                jax.nn.silu(jnp.matmul(u, self.w_gate._array))
                * jnp.matmul(u, self.w_up._array), self.w_down._array,
                preferred_element_type=jnp.float32), x.dtype)

        b, t, h = x.shape
        y = rms_norm(x, self.post_norm._array, self.eps)
        if b * t <= _FFN_CHUNK or (b * t) % _FFN_CHUNK:
            return swiglu(y)
        return jax.lax.map(swiglu, y.reshape(-1, _FFN_CHUNK, h)).reshape(
            b, t, h)

    def _scaled(self, branch, dtype):
        """``scale_depth / sqrt(depth) x branch``: the product in float32
        (the factor is no bfloat16 number), rounded once."""
        return (branch.astype(jnp.float32) * self.branch).astype(dtype)

    def forward(self, x, positions, cache=None, valid=None):
        """``x'``, or ``(x', new_cache)`` where a cache was handed in."""
        y = rms_norm(x, self.input_norm._array, self.eps)
        if self.sparse:
            out = self.mixer(y, cache=cache)
        else:
            out = self.mixer(y, positions, cache=cache, valid=valid)
        if cache is not None:
            out, cache = out
        x = x + self._scaled(out, x.dtype)
        x = x + self._ffn(x)
        return x if cache is None else (x, cache)


class MiniCPMSALAForCausalLM(Layer):
    """Embedding + the mixed stack + final RMSNorm + untied head."""

    def __init__(self, cfg: MiniCPMSALAConfig | None = None, **kwargs):
        super().__init__()
        self.config = cfg = cfg or MiniCPMSALAConfig(**kwargs)
        if len(cfg.mixer_types) != cfg.num_hidden_layers:
            raise InvalidArgumentError(
                f"mixer_types names {len(cfg.mixer_types)} layers, "
                f"num_hidden_layers is {cfg.num_hidden_layers}")
        h, rows = cfg.hidden_size, cfg.vocab_size
        std = cfg.initializer_range if cfg.init_weights else None
        for name, shape in (("embed_tokens", (rows, h)),
                            ("lm_head", (h, rows))):
            setattr(self, name, Parameter.from_array(
                normal_or_zeros(shape, std, cfg.dtype), name=name))
        self.layers = LayerList([MiniCPMSALALayer(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = Parameter.from_array(jnp.ones((h,), cfg.dtype),
                                         name="norm")

    # -- generation-engine contract ------------------------------------------

    def cache_spec(self):
        """One storage kind a layer, in ``mixer_types`` order: K/V rings
        with a pooled ring for a ``minicpm4`` layer, one float32 state
        for a ``lightning-attn`` layer."""
        cfg = self.config
        return [
            _cache.sparse_kv(cfg.num_key_value_heads, cfg.head_dim,
                             cfg.sparse()) if layer.sparse
            else _cache.state(*layer.mixer.cache_shapes(), cache=StateCache)
            for layer in self.layers]

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                caches=None):
        cfg = self.config
        ids = input_ids._array if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        mask = attention_mask._array if isinstance(attention_mask, Tensor) \
            else attention_mask
        b, t = ids.shape
        pos = position_ids._array if isinstance(position_ids, Tensor) \
            else position_ids
        if pos is None:
            pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        valid = None
        if mask is not None and t > 1:
            valid = mask[:, 0, 0, :] == 0
        x = self.embed_tokens._array[ids]
        x = x * jnp.asarray(cfg.scale_emb, x.dtype)
        new_caches = []
        for i, layer in enumerate(self.layers):
            if caches is None:
                x = layer(x, pos, valid=valid)
            else:
                x, c = layer(x, pos, cache=caches[i], valid=valid)
                new_caches.append(c)
        if caches is not None and t > 1:
            # a prefill is read at its last real position only
            last = (t if valid is None else valid.sum(-1)) - 1
            x = jnp.take_along_axis(
                x, jnp.broadcast_to(jnp.asarray(last, jnp.int32),
                                    (b,))[:, None, None], axis=1)
        x = rms_norm(x, self.norm._array, cfg.rms_norm_eps)
        x = x / jnp.asarray(cfg.hidden_size / cfg.dim_model_base, x.dtype)
        logits = Tensor._from_array(jnp.matmul(
            x, self.lm_head._array, preferred_element_type=jnp.float32))
        return logits if caches is None else (logits, new_caches)

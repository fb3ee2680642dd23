"""A decoder that mixes sliding-window and full attention, with a leading
dense feed-forward and routed experts after it.

The architecture of the ``exaone_moe`` family as its public
``config.json`` describes it: pre-norm residual blocks with RMSNorm and
no biases; every mixer is grouped-query softmax attention whose q and k
are RMS-normalised per head (:class:`nn.gqa.CachedGQAttention`); a layer
whose ``layer_types`` entry is ``sliding_attention`` rotates q and k by
their absolute position and sees the last ``sliding_window`` keys, a
``full_attention`` layer is causal over the whole context and has no
position signal; the first ``first_k_dense_replace`` layers have a dense
SwiGLU feed-forward of ``intermediate_size``, the others
:class:`parallel.moe.RoutedExperts` (sigmoid router, top-k renormalised
and scaled, one shared expert); a final RMSNorm and an untied head.
With ``num_nextn_predict_layers`` 1 the model also holds the family's
multi-token-prediction module (:meth:`ExaoneMoEForCausalLM.predict_ahead`);
the serving path does not use it.

:class:`ExaoneMoEConfig` takes the published keys by their names, plus
what one member of an expert-parallel group holds: ``experts_held =
(first, count)`` of the routed experts and ``vocab_held`` rows of the
embedding and head.

For the generation engine the layers are of two kinds
(:meth:`ExaoneMoEForCausalLM.cache_spec`): a full layer keeps a K/V ring
as long as the cache, a sliding layer one of ``sliding_window`` rows.
``forward(input_ids, position_ids, attention_mask, caches)`` is the
engine's contract. With caches, one token a row is a decode step
(``attention_mask`` the ``{ring length: additive decode mask}`` of
``cache.kinds_decode_mask``); more than one is a prefill from position 0
into fresh caches, ``attention_mask`` then the additive key-padding mask
``[B, 1, 1, T]``, and the logits are those of the last real position
alone, ``[B, 1, vocab_held]``. Parameters and activations are ``dtype``
(bfloat16 when served); norm statistics, softmax and router scores are
float32.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..framework.tensor import Parameter, Tensor
from ..generation import cache as _cache
from ..nn.gqa import CachedGQAttention, rms_norm
from ..nn.layer_base import Layer
from ..nn.layers import LayerList
from ..nn.linear_attention import normal_or_zeros
from ..parallel.moe import RoutedExperts, routing_stats

__all__ = ["ExaoneMoEConfig", "ExaoneMoEForCausalLM"]

# a full layer's prefill: 128 queries a block against 4,096 keys at a
# time (a score tensor of 0.13 GB at 64 heads; XLA:TPU's reductions over
# rows of 4,300-8,192 scores are 40 x slower than over 4,096: nn/gqa.py);
# the window layers' blocks are [block, block + W - 1]
_FULL_BLOCK, _WINDOW_BLOCK, _KEY_CHUNK = 128, 512, 4096
# a prompt's feed-forward runs over this many tokens at a time: the
# expert layer gathers top_k rows a token (at 16,384 tokens 1.6 GB in,
# 1.6 GB out and twice that in float32 on the way back)
_FFN_CHUNK = 4096


@dataclass
class ExaoneMoEConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: tuple = ("sliding_attention",) * 3 + ("full_attention",)
    sliding_window: int = 128
    rope_theta: float = 1000000.0
    first_k_dense_replace: int = 1
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_experts: int = 128
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    num_nextn_predict_layers: int = 0
    rms_norm_eps: float = 1e-5
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

    def layer_type(self, index):
        """``layer_types`` repeats with its own period past its end."""
        return self.layer_types[index % len(self.layer_types)]


class DenseSwiGLU(Layer):
    """``(silu(x Wg) * (x Wu)) Wd``; SiLU and the product in float32
    (the two wide products leave the matrix unit as ``dtype``: at 16,384
    tokens by 18,432 a float32 copy of each would be 1.2 GB)."""

    def __init__(self, hidden_size, width, initializer_range, dtype):
        super().__init__()
        for name, shape in (("w_gate", (hidden_size, width)),
                            ("w_up", (hidden_size, width)),
                            ("w_down", (width, hidden_size))):
            setattr(self, name, Parameter.from_array(
                normal_or_zeros(shape, initializer_range, dtype), name=name))

    def forward(self, x):
        gate = jnp.matmul(x, self.w_gate._array).astype(jnp.float32)
        up = jnp.matmul(x, self.w_up._array).astype(jnp.float32)
        return jnp.matmul((jax.nn.silu(gate) * up).astype(x.dtype),
                          self.w_down._array)


class ExaoneDecoderLayer(Layer):
    def __init__(self, cfg: ExaoneMoEConfig, sliding: bool, dense: bool):
        super().__init__()
        dtype = cfg.dtype
        std = cfg.initializer_range if cfg.init_weights else None
        self.eps, self.sliding, self.dense = cfg.rms_norm_eps, sliding, dense
        self.mixer = CachedGQAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, qk_norm=True,
            rope_theta=cfg.rope_theta if sliding else None,
            window=cfg.sliding_window if sliding else None,
            prefill_block=_WINDOW_BLOCK if sliding else _FULL_BLOCK,
            key_chunk=None if sliding else _KEY_CHUNK,
            norm_eps=cfg.rms_norm_eps, initializer_range=std, dtype=dtype)
        if dense:
            self.mlp = DenseSwiGLU(cfg.hidden_size, cfg.intermediate_size,
                                   std, dtype)
        else:
            self.moe = RoutedExperts(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, held=cfg.experts_held,
                shared_width=cfg.moe_intermediate_size
                * cfg.num_shared_experts,
                score=cfg.scoring_func, norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                initializer_range=std, dtype=dtype)
        ones = jnp.ones((cfg.hidden_size,), dtype)
        self.input_norm = Parameter.from_array(ones, name="input_norm")
        self.post_norm = Parameter.from_array(ones, name="post_norm")

    def forward(self, x, cache=None, mask=None, positions=None, valid=None):
        y = rms_norm(x, self.input_norm._array, self.eps)
        with jax.named_scope("attn_window" if self.sliding else "attn_full"):
            out = self.mixer(y, cache=cache, mask=mask, positions=positions)
        if cache is not None:
            out, cache = out
        x = x + out
        y = rms_norm(x, self.post_norm._array, self.eps)
        x = x + self._ffn(y, valid)
        return x if cache is None else (x, cache)

    def _ffn(self, y, valid):
        """The feed-forward, a long prompt ``_FFN_CHUNK`` tokens at a
        time (one loop body, so the peak is one chunk's)."""
        if not self.dense:
            return self.moe.in_chunks(y, valid, _FFN_CHUNK)
        b, t, h = y.shape
        if t <= _FFN_CHUNK or t % _FFN_CHUNK:
            return self.mlp(y)
        out = jax.lax.map(
            self.mlp, y.reshape(b, -1, _FFN_CHUNK, h).swapaxes(0, 1))
        return out.swapaxes(0, 1).reshape(b, t, h)


class ExaoneMoEForCausalLM(Layer):
    """Embedding slice + the mixed stack + final RMSNorm + untied head
    over the same slice."""

    def __init__(self, cfg: ExaoneMoEConfig | None = None, **kwargs):
        super().__init__()
        self.config = cfg = cfg or ExaoneMoEConfig(**kwargs)
        rows = int(cfg.vocab_held or cfg.vocab_size)
        h = cfg.hidden_size
        std = cfg.initializer_range if cfg.init_weights else None
        for name, shape in (("embed_tokens", (rows, h)),
                            ("lm_head", (h, rows))):
            setattr(self, name, Parameter.from_array(
                normal_or_zeros(shape, std, cfg.dtype), name=name))
        self.layers = LayerList([
            ExaoneDecoderLayer(
                cfg, cfg.layer_type(i) == "sliding_attention",
                i < cfg.first_k_dense_replace)
            for i in range(cfg.num_hidden_layers)])
        ones = jnp.ones((h,), cfg.dtype)
        self.norm = Parameter.from_array(ones, name="norm")
        if cfg.num_nextn_predict_layers not in (0, 1):
            from ..errors import InvalidArgumentError

            raise InvalidArgumentError(
                "one multi-token-prediction module at most, got "
                f"num_nextn_predict_layers={cfg.num_nextn_predict_layers}")
        if cfg.num_nextn_predict_layers:
            # the module: two norms, the joining projection, one
            # full-attention sparse block
            self.mtp_hidden_norm = Parameter.from_array(
                ones, name="mtp_hidden_norm")
            self.mtp_embed_norm = Parameter.from_array(
                ones, name="mtp_embed_norm")
            self.mtp_proj = Parameter.from_array(
                normal_or_zeros((2 * h, h), std, cfg.dtype), name="mtp_proj")
            self.mtp_block = ExaoneDecoderLayer(cfg, sliding=False,
                                                dense=False)
        self._stats = None

    # -- generation-engine contract ------------------------------------------

    def cache_spec(self):
        """One storage kind a layer: K/V rows for the K/V heads, as many
        as the cache is long in a full layer and ``sliding_window`` in a
        sliding one."""
        cfg = self.config
        return [_cache.kv(cfg.num_key_value_heads, cfg.head_dim,
                          window=cfg.sliding_window if layer.sliding else None)
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

    def _ids(self, input_ids):
        return input_ids._array if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)

    def _head(self, x):
        x = rms_norm(x, self.norm._array, self.config.rms_norm_eps)
        return jnp.matmul(x, self.lm_head._array,
                          preferred_element_type=jnp.float32)

    def hidden(self, input_ids, position_ids=None, attention_mask=None,
               caches=None):
        """``(x, caches, valid)``: the stack's output before the final
        norm, ``[B, T, hidden]`` (an array), the layers' new caches
        (``None`` without) and which positions of a prompt are real."""
        ids = self._ids(input_ids)
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
            out = layer(x, cache=None if caches is None else caches[i],
                        mask=mask, positions=position_ids, valid=valid)
            if caches is None:
                x = out
            else:
                x, c = out
                new_caches.append(c)
        self._stats = routing_stats(
            [layer.moe for layer in self.layers if not layer.dense])
        return x, (None if caches is None else new_caches), valid

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                caches=None):
        x, new_caches, valid = self.hidden(input_ids, position_ids,
                                           attention_mask, caches)
        if caches is not None and x.shape[1] > 1:
            # a prefill is read at its last real position only
            last = (x.shape[1] if valid is None else valid.sum(-1)) - 1
            x = jnp.take_along_axis(
                x, jnp.broadcast_to(jnp.asarray(last, jnp.int32),
                                    (x.shape[0],))[:, None, None], axis=1)
        logits = Tensor._from_array(self._head(x))
        return logits if caches is None else (logits, new_caches)

    def predict_ahead(self, input_ids):
        """The prediction module, teacher-forced over ``input_ids [B,
        T]``: ``h'_t = [RMSNorm(h_t) ; RMSNorm(E[token_{t+1}])] Wp`` for
        ``t < T - 1`` (``h`` the stack's output), one full-attention
        sparse block over ``h'``, the model's final norm and head:
        logits ``[B, T - 1, vocab_held]`` for token ``t + 2``."""
        ids = self._ids(input_ids)
        eps = self.config.rms_norm_eps
        h, _, _ = self.hidden(ids)
        joined = jnp.concatenate([
            rms_norm(h[:, :-1], self.mtp_hidden_norm._array, eps),
            rms_norm(self.embed_tokens._array[ids[:, 1:]],
                     self.mtp_embed_norm._array, eps)], axis=-1)
        x = self.mtp_block(jnp.matmul(joined, self.mtp_proj._array))
        return Tensor._from_array(self._head(x))

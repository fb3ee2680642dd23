"""Mixture-of-Experts with expert parallelism over the ep mesh axis.

Beyond-reference capability (SURVEY.md §2.3: EP/MoE absent in the
reference). GShard/Switch-style top-k routing implemented as dense
einsum dispatch/combine: expert weights carry a leading [num_experts]
axis sharded on ep, tokens are dispatched with a one-hot combine tensor,
and GSPMD lowers the dispatch einsums to all-to-alls over ICI.

The dense-dispatch formulation (einsum with a [G, S, E, C] combine tensor
instead of gather/scatter) is the canonical TPU design: static shapes,
MXU-friendly, no sorting kernels.

:class:`RoutedExperts` is the serving-side layer: top-k routing over all
the experts of the model by a layer that is told which of them it holds
(one chip's share of an expert-parallel group), dropless, grouped matrix
products over the experts held, plus a shared expert.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import ops
from ..framework import autograd
from ..framework.tensor import Parameter, Tensor
from ..nn import functional as F
from ..nn.layer_base import Layer
from ..nn.layers import Linear
from ..ops.pallas._platform import can_emit_mosaic, prefetch_pallas
# the kernel's shape test keeps the name by which
# benchmark/tests/test_moe_tile_rows_reader.py opens it
from ..ops.pallas.grouped_experts import (
    grouped_experts, grouped_experts_supported as grouped_relu2_supported)
from .mesh import get_mesh
from .sharding import ShardingRules, with_sharding_constraint

__all__ = ["MoELayer", "SwitchFFN", "RoutedExperts", "routing_stats"]


class SwitchFFN(Layer):
    """Top-1 (Switch) routed expert FFN.

    x: [B, L, H] -> [B, L, H]; E experts, each a 2-layer MLP with
    intermediate dim F. Expert params are [E, ...] leaves sharded on ep.
    """

    def __init__(self, hidden_size, intermediate_size, num_experts,
                 capacity_factor=1.25, activation="relu",
                 router_noise=1e-2):
        super().__init__()
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.activation = activation
        self.router_noise = router_noise
        self.router = Linear(hidden_size, num_experts)
        # expert weights: [E, H, F], [E, F], [E, F, H], [E, H]
        bound1 = float(np.sqrt(6.0 / (hidden_size + intermediate_size)))
        from ..framework.random import split_key

        self.expert_w1 = Parameter.from_array(
            jax.random.uniform(
                split_key(), (num_experts, hidden_size, intermediate_size),
                jnp.float32, -bound1, bound1,
            ),
            name="expert_w1",
        )
        self.expert_b1 = Parameter.from_array(
            jnp.zeros((num_experts, intermediate_size)), name="expert_b1"
        )
        self.expert_w2 = Parameter.from_array(
            jax.random.uniform(
                split_key(), (num_experts, intermediate_size, hidden_size),
                jnp.float32, -bound1, bound1,
            ),
            name="expert_w2",
        )
        self.expert_b2 = Parameter.from_array(
            jnp.zeros((num_experts, hidden_size)), name="expert_b2"
        )
        self._last_aux_loss = None

    @staticmethod
    def sharding_rules():
        return ShardingRules([
            (r"expert_(w|b)\d$", P("ep")),
        ])

    def forward(self, x):
        logits = self.router(x)  # [B, L, E]
        fn = self._dispatch_fn()
        param_tensors = [self.expert_w1, self.expert_b1,
                         self.expert_w2, self.expert_b2]
        mesh = get_mesh()
        if mesh is not None and int(mesh.shape.get("ep", 1)) > 1:
            # eager edge: settle expert params onto the ep axis once; they
            # stay resident across calls
            from jax.sharding import NamedSharding

            for p in param_tensors:
                if not isinstance(p._array, jax.core.Tracer):
                    p._array = jax.device_put(
                        p._array, NamedSharding(mesh, P("ep"))
                    )

            def repl(t):
                if isinstance(t, Tensor) and not isinstance(
                    t._array, jax.core.Tracer
                ):
                    return Tensor._from_array(
                        jax.device_put(t._array, NamedSharding(mesh, P())),
                        stop_gradient=t.stop_gradient,
                    )
                return t

            x, logits = repl(x), repl(logits)
        out, aux = autograd.apply_op(
            "moe_switch_ffn", jax.jit(fn),
            [x, logits, *param_tensors],
            {},
        )
        self._last_aux_loss = aux
        return out

    def aux_loss(self):
        """Load-balancing auxiliary loss of the last forward (Switch
        Transformer eq. 4); add `model.moe.aux_loss()` to the train loss."""
        return self._last_aux_loss

    def _dispatch_fn(self):
        E = self.num_experts
        cap_f = self.capacity_factor
        act = getattr(jax.nn, self.activation)
        training = self.training
        noise = self.router_noise

        def pure(x, logits, w1, b1, w2, b2):
            b, l, h = x.shape
            s = b * l
            cap = max(1, int(cap_f * s / E))
            xt = x.reshape(s, h)
            lg = logits.reshape(s, E).astype(jnp.float32)
            # NOTE: router jitter (Switch §2.2) is intentionally omitted —
            # stateful RNG inside this pure fn would bake a constant under
            # jit; thread it via the train-step rng when needed.
            probs = jax.nn.softmax(lg, axis=-1)
            gate = jnp.max(probs, axis=-1)              # [S]
            expert = jnp.argmax(probs, axis=-1)         # [S]
            # position of each token within its expert's queue
            onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)   # [S, E]
            # rank within the chosen expert's queue: mask the cumsum to the
            # chosen column *before* the -1 (subtracting inside the sum
            # would shift by E, aliasing the first E tokens into slot 0)
            pos_in_expert = (
                jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
            )  # [S]
            keep = pos_in_expert < cap
            gate = gate * keep

            # dispatch tensor [S, E, C]
            disp = (
                jax.nn.one_hot(expert, E, dtype=x.dtype)[:, :, None]
                * jax.nn.one_hot(
                    jnp.clip(pos_in_expert, 0, cap - 1), cap, dtype=x.dtype
                )[:, None, :]
                * keep[:, None, None]
            )
            # expert inputs [E, C, H]
            ex_in = jnp.einsum("sec,sh->ech", disp, xt)
            ex_in = with_sharding_constraint(ex_in, P("ep", None, None))
            hmid = act(
                jnp.einsum("ech,ehf->ecf", ex_in, w1) + b1[:, None, :]
            )
            ex_out = jnp.einsum("ecf,efh->ech", hmid, w2) + b2[:, None, :]
            ex_out = with_sharding_constraint(ex_out, P("ep", None, None))
            combine = disp * gate[:, None, None]        # [S, E, C]
            yt = jnp.einsum("sec,ech->sh", combine, ex_out)

            # load-balance aux loss: E * sum_e f_e * p_e
            frac_tokens = jnp.mean(
                jax.nn.one_hot(expert, E, dtype=jnp.float32), axis=0
            )
            frac_probs = jnp.mean(probs, axis=0)
            aux = E * jnp.sum(frac_tokens * frac_probs)
            return yt.reshape(b, l, h), aux

        return pure


MoELayer = SwitchFFN  # alias


class RoutedExperts(Layer):
    """Top-k routed experts plus a shared expert, as ONE member of an
    expert-parallel group computes them.

    The router scores ALL ``num_experts`` (``score``: ``sigmoid`` or
    ``softmax``), the ``top_k`` largest are chosen, and their weights are
    renormalised over the chosen (``norm_topk_prob``) and scaled by
    ``routed_scaling_factor``. Of the experts this layer holds
    ``held = (first, count)``: its weights are stacked leaves
    ``[count, ...]``. It computes its own experts' part of the result::

        y_here = sum_{i in top_k, i held} w_i E_i(x) + E_shared(x)

    with ``w_i`` normalised over all the chosen, held or not. An expert
    is, by ``activation``, a gated MLP or a plain one::

        "swiglu":  E(x) = W_down(SiLU(W_gate x) * W_up x)
        "relu2":   E(x) = W_down(relu(W_up x)^2)

    and the shared expert is of the same form at ``shared_width``. With
    ``latent_size`` the routed experts work in a narrower width than the
    residual stream: the token's own chip projects ``l = W_ldown x``
    once (``hidden -> latent_size``) before the pairs are sorted, every
    routed expert maps ``latent_size -> expert_width -> latent_size``,
    and the weighted sum of the held experts' results is projected back
    once, ``W_lup (sum_i w_i E_i(l))``; the router and the shared expert
    read the full-width ``x``. What an expert-parallel group exchanges
    is then ``latent_size`` wide. With every expert held that is the
    whole layer; the shares of a group add up to it, the shared expert
    counted once (tests/test_routed_experts.py). No token is dropped and
    every shape is static: the token-expert pairs are sorted by expert,
    those that fall elsewhere last, and the products run as grouped
    matrix products over the experts held (``jax.lax.ragged_dot``:
    XLA:TPU's grouped kernel visits only the row tiles of groups that
    have rows, so a decode step reads the weights of the experts that
    were hit and no others). The same path serves a prompt of thousands
    of tokens and a decode step of a few dozen. Where :meth:`takes_kernel`
    says so (on a TPU, either activation), the three or two products of
    a layer are one Mosaic kernel over the same sorted rows instead, a
    hit expert's matrices read once and the hidden rows in VMEM
    (``ops/pallas/grouped_experts.py``).

    The leaves, by name (a caller that hands weights over does so by
    these names), with ``w`` = ``latent_size or hidden_size``:
    ``router [hidden, num_experts + zero_experts]`` always;
    ``select_bias [num_experts + zero_experts]`` with ``selection_bias``;
    ``w_up [count, w, expert_width]`` and ``w_down [count, expert_width,
    w]`` always, ``w_gate [count, w, expert_width]`` with ``"swiglu"``
    only; ``latent_down [hidden, latent_size]`` and ``latent_up
    [latent_size, hidden]`` with ``latent_size`` only; with
    ``shared_width``: ``shared_up [hidden, shared_width]`` and
    ``shared_down [shared_width, hidden]``, and ``shared_gate [hidden,
    shared_width]`` with ``"swiglu"`` only.

    ``zero_experts``: the router scores that many outputs more, experts
    ``num_experts ..`` that have no weights: a chosen one returns the
    token itself, so its term is ``w_i x``. It is the token's own chip
    that adds it (nothing is dispatched: the pairs sort with those of
    other chips' experts and take no row of the grouped products), once
    a group: the shares of a group add up to the whole layer with that
    term counted once. ``selection_bias``: a leaf ``select_bias`` as wide
    as the router is added to the scores for the CHOICE of the ``top_k``
    and not to the weights, which stay the chosen experts' scores (the
    load-balancing bias of the families that train without an auxiliary
    loss; zeros until something sets it).

    ``forward`` takes and returns arrays ``[..., hidden]``; after it,
    :attr:`last_load` holds, per held expert, how many pairs it was given
    and :attr:`last_zero` how many pairs chose a zero expert (traced
    values inside a trace: the caller's program may return them);
    :attr:`last_tile_rows` the rows the kernel's row tiles multiplied
    for those pairs, ``None`` where ``ragged_dot`` ran.
    """

    def __init__(self, hidden_size, expert_width, num_experts, top_k,
                 held=None, shared_width=0, score="sigmoid",
                 norm_topk_prob=True, routed_scaling_factor=1.0,
                 zero_experts=0, selection_bias=False,
                 activation="swiglu", latent_size=None,
                 initializer_range=0.02, dtype="float32"):
        super().__init__()
        from ..errors import InvalidArgumentError
        from ..nn.linear_attention import normal_or_zeros

        first, count = (0, num_experts) if held is None else map(int, held)
        if not 0 <= first < first + count <= num_experts \
                or not 1 <= top_k <= num_experts:
            raise InvalidArgumentError(
                f"held=({first}, {count}) / top_k={top_k} do not fit "
                f"{num_experts} experts")
        if score not in ("sigmoid", "softmax"):
            raise InvalidArgumentError(f"unknown router score {score!r}")
        if activation not in ("swiglu", "relu2"):
            raise InvalidArgumentError(
                f"unknown expert activation {activation!r}")
        self.gated = activation == "swiglu"
        self.latent_size = None if latent_size is None else int(latent_size)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.first, self.count = first, count
        self.score, self.norm_topk_prob = score, bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        h, f, std = int(hidden_size), int(expert_width), initializer_range

        def param(name, shape):
            setattr(self, name, Parameter.from_array(
                normal_or_zeros(shape, std, dtype), name=name))

        self.zero_experts = int(zero_experts)
        param("router", (h, self.num_experts + self.zero_experts))
        if selection_bias:
            self.select_bias = Parameter.from_array(
                jnp.zeros((self.num_experts + self.zero_experts,),
                          jnp.float32), name="select_bias")
        else:
            self.select_bias = None
        width = self.latent_size or h
        if self.latent_size:
            param("latent_down", (h, width))
            param("latent_up", (width, h))
        if self.gated:
            param("w_gate", (count, width, f))
        param("w_up", (count, width, f))
        param("w_down", (count, f, width))
        self.shared_width = int(shared_width)
        if self.shared_width:
            if self.gated:
                param("shared_gate", (h, self.shared_width))
            param("shared_up", (h, self.shared_width))
            param("shared_down", (self.shared_width, h))
        self.last_load = self.last_zero = self.last_tile_rows = None
        prefetch_pallas()  # the kernel's imports, while the weights are made

    def route(self, x):
        """``(idx [T, k], w [T, k])``: the chosen experts of each token
        and their weights, in float32 from float32 scores."""
        logits = jnp.matmul(x.astype(jnp.float32),
                            self.router._array.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        scores = (jax.nn.sigmoid(logits) if self.score == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        if self.select_bias is None:
            w, idx = jax.lax.top_k(scores, self.top_k)
        else:
            _, idx = jax.lax.top_k(
                scores + self.select_bias._array.astype(jnp.float32),
                self.top_k)
            w = jnp.take_along_axis(scores, idx, axis=-1)
        if self.norm_topk_prob:
            w = w / w.sum(-1, keepdims=True)
        return idx, w * self.routed_scaling_factor

    def takes_kernel(self, xs):
        """Whether the grouped products over the sorted rows ``xs`` run
        as ONE Mosaic kernel here and now (``ops/pallas/grouped_experts``),
        by what the call can see: a TPU, no multi-device mesh in scope,
        weights of the rows' dtype, shapes the kernel supports (whole
        lanes, whole sublane packs of rows, a block of the expert's
        matrices that fits VMEM), gated experts or not. Everywhere else
        they are ``jax.lax.ragged_dot`` calls."""
        up, down = self.w_up._array, self.w_down._array
        gate = (self.w_gate._array.shape,) if self.gated else ()
        return (can_emit_mosaic() and up.dtype == xs.dtype
                and grouped_relu2_supported(xs.shape, up.shape, down.shape,
                                            xs.dtype, *gate))

    def in_chunks(self, x, valid=None, chunk=1024):
        """:meth:`forward` of ``x [B, T, hidden]``, a long sequence
        ``chunk`` tokens at a time (one loop body, so the sorted pairs
        and their hidden rows are one chunk's at the peak);
        :attr:`last_load`, :attr:`last_zero` and :attr:`last_tile_rows`
        are then the chunks' sums. A sequence of at most one chunk, or
        of no whole number of them, goes through whole."""
        b, t, h = x.shape
        if t <= chunk or t % chunk:
            return self(x, valid=valid)
        if valid is None:
            valid = jnp.ones((b, t), bool)

        def one(c):
            y = self(c[0], valid=c[1])
            return y, self.last_load, (
                self.last_zero if self.zero_experts
                else jnp.zeros((), jnp.int32)), self.last_tile_rows

        out, loads, zeros, tile_rows = jax.lax.map(
            one, (x.reshape(b, -1, chunk, h).swapaxes(0, 1),
                  valid.reshape(b, -1, chunk).swapaxes(0, 1)))
        self.last_load = loads.sum(0)
        if self.zero_experts:
            self.last_zero = zeros.sum(0)
        if tile_rows is not None:
            self.last_tile_rows = tile_rows.sum(0)
        return out.swapaxes(0, 1).reshape(b, t, h)

    def forward(self, x, valid=None):
        """``x [..., hidden]``; ``valid [...]`` bool marks the tokens
        that count in :attr:`last_load` (padding is computed but not
        counted)."""
        shape = x.shape
        x = x.reshape(-1, shape[-1])
        t, k, n = x.shape[0], self.top_k, self.count
        with jax.named_scope("moe_experts"):
            idx, w = self.route(x)
            local = idx - self.first
            here = (local >= 0) & (local < n)
            # pairs in expert order, those of other chips' experts last
            group = jnp.where(here, local, n).reshape(-1).astype(jnp.int32)
            order = jnp.argsort(group, stable=True)
            sizes = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)[:n]
            if self.latent_size:
                with jax.named_scope("moe_latent"):
                    xs = jnp.matmul(x, self.latent_down._array)[order // k]
            else:
                xs = x[order // k]
            self.last_tile_rows = None
            if self.takes_kernel(xs):
                # the products in one kernel, the hidden rows in VMEM
                out, self.last_tile_rows = grouped_experts(
                    xs, self.w_up._array, self.w_down._array, sizes,
                    self.w_gate._array if self.gated else None)
            else:
                if self.gated:
                    gate = jax.lax.ragged_dot(xs, self.w_gate._array, sizes)
                    up = jax.lax.ragged_dot(xs, self.w_up._array, sizes)
                    hid = jax.nn.silu(gate.astype(jnp.float32)) \
                        * up.astype(jnp.float32)
                else:
                    hid = jnp.square(jax.nn.relu(jax.lax.ragged_dot(
                        xs, self.w_up._array, sizes).astype(jnp.float32)))
                out = jax.lax.ragged_dot(hid.astype(x.dtype),
                                         self.w_down._array, sizes)
            # back to (token, choice) order; rows past the last group are
            # whatever the kernel left there and are masked, not scaled
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(t * k, dtype=order.dtype))
            pair = out[back].reshape(t, k, -1).astype(jnp.float32)
            y = jnp.where(here[..., None], pair * w[..., None], 0.0).sum(1)
            if self.latent_size:
                with jax.named_scope("moe_latent"):
                    y = jnp.matmul(y.astype(x.dtype), self.latent_up._array,
                                   preferred_element_type=jnp.float32)
            if valid is None:
                self.last_load = sizes
            else:
                counted = here & valid.reshape(-1)[:, None]
                self.last_load = jnp.zeros((n + 1,), jnp.int32).at[
                    jnp.where(counted, local, n).reshape(-1)].add(1)[:n]
            if self.zero_experts:
                with jax.named_scope("moe_zero"):
                    zero = idx >= self.num_experts
                    y = y + jnp.where(zero, w, 0.0).sum(
                        -1, keepdims=True) * x.astype(jnp.float32)
                    if valid is not None:
                        zero = zero & valid.reshape(-1)[:, None]
                    self.last_zero = zero.sum().astype(jnp.int32)
            if self.shared_width:
                if self.gated:
                    hid = jax.nn.silu(jnp.matmul(
                        x, self.shared_gate._array,
                        preferred_element_type=jnp.float32)) * jnp.matmul(
                        x, self.shared_up._array,
                        preferred_element_type=jnp.float32)
                else:
                    hid = jnp.square(jax.nn.relu(jnp.matmul(
                        x, self.shared_up._array,
                        preferred_element_type=jnp.float32)))
                y = y + jnp.matmul(hid.astype(x.dtype),
                                   self.shared_down._array,
                                   preferred_element_type=jnp.float32)
            return y.astype(x.dtype).reshape(shape)


def routing_stats(layers):
    """What the last forward of each of the :class:`RoutedExperts`
    ``layers`` routed here, a value a layer: token-expert pairs that
    landed on held experts (``pairs [L]``), distinct held experts that
    got at least one (``hit [L]``), per held expert its pairs over all
    layers (``load [held]``); where the layers have zero-compute
    experts, the pairs that chose one (``zero_pairs [L]``); where the
    experts' kernel ran, the rows its row tiles multiplied for those
    pairs (``tile_rows [L]``). Inside a trace these are traced values
    of that trace."""
    loads = jnp.stack([m.last_load for m in layers])
    stats = {"pairs": loads.sum(1), "hit": (loads > 0).sum(1),
             "load": loads.sum(0)}
    for name, last in (("zero_pairs", "last_zero"),
                       ("tile_rows", "last_tile_rows")):
        if getattr(layers[0], last) is not None:
            stats[name] = jnp.stack([getattr(m, last) for m in layers])
    return stats

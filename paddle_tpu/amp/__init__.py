"""Automatic mixed precision.

Reference parity:
- dygraph autocast: fluid/dygraph/amp/auto_cast.py + C++ hook
  imperative/amp_auto_cast.cc (white/black op lists, cast-at-dispatch)
- loss scaling: fluid/dygraph/amp/loss_scaler.py:27 (AmpScaler) over
  operators/amp/amp_check_finite_and_scale_op
- static decorator: fluid/contrib/mixed_precision/decorator.py + fp16_lists.py

TPU-native: the autocast dtype is bfloat16 — same exponent range as fp32,
so loss scaling is numerically unnecessary (GradScaler defaults to
enabled=False on bf16 but keeps the fp16 API for parity). The cast hook
runs at eager op dispatch (framework/autograd.py _amp_hook) and therefore
also inside functionalized/jitted train steps, where XLA folds the casts
into fused matmul epilogues.
"""
from __future__ import annotations

import contextlib
import threading

import jax.numpy as jnp
import numpy as np

from ..framework import autograd
from ..framework.tensor import Tensor

__all__ = ["auto_cast", "amp_guard", "GradScaler", "AmpScaler", "decorate",
           "WHITE_LIST", "BLACK_LIST"]

# fp16_lists.py white list: matmul-class ops that benefit from MXU dtype.
# "linear" is the workhorse: every nn.Linear dispatches it, and leaving
# it off the list silently ran all transformer MLPs in f32 (225 of 300
# BERT-step dots; tests/test_amp.py pins the dot dtypes of a compiled
# Linear train step).
WHITE_LIST = {
    "matmul", "mul", "bmm", "addmm", "einsum", "linear",
    "conv1d", "conv2d", "conv2d_transpose", "conv3d",
}
# fp16_lists.py black list: numerically sensitive reductions/normalizations.
# TPU divergence from the reference's fp16 lists: batch_norm and layer_norm
# are NOT black-listed — their kernels internally accumulate statistics in
# f32 while carrying the activation dtype (ops/kernels.py), which is the
# TPU-native bf16 recipe. Black-listing them would round-trip every
# activation through an f32 HBM buffer and make conv nets memory-bound
# (measured 2x step time on ResNet-50, see COVERAGE.md).
BLACK_LIST = {
    "softmax_with_cross_entropy", "cross_entropy", "softmax", "log_softmax",
    "group_norm", "instance_norm",
    "exp", "log", "log2", "log10", "log1p", "logsumexp",
    "reduce_mean", "reduce_sum", "mean", "sum", "cumsum",
    "sigmoid", "erf", "pow", "rsqrt", "sqrt", "square",
}

_state = threading.local()


def _enabled():
    return getattr(_state, "amp", None)


def _hook(op_type, arrays):
    """Cast arrays at op dispatch per the active autocast scope."""
    scope = _enabled()
    if scope is None:
        return arrays
    dtype, white, black = scope
    if op_type in white:
        return [
            a.astype(dtype)
            if hasattr(a, "dtype") and a.dtype == jnp.float32
            else a
            for a in arrays
        ]
    if op_type in black:
        return [
            a.astype(jnp.float32)
            if hasattr(a, "dtype") and a.dtype == jnp.dtype(dtype)
            else a
            for a in arrays
        ]
    return arrays


autograd.set_amp_hook(_hook)


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16"):
    """paddle.amp.auto_cast — scope in which white-listed ops run in
    bf16/fp16."""
    if not enable:
        yield
        return
    white = set(WHITE_LIST) | set(custom_white_list or ())
    black = (set(BLACK_LIST) | set(custom_black_list or ())) - set(
        custom_white_list or ()
    )
    if level == "O2":
        # O2: everything except the black list
        white = None  # sentinel: cast-all handled below
    prev = _enabled()
    jdtype = jnp.dtype(dtype)
    if white is None:
        scope = (jdtype, _CastAll(black), black)
    else:
        scope = (jdtype, white, black)
    _state.amp = scope
    try:
        yield
    finally:
        _state.amp = prev


class _CastAll:
    """O2 'white list': every op except the black list."""

    def __init__(self, black):
        self.black = black

    def __contains__(self, op):
        return op not in self.black


amp_guard = auto_cast  # fluid.dygraph.amp.amp_guard alias


class GradScaler:
    """Dynamic loss scaler (AmpScaler, fluid/dygraph/amp/loss_scaler.py:27).

    On bf16 (TPU default) scaling is a no-op unless explicitly enabled;
    the fp16 semantics (scale, unscale, inf check, dynamic adjustment)
    are implemented exactly for API and numeric parity.
    """

    def __init__(self, enable=True, init_loss_scaling=32768.0,
                 incr_ratio=2.0, decr_ratio=0.5, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling)
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every_n_steps = incr_every_n_steps
        self._decr_every_n_nan_or_inf = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False

    def scale(self, var):
        if not self._enable:
            return var
        from .. import ops

        return ops.scale(var, scale=self._scale)

    def unscale_(self, optimizer):
        """Divide grads by the scale; record found_inf
        (amp_check_finite_and_scale semantics)."""
        if not self._enable:
            self._found_inf = False
            return
        inv = 1.0 / self._scale
        found = False
        for p in optimizer._parameter_list:
            if p.grad is None:
                continue
            g = p.grad._array * inv
            finite = bool(jnp.all(jnp.isfinite(g)))
            found = found or not finite
            p.grad = Tensor._from_array(g)
        self._found_inf = found

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            return
        self.unscale_(optimizer)
        if not self._found_inf:
            optimizer.step()
        self.update()

    def minimize(self, optimizer, scaled_loss):
        scaled_loss.backward()
        self.step(optimizer)
        optimizer.clear_grad()

    def update(self):
        if not (self._enable and self._dynamic):
            return
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every_n_nan_or_inf:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
        else:
            self._good_steps += 1
            self._bad_steps = 0
            if self._good_steps >= self._incr_every_n_steps:
                self._scale *= self._incr_ratio
                self._good_steps = 0

    def is_enable(self):
        return self._enable

    def get_loss_scaling(self):
        return self._scale

    def set_loss_scaling(self, v):
        self._scale = float(v)

    def state_dict(self):
        return {
            "scale": self._scale,
            "incr_ratio": self._incr_ratio,
            "decr_ratio": self._decr_ratio,
            "incr_every_n_steps": self._incr_every_n_steps,
            "decr_every_n_nan_or_inf": self._decr_every_n_nan_or_inf,
            "good_steps": self._good_steps,
            "bad_steps": self._bad_steps,
        }

    def load_state_dict(self, state):
        self._scale = state.get("scale", self._scale)
        self._good_steps = state.get("good_steps", 0)
        self._bad_steps = state.get("bad_steps", 0)


AmpScaler = GradScaler


def decorate(models=None, optimizers=None, level="O2", dtype="bfloat16",
             master_weight=None, save_dtype=None):
    """paddle.amp.decorate — O2: cast model parameters to the AMP dtype.

    Master weights: the functionalized optimizer keeps its accumulators in
    the original param dtype; with master_weight=True params stay fp32 and
    only compute autocasts (equivalent to O1 + cast-all)."""
    if level not in ("O1", "O2"):
        raise ValueError("level must be O1 or O2")
    if level == "O2" and models is not None and not master_weight:
        target = jnp.dtype(dtype)
        model_list = models if isinstance(models, (list, tuple)) else [models]
        for m in model_list:
            for _, p in m.named_parameters():
                if p._array.dtype == jnp.float32:
                    p._array = p._array.astype(target)
    if optimizers is None:
        return models
    return models, optimizers

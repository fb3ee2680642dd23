"""Optimizers.

Reference parity: python/paddle/fluid/optimizer.py:56 (Optimizer base,
minimize) + operators/optimizers/*.cc update kernels (sgd, momentum, adam,
adamax, adagrad, adadelta, rmsprop, lamb). TPU-native: each update rule is a
pure jnp function over (param, grad, accumulators) — applied eagerly per
tensor, or traced into the one fused XLA module when the train step is
functionalized (framework/jit.py). Optimizer state is exposed as arrays so
jitted steps can thread it as data.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..framework.autograd import no_grad
from ..framework.tensor import Tensor
from . import lr as lr  # noqa: F401
from .lr import LRScheduler

__all__ = [
    "Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adagrad", "Adadelta",
    "RMSProp", "Adamax", "Lamb", "lr",
    "ClipGradByValue", "ClipGradByNorm", "ClipGradByGlobalNorm",
    "L1Decay", "L2Decay",
]


# -- gradient clipping (fluid/clip.py) --------------------------------------


class ClipGradBase:
    def __call__(self, params_grads):
        raise NotImplementedError


class ClipGradByValue(ClipGradBase):
    def __init__(self, max, min=None):
        self.max = float(max)
        self.min = float(min) if min is not None else -self.max

    def __call__(self, params_grads):
        return [(p, jnp.clip(g, self.min, self.max)) for p, g in params_grads]


class ClipGradByNorm(ClipGradBase):
    def __init__(self, clip_norm):
        self.clip_norm = float(clip_norm)

    def __call__(self, params_grads):
        out = []
        for p, g in params_grads:
            norm = jnp.sqrt(jnp.sum(g * g))
            factor = jnp.where(norm > self.clip_norm, self.clip_norm / jnp.maximum(norm, 1e-12), 1.0)
            out.append((p, g * factor))
        return out


class ClipGradByGlobalNorm(ClipGradBase):
    def __init__(self, clip_norm):
        self.clip_norm = float(clip_norm)

    def __call__(self, params_grads):
        if not params_grads:
            return params_grads
        global_sq = sum(jnp.sum(g.astype(jnp.float32) ** 2) for _, g in params_grads)
        gnorm = jnp.sqrt(global_sq)
        factor = jnp.where(
            gnorm > self.clip_norm, self.clip_norm / jnp.maximum(gnorm, 1e-12), 1.0
        )
        return [(p, g * factor.astype(g.dtype)) for p, g in params_grads]


# -- regularizers (fluid/regularizer.py) ------------------------------------


class L2Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)

    def __call__(self, param, grad):
        return grad + self.coeff * param


class L1Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)

    def __call__(self, param, grad):
        return grad + self.coeff * jnp.sign(param)


def _resolve_weight_decay(weight_decay):
    if weight_decay is None:
        return None
    if isinstance(weight_decay, (int, float)):
        return L2Decay(float(weight_decay))
    return weight_decay


# -- base -------------------------------------------------------------------


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        if parameters is None:
            raise ValueError("parameters must be provided (dygraph mode)")
        self._parameter_list = list(parameters)
        self._learning_rate = learning_rate
        self._weight_decay = _resolve_weight_decay(weight_decay)
        self._grad_clip = grad_clip
        # accumulators: name -> list of jnp arrays aligned with parameters
        self._accumulators: dict[str, list] = {}
        self._global_step = 0
        # set by framework/jit.py to thread a traced lr through a compiled
        # step instead of baking a python float into the XLA module
        self._lr_override = None

    # accumulator helpers ---------------------------------------------------
    def _ensure_accumulator(self, name, like_fn=None):
        if name not in self._accumulators:
            self._accumulators[name] = [
                (like_fn(p) if like_fn else jnp.zeros(p._array.shape, p._array.dtype))
                for p in self._parameter_list
            ]
        return self._accumulators[name]

    def get_lr(self):
        if self._lr_override is not None:
            return self._lr_override
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    def clear_grad(self):
        for p in self._parameter_list:
            p.grad = None

    clear_gradients = clear_grad

    def _fused_decay_coeff(self):
        """L2-decay coefficient an optimizer's fused update kernel will
        fold in itself (``None``: decay is pre-applied to the grad here
        in ``step()``, the historical path). Only optimizers with a
        fused pallas update override this (Momentum)."""
        return None

    # main entry points -----------------------------------------------------
    @no_grad()
    def step(self):
        # when the update kernel fuses L2 decay (Momentum on the fused
        # path), skip the separate decay pass here — but only for params
        # without a per-param regularizer (those keep their own)
        fused_wd = self._fused_decay_coeff()
        params_grads = []
        for i, p in enumerate(self._parameter_list):
            if p.grad is None or not getattr(p, "trainable", True):
                continue
            g = p.grad._array.astype(p._array.dtype)
            if self._weight_decay is not None and getattr(p, "regularizer", None) is None \
                    and not isinstance(self, AdamW):
                if fused_wd is None:
                    g = self._weight_decay(p._array, g)
            elif getattr(p, "regularizer", None) is not None:
                g = p.regularizer(p._array, g)
            params_grads.append(((i, p), g))
        if self._grad_clip is not None:
            clipped = self._grad_clip([(ip, g) for ip, g in params_grads])
            params_grads = clipped
        lr_value = self.get_lr()
        self._global_step += 1
        for (i, p), g in params_grads:
            new_param = self._apply_one(i, p._array, g, lr_value)
            # keep the param dtype stable: scalar math (e.g. beta**t under
            # x64) must not silently upcast master weights
            if new_param.dtype != p._array.dtype:
                new_param = new_param.astype(p._array.dtype)
            p._array = new_param

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        loss.backward()
        self.step()
        return None, None

    def _apply_one(self, index, param, grad, lr):
        raise NotImplementedError

    # state dict ------------------------------------------------------------
    def state_dict(self):
        out = {"global_step": self._global_step}
        for name, accs in self._accumulators.items():
            for i, a in enumerate(accs):
                out[f"{name}_{i}"] = np.asarray(a)
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        return out

    def set_state_dict(self, state):
        self._global_step = int(state.get("global_step", 0))
        names = {k.rsplit("_", 1)[0] for k in state if k not in ("global_step", "LR_Scheduler")}
        for name in names:
            accs = []
            i = 0
            while f"{name}_{i}" in state:
                accs.append(jnp.asarray(state[f"{name}_{i}"]))
                i += 1
            if accs:
                self._accumulators[name] = accs
        if "LR_Scheduler" in state and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state["LR_Scheduler"])


# -- concrete optimizers ----------------------------------------------------


class SGD(Optimizer):
    """operators/optimizers/sgd_op.cc"""

    def _apply_one(self, index, param, grad, lr):
        return param - lr * grad


class Momentum(Optimizer):
    """operators/optimizers/momentum_op.cc (+ use_nesterov).

    Behind ``FLAGS_use_fused_optimizer`` the update goes through
    ``ops/pallas/optimizer_update.py``: on TPU a parameter whose
    ``[rows, 128]`` view is free (vectors, matrices, pointwise conv
    weights) takes the fused momentum/weight-decay kernel, one VMEM
    pass with param and velocity updated in place; a weight with a
    spatial extent, and every parameter elsewhere, takes the jnp
    fallback, the identical expression (bit-compatible), so eager and
    compiled steps agree everywhere.
    """

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _fused_decay_coeff(self):
        from ..flags import flag

        # decay folds into the kernel only when it is a plain L2Decay
        # and no grad clip exists (clipping must see the decayed grad —
        # deferring decay past the clip would change numerics)
        if (not flag("use_fused_optimizer") or self._grad_clip is not None
                or type(self._weight_decay) is not L2Decay
                or not self._weight_decay.coeff):
            return None
        return self._weight_decay.coeff

    def _apply_one(self, index, param, grad, lr):
        from ..flags import flag

        vel = self._ensure_accumulator("velocity")
        if flag("use_fused_optimizer"):
            from ..ops.pallas import fused_momentum_update

            wd = self._fused_decay_coeff() or 0.0
            if wd and getattr(self._parameter_list[index], "regularizer",
                              None) is not None:
                wd = 0.0  # per-param regularizer already applied in step()
            new_p, vel[index] = fused_momentum_update(
                param, grad, vel[index], lr, momentum=self._momentum,
                weight_decay=wd, use_nesterov=self._use_nesterov)
            return new_p
        v = self._momentum * vel[index] + grad
        vel[index] = v
        if self._use_nesterov:
            return param - lr * (grad + self._momentum * v)
        return param - lr * v


class Adam(Optimizer):
    """operators/optimizers/adam_op.cc"""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, lazy_mode=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _apply_one(self, index, param, grad, lr):
        m = self._ensure_accumulator("moment1")
        v = self._ensure_accumulator("moment2")
        t = self._global_step
        m[index] = self._beta1 * m[index] + (1 - self._beta1) * grad
        v[index] = self._beta2 * v[index] + (1 - self._beta2) * grad * grad
        mhat = m[index] / (1 - self._beta1**t)
        vhat = v[index] / (1 - self._beta2**t)
        return param - lr * mhat / (jnp.sqrt(vhat) + self._epsilon)


class AdamW(Adam):
    """Decoupled weight decay (reference: fluid AdamW via optimizer.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.01, grad_clip=None, name=None,
                 apply_decay_param_fun=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, name=name)
        self._wd_coeff = float(weight_decay) if isinstance(weight_decay, (int, float)) \
            else getattr(weight_decay, "coeff", 0.0)
        self._apply_decay_param_fun = apply_decay_param_fun

    def _apply_one(self, index, param, grad, lr):
        p = self._parameter_list[index]
        decay = True
        if self._apply_decay_param_fun is not None:
            decay = self._apply_decay_param_fun(p.name)
        new_param = super()._apply_one(index, param, grad, lr)
        if decay and self._wd_coeff:
            new_param = new_param - lr * self._wd_coeff * param
        return new_param


class Adagrad(Optimizer):
    """operators/optimizers/adagrad_op.cc"""

    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _apply_one(self, index, param, grad, lr):
        acc = self._ensure_accumulator(
            "moment", lambda p: jnp.full(p._array.shape, self._init_acc, p._array.dtype))
        acc[index] = acc[index] + grad * grad
        return param - lr * grad / (jnp.sqrt(acc[index]) + self._epsilon)


class Adadelta(Optimizer):
    """operators/optimizers/adadelta_op.cc"""

    def __init__(self, learning_rate=1.0, epsilon=1e-6, rho=0.95, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon, self._rho = epsilon, rho

    def _apply_one(self, index, param, grad, lr):
        avg_sq = self._ensure_accumulator("avg_squared_grad")
        avg_up = self._ensure_accumulator("avg_squared_update")
        avg_sq[index] = self._rho * avg_sq[index] + (1 - self._rho) * grad * grad
        update = -jnp.sqrt((avg_up[index] + self._epsilon) / (avg_sq[index] + self._epsilon)) * grad
        avg_up[index] = self._rho * avg_up[index] + (1 - self._rho) * update * update
        return param + lr * update


class RMSProp(Optimizer):
    """operators/optimizers/rmsprop_op.cc"""

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho, self._epsilon, self._momentum, self._centered = rho, epsilon, momentum, centered

    def _apply_one(self, index, param, grad, lr):
        ms = self._ensure_accumulator("mean_square")
        mom = self._ensure_accumulator("momentum")
        ms[index] = self._rho * ms[index] + (1 - self._rho) * grad * grad
        if self._centered:
            mg = self._ensure_accumulator("mean_grad")
            mg[index] = self._rho * mg[index] + (1 - self._rho) * grad
            denom = ms[index] - mg[index] ** 2 + self._epsilon
        else:
            denom = ms[index] + self._epsilon
        mom[index] = self._momentum * mom[index] + lr * grad / jnp.sqrt(denom)
        return param - mom[index]


class Adamax(Optimizer):
    """operators/optimizers/adamax_op.cc"""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _apply_one(self, index, param, grad, lr):
        m = self._ensure_accumulator("moment")
        inf_norm = self._ensure_accumulator("inf_norm")
        t = self._global_step
        m[index] = self._beta1 * m[index] + (1 - self._beta1) * grad
        inf_norm[index] = jnp.maximum(self._beta2 * inf_norm[index], jnp.abs(grad))
        lr_t = lr / (1 - self._beta1**t)
        return param - lr_t * m[index] / (inf_norm[index] + self._epsilon)


class Lamb(Optimizer):
    """operators/optimizers/lamb_op.cc — layerwise adaptive large-batch opt."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _apply_one(self, index, param, grad, lr):
        m = self._ensure_accumulator("moment1")
        v = self._ensure_accumulator("moment2")
        t = self._global_step
        m[index] = self._beta1 * m[index] + (1 - self._beta1) * grad
        v[index] = self._beta2 * v[index] + (1 - self._beta2) * grad * grad
        mhat = m[index] / (1 - self._beta1**t)
        vhat = v[index] / (1 - self._beta2**t)
        r = mhat / (jnp.sqrt(vhat) + self._epsilon)
        wd = self._lamb_wd
        p_obj = self._parameter_list[index]
        if self._exclude_fn is not None and self._exclude_fn(p_obj):
            wd = 0.0
        update = r + wd * param
        w_norm = jnp.sqrt(jnp.sum(param**2))
        u_norm = jnp.sqrt(jnp.sum(update**2))
        trust = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
        return param - lr * trust * update


# wrapper optimizers (fluid/optimizer.py:3411,3102,4822) — imported last so
# wrappers.py can see Optimizer on the partially-initialized package
from .wrappers import (  # noqa: E402
    ExponentialMovingAverage, ModelAverage, Lookahead, LookaheadOptimizer,
)

__all__ += ["ExponentialMovingAverage", "ModelAverage", "Lookahead",
            "LookaheadOptimizer"]

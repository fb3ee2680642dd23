"""Functionalization + compiled train steps.

Reference parity: the role played by ParallelExecutor/CompiledProgram
(paddle/fluid/framework/parallel_executor.cc, python/paddle/fluid/compiler.py:87)
— turning a model + optimizer into an efficient multi-device executable — and
by dygraph-to-static (python/paddle/fluid/dygraph/jit.py).

TPU-native design: instead of rewriting a program IR, we *functionalize* the
eager objects. A Layer's parameters/buffers and an Optimizer's accumulators
are extracted as pytrees of jax arrays; the eager forward/step code is run
once under JAX tracing with traced arrays swapped into the live objects,
yielding a single pure function

    step(state, batch, lr, rng) -> (state', metrics)

that XLA compiles (and, under a Mesh, partitions via GSPMD). The eager code
is the single source of truth — the same optimizer math runs eagerly and
compiled.
"""
from __future__ import annotations

import contextlib
import itertools
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp

# deterministic TrainStepFn instance ids (cache-key stability; see
# TrainStepFn.__init__)
_step_fn_counter = itertools.count()

from ..monitor import flight_recorder as _flight
from . import autograd
from .random import default_generator
from .tensor import Tensor

__all__ = [
    "capture_state",
    "functional_call",
    "TrainStepFn",
    "train_step",
    "eval_step",
]


# ---------------------------------------------------------------------------
# state extraction / swapping
# ---------------------------------------------------------------------------


def capture_state(model, optimizer=None):
    """Extract the functional state of a model (+ optional optimizer).

    Returns a dict pytree:
      params  — trainable parameter arrays (name -> array)
      frozen  — non-trainable parameter arrays
      buffers — persistable buffers (batchnorm stats, ...)
      opt     — optimizer accumulators + step count (if optimizer given)
    """
    params = OrderedDict()
    frozen = OrderedDict()
    for name, p in model.named_parameters():
        (params if getattr(p, "trainable", True) else frozen)[name] = p._array
    buffers = OrderedDict(
        (name, b._array) for name, b in model.named_buffers() if b is not None
    )
    state = {"params": params, "frozen": frozen, "buffers": buffers}
    if optimizer is not None:
        state["opt"] = {
            "accums": {k: list(v) for k, v in optimizer._accumulators.items()},
            "step": jnp.asarray(optimizer._global_step, jnp.int32),
        }
    return state


def restore_state(model, state, optimizer=None):
    """Write a state pytree back into the live eager objects."""
    named = dict(model.named_parameters())
    for name, arr in list(state["params"].items()) + list(state["frozen"].items()):
        named[name]._array = arr
    named_buf = dict(model.named_buffers())
    for name, arr in state["buffers"].items():
        named_buf[name]._array = arr
    if optimizer is not None and "opt" in state:
        optimizer._accumulators = {
            k: list(v) for k, v in state["opt"]["accums"].items()
        }
        optimizer._global_step = state["opt"]["step"]


@contextlib.contextmanager
def _swapped_model(model, state, rng_key=None):
    """Swap state arrays into the model's live tensors for the duration.

    On exit, the (possibly updated, e.g. batchnorm) buffer arrays are written
    into ``state["buffers"]`` and originals restored.
    """
    named = dict(model.named_parameters())
    named_buf = {n: b for n, b in model.named_buffers() if b is not None}
    saved_p = {n: t._array for n, t in named.items()}
    saved_b = {n: t._array for n, t in named_buf.items()}
    gen = default_generator()
    saved_key = gen.get_state()
    try:
        for name, arr in state["params"].items():
            named[name]._array = arr
        for name, arr in state["frozen"].items():
            named[name]._array = arr
        for name, arr in state["buffers"].items():
            named_buf[name]._array = arr
        if rng_key is not None:
            gen.set_state(rng_key)
        yield
        state["buffers"] = OrderedDict(
            (n, named_buf[n]._array) for n in state["buffers"]
        )
        state["rng"] = gen.get_state() if rng_key is not None else None
    finally:
        gen.set_state(saved_key)
        for n, a in saved_p.items():
            named[n]._array = a
        for n, a in saved_b.items():
            named_buf[n]._array = a


def functional_call(model, state, *args, rng=None, **kwargs):
    """Run ``model(*args)`` as a pure function of ``state``.

    ``args`` may be jax arrays or Tensors. Returns (outputs, new_state) where
    outputs have been unwrapped to jax arrays.
    """
    state = dict(state)
    state["buffers"] = OrderedDict(state["buffers"])
    wrapped = [
        a if isinstance(a, Tensor) else Tensor._from_array(jnp.asarray(a))
        for a in args
    ]
    with _swapped_model(model, state, rng_key=rng):
        with autograd.no_grad():
            out = model(*wrapped, **kwargs)
    out = jax.tree_util.tree_map(
        lambda x: x._array if isinstance(x, Tensor) else x,
        out,
        is_leaf=lambda x: isinstance(x, Tensor),
    )
    return out, state


# ---------------------------------------------------------------------------
# optimizer functionalization
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _swapped_opt(optimizer, opt_state, lr):
    saved_acc = optimizer._accumulators
    saved_step = optimizer._global_step
    saved_lr = optimizer._lr_override
    try:
        optimizer._accumulators = {
            k: list(v) for k, v in opt_state["accums"].items()
        }
        optimizer._global_step = opt_state["step"]
        optimizer._lr_override = lr
        yield
        opt_state["accums"] = {
            k: list(v) for k, v in optimizer._accumulators.items()
        }
        opt_state["step"] = jnp.asarray(optimizer._global_step, jnp.int32)
    finally:
        optimizer._accumulators = saved_acc
        optimizer._global_step = saved_step
        optimizer._lr_override = saved_lr


def _apply_optimizer(model, optimizer, state, grads, lr):
    """Run optimizer.step() purely: returns (new_params, new_opt_state)."""
    named = dict(model.named_parameters())
    saved = {n: t._array for n, t in named.items()}
    saved_grads = {n: t.grad for n, t in named.items()}
    opt_state = {
        "accums": dict(state["opt"]["accums"]),
        "step": state["opt"]["step"],
    }
    try:
        for name, arr in state["params"].items():
            named[name]._array = arr
            g = grads.get(name)
            named[name].grad = Tensor._from_array(g) if g is not None else None
        for name, arr in state["frozen"].items():
            named[name]._array = arr
            named[name].grad = None
        with _swapped_opt(optimizer, opt_state, lr):
            optimizer.step()
        new_params = OrderedDict(
            (n, named[n]._array) for n in state["params"]
        )
        return new_params, opt_state
    finally:
        for n, a in saved.items():
            named[n]._array = a
            named[n].grad = saved_grads[n]


def init_opt_state(model, optimizer, state=None):
    """Materialize optimizer accumulators without advancing real state.

    Accumulator layout differs per optimizer class and is created lazily by
    eager ``step()``; we discover it with ``jax.eval_shape`` (abstract trace,
    no FLOPs) and allocate concrete zeros. This keeps the step function's
    input pytree structure stable from the very first compiled step.
    """
    if state is None:
        state = capture_state(model, optimizer)
    if optimizer._accumulators:
        return state  # already materialized (e.g. loaded from checkpoint)

    def probe(params):
        zero_grads = {n: jnp.zeros_like(a) for n, a in params.items()}
        st = {
            "params": params,
            "frozen": state["frozen"],
            "opt": {"accums": {}, "step": jnp.asarray(0, jnp.int32)},
        }
        _, opt_state = _apply_optimizer(model, optimizer, st, zero_grads, 0.0)
        return opt_state["accums"]

    shapes = jax.eval_shape(probe, state["params"])
    accums = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes
    )
    # optimizers whose accumulators must not start at zero (e.g. Lookahead
    # slow weights = initial fast weights) expose concrete initial values
    init_hook = getattr(optimizer, "_init_accumulator_values", None)
    if init_hook is not None:
        accums = {**accums, **init_hook()}
    optimizer._accumulators = {k: list(v) for k, v in accums.items()}
    state["opt"] = {
        "accums": accums,
        "step": jnp.asarray(optimizer._global_step, jnp.int32),
    }
    return state


# ---------------------------------------------------------------------------
# compiled train / eval steps
# ---------------------------------------------------------------------------


class TrainStepFn:
    """A compiled training step bound to live eager objects.

    ``self.pure`` is the pure function
        pure(state, batch, lr, rng) -> (state', metrics)
    usable directly under jax.jit / pjit / shard_map.  Calling the object
    runs one step, keeping state on device; ``sync()`` writes state back
    into the eager model/optimizer (for checkpointing etc).
    """

    def __init__(self, model, optimizer, loss_fn, jit=True, donate=True,
                 recompute=False, grad_accum_steps=1, grad_accum_avg=True):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        # DistributedStrategy-driven behaviors (fleet meta-optimizer parity,
        # python/paddle/fluid/optimizer.py:4685 RecomputeOptimizer and
        # distributed/fleet/meta_optimizers/gradient_merge_optimizer.py):
        # recompute → jax.checkpoint over the forward (trade FLOPs for HBM);
        # grad_accum_steps=k → k-step gradient accumulation inside the
        # compiled step, optimizer applied every k-th call.
        self.recompute = bool(recompute)
        self.grad_accum_steps = int(grad_accum_steps)
        self.grad_accum_avg = bool(grad_accum_avg)
        self.state = init_opt_state(model, optimizer)
        if self.grad_accum_steps > 1:
            self.state["gm"] = {
                "acc": OrderedDict(
                    (n, jnp.zeros_like(a))
                    for n, a in self.state["params"].items()
                ),
                "count": jnp.asarray(0, jnp.int32),
            }
        if donate:
            # the initial state aliases the live model's arrays; donation
            # would invalidate them on TPU — copy once so the eager objects
            # stay readable until sync()
            self.state = jax.tree_util.tree_map(jnp.copy, self.state)
        self.pure = self._build_pure()
        self._jit = bool(jit)
        if jit:
            self.compiled = jax.jit(
                self.pure, donate_argnums=(0,) if donate else ()
            )
        else:
            self.compiled = self.pure
        # per-batch-signature executables through the SHARED compiled-
        # callable runtime (runtime/compiled.py): AOT compile + cost
        # capture + LRU bound (FLAGS_compiled_cache_capacity — the same
        # knob the executor obeys; the old hardcoded 16 here silently
        # evicted/recompiled under many batch signatures) + the
        # donation-safe demote-to-jit fallback, all one policy.
        from ..runtime.compiled import CompiledStore

        self._exec = CompiledStore(
            "train_step", cost_label="train_step",
            hit_counter="train_step::exec_cache_hit",
            miss_counter="train_step::exec_cache_miss")
        # deterministic per-instance index (not id()): the derived
        # cache_key must be stable across runs for log correlation, yet
        # distinct per step fn so two models with identical batch avals
        # don't collide in the global CostRecord registry
        self._instance = next(_step_fn_counter)
        self._rng = default_generator().split()
        self._stall = _flight.StepWatch("train_step", jax.devices()[0])

    def _build_pure(self):
        model, optimizer, loss_fn = self.model, self.optimizer, self.loss_fn
        recompute = getattr(self, "recompute", False)
        k = getattr(self, "grad_accum_steps", 1)
        avg = getattr(self, "grad_accum_avg", True)
        # FLAGS_quantized_allreduce, read at step CONSTRUCTION (like
        # donate): gradients route through the int8-with-per-block-scales
        # sync (distributed/quantized.py) — on a bound-axis SPMD world
        # the real quantized collectives, under GSPMD/single-controller
        # the same two quantization hops with the wire bytes accounted in
        # the collective ledger. Capturing the flag here (not at trace
        # time) keeps a compiled step's behavior fixed: flipping the flag
        # later builds a NEW step fn with its own cache keys.
        from ..flags import flag as _flag

        quantized_sync = bool(_flag("quantized_allreduce"))

        def pure(state, batch, lr, rng):
            frozen, buffers = state["frozen"], state["buffers"]

            def loss_of(params):
                st = {
                    "params": params,
                    "frozen": frozen,
                    "buffers": OrderedDict(buffers),
                }
                wrapped = [Tensor._from_array(a) for a in batch]
                was_training = model.training
                model.train()  # a train step always traces in train mode
                try:
                    with _swapped_model(model, st, rng_key=rng):
                        with autograd.no_grad():
                            loss = loss_fn(model, *wrapped)
                finally:
                    if not was_training:
                        model.eval()
                loss_arr = loss._array if isinstance(loss, Tensor) else loss
                return loss_arr, st["buffers"]

            if recompute:
                # RecomputeOptimizer equivalent (fluid/optimizer.py:4685):
                # forward activations are not saved for backward — XLA
                # rematerializes them, trading MXU FLOPs for HBM.
                loss_of = jax.checkpoint(loss_of)

            (loss, new_buffers), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(state["params"])

            if quantized_sync:
                # int8 gradient sync: under GSPMD the grads at this point
                # are already the global mean, so the hook applies the
                # wire-precision rounding (and books the quantized wire
                # bytes); in a bound-axis SPMD body it IS the all-reduce.
                from ..distributed import quantized as _qar

                # quantized=True pins the construction-time capture: the
                # default would re-read the flag at trace time, and a
                # flag flip before a retrace would silently swap modes
                grads = _qar.sync_grads(grads, average=False,
                                        quantized=True)

            if k <= 1:
                new_params, new_opt = _apply_optimizer(
                    model, optimizer, state, grads, lr
                )
                new_state = {
                    "params": new_params,
                    "frozen": frozen,
                    "buffers": new_buffers,
                    "opt": new_opt,
                }
                return new_state, {"loss": loss}

            # gradient merge (meta_optimizers/gradient_merge_optimizer.py):
            # accumulate k micro-grads, apply the optimizer on the k-th.
            acc = jax.tree_util.tree_map(jnp.add, state["gm"]["acc"], grads)
            count = state["gm"]["count"] + 1

            def apply_branch(_):
                g = (
                    jax.tree_util.tree_map(lambda a: a / k, acc)
                    if avg
                    else acc
                )
                new_params, new_opt = _apply_optimizer(
                    model, optimizer, state, g, lr
                )
                zeros = jax.tree_util.tree_map(jnp.zeros_like, acc)
                return new_params, new_opt, zeros, jnp.asarray(0, jnp.int32)

            def skip_branch(_):
                opt_state = {
                    "accums": {
                        kk: list(v)
                        for kk, v in state["opt"]["accums"].items()
                    },
                    "step": jnp.asarray(state["opt"]["step"], jnp.int32),
                }
                return (
                    OrderedDict(state["params"]),
                    opt_state,
                    acc,
                    jnp.asarray(count, jnp.int32),
                )

            new_params, new_opt, new_acc, new_count = jax.lax.cond(
                count >= k, apply_branch, skip_branch, None
            )
            new_state = {
                "params": new_params,
                "frozen": frozen,
                "buffers": new_buffers,
                "opt": new_opt,
                "gm": {"acc": new_acc, "count": new_count},
            }
            return new_state, {"loss": loss}

        return pure

    def __call__(self, *batch):
        # the span names parallel/train.py's sharded step uses; no outer
        # train::step here (callers wrap their own, and a wrapper would
        # take every device gap in the benchmark's attribution). The
        # watch times the same two phases whatever the profiler's state
        # (its split of the call, and the train_stall record)
        watch = self._stall
        t0 = watch.enter()
        batch = tuple(  # H2D of a host batch
            b._array if isinstance(b, Tensor) else jnp.asarray(b)
            for b in batch
        )
        watch.phase("train::shard_batch", t0)
        if not getattr(self, "_usage_checked", False):
            self._freeze_unused_params(batch)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        self._rng, sub = jax.random.split(self._rng)
        from ..flags import flag

        t0 = time.perf_counter_ns()
        if flag("check_nan_inf"):
            # FLAGS_check_nan_inf (platform/flags.cc:44 →
            # details/nan_inf_utils_detail.cc): the reference scans
            # every op's outputs post-run; the XLA-native equivalent is
            # checkify float_checks — every primitive inside the
            # compiled step gets an instrumented NaN check that reports
            # the producing operation's source location.
            metrics = self._run_checked(batch, lr, sub)
        else:
            metrics = self._dispatch(batch, lr, sub)
        watch.phase("train::step_dispatch", t0, watch.nested)
        if flag("benchmark"):
            # FLAGS_benchmark: synchronous dispatch for exact timings
            jax.block_until_ready(metrics)
        # NOTE: LR schedulers keep eager semantics — the user calls
        # scheduler.step() (per epoch or per batch) exactly as in eager mode;
        # the current value is read and fed in as a traced scalar each step.
        watch.leave()
        return metrics

    def _dispatch(self, batch, lr, sub):
        """Run one step through the shared compiled-callable runtime:
        per-batch-signature AOT compile (the same single XLA compile
        jax.jit's first call would pay, captured for the utilization
        accounting), LRU caching, and the donation-safe demote-to-jit
        fallback all follow the one policy in runtime/compiled.py."""
        if not self._jit:
            self.state, metrics = self.compiled(self.state, batch, lr, sub)
            return metrics
        # params can migrate to frozen (_freeze_unused_params) and the
        # gradient-merge slot changes the state pytree — both change the
        # compiled signature, so they key the executable cache alongside
        # the batch avals
        sig = (self._instance, len(self.state["params"]),
               "gm" in self.state) + tuple(
            (tuple(b.shape), str(b.dtype)) for b in batch)
        nested = self._stall.nested
        entry, _ = self._exec.get_or_build(
            sig, lambda: (self.compiled, None), nested=nested)
        new_state, metrics = self._exec.dispatch(
            entry, self.state, batch, lr, sub,
            donated=lambda: jax.tree_util.tree_leaves(self.state),
            nested=nested)
        self.state = new_state
        return metrics

    def _run_checked(self, batch, lr, sub):
        from jax.experimental import checkify

        from ..errors import FatalError

        if not hasattr(self, "_checked_fn"):
            # no donation: on error the pre-step state must stay valid
            self._checked_fn = jax.jit(
                checkify.checkify(self.pure, errors=checkify.float_checks)
            )
        err, (new_state, metrics) = self._checked_fn(
            self.state, batch, lr, sub
        )
        try:
            err.throw()
        except Exception as e:  # checkify.JaxRuntimeError
            # FLAGS_check_nan_inf_action, shared policy with the executor
            # scan (flight_recorder.nan_event_action): warn counts + logs
            # and keeps training, dump writes the flight-recorder
            # snapshot before raising, raise is the default
            from ..monitor import flight_recorder as _flight

            if _flight.nan_event_action(
                    "train_step",
                    f"non-finite value produced inside the train step: "
                    f"{e}") is not None:
                raise FatalError(
                    f"check_nan_inf: non-finite value produced inside the "
                    f"train step: {e}"
                ) from e
        self.state = new_state
        return metrics

    def _freeze_unused_params(self, batch):
        """Move params the loss never reads into the frozen group.

        Eager-parity: eager step() skips params with grad None, but
        value_and_grad returns *zeros* for unused params, which would
        wrongly apply weight decay / advance accumulators on them. A
        one-time abstract trace finds the truly-unused leaves (an outer
        jaxpr invar unused at the top level cannot be consumed by any
        nested jaxpr either — nested use passes through call-eqn invars).
        """
        self._usage_checked = True
        names = list(self.state["params"].keys())

        def probe(params, batch, rng):
            (loss, _), grads = _noop_grads_probe(
                self.model, self.loss_fn, params,
                self.state["frozen"], self.state["buffers"], batch, rng,
            )
            return loss

        try:
            jaxpr = jax.make_jaxpr(probe)(
                self.state["params"], batch, self._rng
            ).jaxpr
        except Exception:
            return  # fail open: keep zero-grad behavior
        n = len(names)
        invars = jaxpr.invars[:n]
        used = set()
        for eqn in jaxpr.eqns:
            used.update(map(id, eqn.invars))
        used.update(map(id, jaxpr.outvars))
        unused = [nm for nm, v in zip(names, invars) if id(v) not in used]
        if not unused:
            return
        for nm in unused:
            self.state["frozen"][nm] = self.state["params"].pop(nm)
            if "gm" in self.state:
                self.state["gm"]["acc"].pop(nm, None)
        # rebuild: the pure fn closes over nothing stateful, but the pytree
        # structure of `state` changed, so recompilation happens naturally

    def save_checkpoint(self, path, step=None, async_=None, keep=None):
        """Snapshot the on-device state crash-consistently (async by
        default — FLAGS_checkpoint_async); distributed/checkpoint.py."""
        from ..distributed import checkpoint as _ckpt

        return _ckpt.save_train_step(self, path, step=step, async_=async_,
                                     keep=keep)

    def load_checkpoint(self, path):
        """Restore a snapshot written by ``save_checkpoint`` (also
        accepts one saved from a sharded/multi-rank world — the global
        arrays are reassembled from all shards). Returns the manifest."""
        from ..distributed import checkpoint as _ckpt

        return _ckpt.restore_train_step(self, path)

    def sync(self):
        # copy before restoring: restore_state aliases state arrays into
        # the live objects, and the next step() donates self.state — without
        # the copy, donation would invalidate the model's own parameters
        state = jax.tree_util.tree_map(jnp.copy, self.state)
        restore_state(self.model, state, self.optimizer)
        return self


def _noop_grads_probe(model, loss_fn, params, frozen, buffers, batch, rng):
    """Forward-only probe used by _freeze_unused_params."""
    def loss_of(p):
        st = {
            "params": p,
            "frozen": frozen,
            "buffers": OrderedDict(buffers),
        }
        wrapped = [Tensor._from_array(a) for a in batch]
        with _swapped_model(model, st, rng_key=rng):
            with autograd.no_grad():
                loss = loss_fn(model, *wrapped)
        loss_arr = loss._array if isinstance(loss, Tensor) else loss
        return loss_arr, st["buffers"]

    out = loss_of(params)
    return out, None


def train_step(model, optimizer, loss_fn, jit=True, donate=True,
               recompute=False, grad_accum_steps=1, grad_accum_avg=True):
    """Build a compiled train step.

    ``loss_fn(model, *batch) -> scalar loss Tensor`` runs the eager forward.
    """
    return TrainStepFn(
        model, optimizer, loss_fn, jit=jit, donate=donate,
        recompute=recompute, grad_accum_steps=grad_accum_steps,
        grad_accum_avg=grad_accum_avg,
    )


def eval_step(model, fn=None, jit=True):
    """Compile an inference step: returns callable(batch...) -> arrays.

    ``fn(model, *batch)`` customizes the computation (e.g. decode instead of
    raw logits); by default the model's forward is used. The model is run in
    eval mode regardless of its current training flag.
    """

    def pure(state, *batch):
        state = dict(state)
        state["buffers"] = OrderedDict(state["buffers"])
        wrapped = [
            a if isinstance(a, Tensor) else Tensor._from_array(jnp.asarray(a))
            for a in batch
        ]
        with _swapped_model(model, state):
            with autograd.no_grad():
                out = fn(model, *wrapped) if fn is not None else model(*wrapped)
        return jax.tree_util.tree_map(
            lambda x: x._array if isinstance(x, Tensor) else x,
            out,
            is_leaf=lambda x: isinstance(x, Tensor),
        )

    compiled = jax.jit(pure) if jit else pure

    # the module-tree walk (named_parameters/named_buffers recursion) runs
    # once; per call only the current arrays are read off the cached
    # Tensor objects — fresh values with no per-step tree traversal
    # (training mutates t._array in place, never the Tensor identities)
    cached = {}

    def snapshot():
        if not cached:
            cached["params"] = [
                (n, p, getattr(p, "trainable", True))
                for n, p in model.named_parameters()
            ]
            cached["buffers"] = [
                (n, b) for n, b in model.named_buffers() if b is not None
            ]
        params, frozen = OrderedDict(), OrderedDict()
        for n, p, trainable in cached["params"]:
            (params if trainable else frozen)[n] = p._array
        return {
            "params": params,
            "frozen": frozen,
            "buffers": OrderedDict(
                (n, b._array) for n, b in cached["buffers"]
            ),
        }

    def run(*batch):
        arrs = tuple(
            b._array if isinstance(b, Tensor) else jnp.asarray(b) for b in batch
        )
        was_training = model.training
        model.eval()
        try:
            return compiled(snapshot(), *arrs)
        finally:
            if was_training:
                model.train()

    run.pure = pure
    return run

"""Sharded (multi-device) train steps.

Reference parity: CompiledProgram.with_data_parallel + ParallelExecutor
(python/paddle/fluid/compiler.py:160, framework/parallel_executor.cc) —
replicate the step across devices and keep gradients in sync. TPU-native:
the functionalized step (framework/jit.py) is pjit-compiled with
NamedShardings; XLA/GSPMD inserts the all-reduces the reference's
multi_devices_graph_pass inserted by hand, fuses them (fuse_all_reduce_op
pass ≙ XLA collective combining), and overlaps them with compute.

DistributedStrategy consumption (fleet meta-optimizer parity — the
reference composes program-rewriting meta-optimizers via
base/strategy_compiler.py; here the strategy configures the step builder):
  recompute       → jax.checkpoint over the forward
                    (fluid/optimizer.py:4685 RecomputeOptimizer)
  gradient_merge  → k-step grad accumulation inside the compiled step
                    (meta_optimizers/gradient_merge_optimizer.py)
  sharding        → ZeRO-1 optimizer-state sharding over dp
                    (capability absent in the reference; TPU-first design)
  localsgd        → per-device divergent replicas + periodic param
                    averaging (meta_optimizers/localsgd_optimizer.py)
  amp             → bf16 autocast around the loss fn
  dgc / a_sync    → not implementable on this runtime: loud error, never a
                    silent no-op.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..framework import jit as fjit
from ..framework.random import default_generator
from ..framework.tensor import Tensor
from ..monitor import flight_recorder as _flight
from ..monitor import registry as _mon
from ..profiler import RecordEvent
from .mesh import mesh_scope
from .sharding import DEFAULT_RULES, shard_batch, shard_state, zero1_shard_opt

__all__ = [
    "sharded_train_step",
    "ShardedTrainStep",
    "LocalSGDTrainStep",
    "consume_strategy",
]


def consume_strategy(strategy):
    """Translate a fleet DistributedStrategy into step-builder options.

    Every accepted flag either maps to a real behavior or raises — the
    reference's StrategyCompiler selects meta-optimizers the same way
    (base/strategy_compiler.py); silently ignoring a flag is never allowed.
    """
    if strategy is None:
        return {}
    if getattr(strategy, "dgc", False):
        raise NotImplementedError(
            "DistributedStrategy.dgc: deep gradient compression is a "
            "NCCL-ring bandwidth optimization (reference "
            "details/sparse_all_reduce_op_handle.cc); on TPU the gradient "
            "all-reduce rides ICI inside the XLA program and cannot be "
            "sparsified post-hoc. Use gradient_merge or localsgd to cut "
            "communication instead."
        )
    if getattr(strategy, "a_sync", False):
        # parameter-server mode (distributed/ps): trainers run
        # independent dense steps (no dp collective), sparse tables sync
        # through the table servers via PSEmbedding/GeoPSEmbedding.
        # k_steps > 0 in a_sync_configs selects geo mode — the reference's
        # sync/async/geo triple (distribute_transpiler.py:256,
        # geo_sgd_transpiler.py).
        conflicting = [
            f for f in ("recompute", "amp", "sharding", "localsgd",
                        "gradient_merge", "pipeline", "lars", "lamb")
            if getattr(strategy, f, False)
        ]
        if conflicting:
            raise NotImplementedError(
                f"DistributedStrategy.a_sync cannot combine with "
                f"{conflicting}: parameter-server trainers run plain "
                "local dense steps (the reference's PS path has the same "
                "separation from the collective meta-optimizers)"
            )
        cfg = getattr(strategy, "a_sync_configs", None)
        # the reference documents both the attr form and plain dict
        # assignment (strategy.a_sync_configs = {"k_steps": N})
        k = (cfg.get("k_steps", 0) if isinstance(cfg, dict)
             else getattr(cfg, "k_steps", 0))
        return {
            "a_sync": True,
            "geo_k_steps": int(k or 0),
            "recompute": False, "amp": False, "grad_accum_steps": 1,
            "grad_accum_avg": True, "zero1": False, "localsgd": False,
            "localsgd_k": 1, "rules": None,
        }
    if getattr(strategy, "pipeline", False):
        raise NotImplementedError(
            "DistributedStrategy.pipeline cannot split an arbitrary eager "
            "model automatically; build the stages explicitly with "
            "parallel.GPipe over a mesh with pp_degree > 1 "
            "(parallel/pipeline.py)."
        )
    opts = {
        "recompute": bool(getattr(strategy, "recompute", False)),
        "amp": bool(getattr(strategy, "amp", False)),
        "grad_accum_steps": 1,
        "grad_accum_avg": True,
        "zero1": bool(getattr(strategy, "sharding", False)),
        "localsgd": bool(getattr(strategy, "localsgd", False)),
        "localsgd_k": 1,
        "rules": getattr(strategy, "sharding_rules", None),
    }
    if getattr(strategy, "gradient_merge", False):
        cfg = strategy.gradient_merge_configs
        opts["grad_accum_steps"] = int(cfg.k_steps)
        opts["grad_accum_avg"] = bool(cfg.avg)
    if opts["localsgd"]:
        opts["localsgd_k"] = int(strategy.localsgd_configs.k_steps)
        if opts["grad_accum_steps"] > 1 or opts["zero1"]:
            raise NotImplementedError(
                "localsgd cannot be combined with gradient_merge/sharding "
                "(params diverge per-replica; there is no global optimizer "
                "state to shard)"
            )
    return opts


def _amp_wrap(loss_fn, strategy):
    """Wrap a loss fn in bf16 autocast per strategy.amp_configs."""
    cfg = getattr(strategy, "amp_configs", None)
    white = list(getattr(cfg, "custom_white_list", []) or [])
    black = list(getattr(cfg, "custom_black_list", []) or [])

    def wrapped(model, *batch):
        from .. import amp as amp_mod

        with amp_mod.auto_cast(
            custom_white_list=white or None,
            custom_black_list=black or None,
        ):
            return loss_fn(model, *batch)

    return wrapped


class ShardedTrainStep(fjit.TrainStepFn):
    """TrainStepFn partitioned over a device mesh.

    The loss gradient is averaged over the dp axis implicitly: the batch is
    sharded on dp, the loss is a global mean, so d(loss)/d(params) *is* the
    dp-mean — the allreduce the reference inserts per-gradient
    (framework/details/all_reduce_op_handle.cc) falls out of GSPMD.
    """

    def __init__(self, model, optimizer, loss_fn, mesh, rules=None,
                 batch_axes=("dp",), donate=True, strategy=None,
                 recompute=False, grad_accum_steps=1, grad_accum_avg=True,
                 zero1=False):
        opts = consume_strategy(strategy)
        if opts:
            recompute = recompute or opts["recompute"]
            if opts["grad_accum_steps"] > 1:
                grad_accum_steps = opts["grad_accum_steps"]
                grad_accum_avg = opts["grad_accum_avg"]
            zero1 = zero1 or opts["zero1"]
            rules = rules or opts["rules"]
            if opts["amp"]:
                loss_fn = _amp_wrap(loss_fn, strategy)
        self.mesh = mesh
        self.rules = rules or DEFAULT_RULES
        self.batch_axes = batch_axes
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.recompute = bool(recompute)
        self.grad_accum_steps = int(grad_accum_steps)
        self.grad_accum_avg = bool(grad_accum_avg)
        self.zero1 = bool(zero1)
        with mesh_scope(mesh):
            self.state = fjit.init_opt_state(model, optimizer)
            if self.grad_accum_steps > 1:
                from collections import OrderedDict

                self.state["gm"] = {
                    "acc": OrderedDict(
                        (n, jnp.zeros_like(a))
                        for n, a in self.state["params"].items()
                    ),
                    "count": jnp.asarray(0, jnp.int32),
                }
            self.state_shardings = shard_state(self.state, self.rules, mesh)
            if self.zero1:
                zero1_shard_opt(self.state_shardings, self.state, mesh)
            # place initial state according to the shardings
            self.state = jax.tree_util.tree_map(
                lambda a, s: jax.device_put(a, s),
                self.state,
                self.state_shardings,
            )
            self.pure = self._build_pure()
            self.compiled = jax.jit(
                self.pure,
                in_shardings=(
                    self.state_shardings,
                    None,  # batch shardings applied via device_put
                    NamedSharding(mesh, P()),
                    NamedSharding(mesh, P()),
                ),
                out_shardings=(self.state_shardings, None),
                donate_argnums=(0,) if donate else (),
            )
        self._rng = default_generator().split()
        self._stall = _flight.StepWatch("train_step", mesh.devices.flat[0])

    def __call__(self, *batch):
        watch = self._stall
        t0 = watch.enter()
        with RecordEvent("train::step"), mesh_scope(self.mesh):
            arrs = tuple(  # H2D + layout
                b._array if isinstance(b, Tensor) else jnp.asarray(b)
                for b in batch
            )
            shardings = shard_batch(arrs, self.mesh, self.batch_axes)
            arrs = jax.tree_util.tree_map(jax.device_put, arrs, shardings)
            watch.phase("train::shard_batch", t0)
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
            self._rng, sub = jax.random.split(self._rng)
            t0 = time.perf_counter_ns()
            self.state, metrics = self.compiled(self.state, arrs, lr, sub)
            watch.phase("train::step_dispatch", t0)
            _mon.counter("train/sharded_steps").inc()
        watch.leave()
        return metrics


    def save_checkpoint(self, path, step=None, async_=None, keep=None,
                        peer_timeout_s=None):
        """Snapshot the device state (per-shard, with PartitionSpec
        metadata) — see distributed/checkpoint.py. Async by default
        (``FLAGS_checkpoint_async``): the step loop pays one device-side
        copy; serialize/fsync/publish run on the writer thread."""
        from ..distributed import checkpoint as _ckpt

        return _ckpt.save_train_step(self, path, step=step, async_=async_,
                                     keep=keep,
                                     peer_timeout_s=peer_timeout_s)

    def load_checkpoint(self, path):
        """Restore a snapshot, re-slicing every leaf (including ZeRO-1
        optimizer shards) onto THIS step's mesh — which may be a
        different world size than the save. Returns the manifest."""
        from ..distributed import checkpoint as _ckpt

        return _ckpt.restore_train_step(self, path)

    def sync(self, gather=True):
        """Write device state back into the eager objects.

        gather=True (default) materializes host-local copies so the eager
        model is usable on any backend afterwards (paddle semantics:
        state_dict/save/eval after training); gather=False keeps the
        mesh-sharded layout (fast path when the state will only feed
        another sharded step).
        """
        state = self.state
        if gather:
            import numpy as np

            state = jax.tree_util.tree_map(
                lambda a: jnp.asarray(np.asarray(a)), state
            )
        else:
            # copy: restore_state aliases arrays into the live objects and
            # the next step() donates self.state
            state = jax.tree_util.tree_map(jnp.copy, state)
        fjit.restore_state(self.model, state, self.optimizer)
        return self


class LocalSGDTrainStep:
    """LocalSGD over the dp mesh axis (meta_optimizers/localsgd_optimizer.py).

    Each dp replica holds its own divergent copy of params + optimizer
    state (stacked on a leading axis, sharded P("dp")) and trains on its
    own batch shard with NO gradient communication; every ``k_steps`` calls
    the replicas' parameters are averaged with one pmean over ICI. The
    reference rewrites the program to insert c_allreduce on params every
    k steps — here the periodic sync is a lax.cond inside one shard_map'd
    XLA program, so off-sync steps run with zero collective traffic.
    """

    def __init__(self, model, optimizer, loss_fn, mesh, k_steps=1,
                 recompute=False, donate=True):
        self.mesh = mesh
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.recompute = bool(recompute)
        self.grad_accum_steps = 1
        self.grad_accum_avg = True
        self.k_steps = int(k_steps)
        self.ndp = int(mesh.shape["dp"])
        if self.ndp <= 1:
            raise ValueError("LocalSGD needs a dp axis of size > 1")

        base = fjit.init_opt_state(model, optimizer)
        stack = lambda a: jnp.broadcast_to(
            a[None], (self.ndp,) + a.shape
        ).astype(a.dtype)
        self.state = {
            "params": jax.tree_util.tree_map(stack, base["params"]),
            # never updated, stays replicated — but copied: donation of
            # aliased leaves would invalidate the live model's arrays
            "frozen": jax.tree_util.tree_map(jnp.copy, base["frozen"]),
            "buffers": jax.tree_util.tree_map(stack, base["buffers"]),
            "opt": jax.tree_util.tree_map(stack, base["opt"]),
        }
        self._count = jnp.asarray(0, jnp.int32)
        # reuse the functional step builder for the per-replica local step
        self.pure_local = fjit.TrainStepFn._build_pure(self)

        k = self.k_steps

        def body(state, count, batch, lr, rng):
            squeeze = lambda a: jnp.squeeze(a, 0)
            local = {
                "params": jax.tree_util.tree_map(squeeze, state["params"]),
                "frozen": state["frozen"],
                "buffers": jax.tree_util.tree_map(squeeze, state["buffers"]),
                "opt": jax.tree_util.tree_map(squeeze, state["opt"]),
            }
            rng = jax.random.fold_in(rng, jax.lax.axis_index("dp"))
            new_local, metrics = self.pure_local(local, batch, lr, rng)
            count = count + 1

            def sync_branch(p):
                return jax.tree_util.tree_map(
                    lambda x: jax.lax.pmean(x, "dp"), p
                )

            do_sync = count >= k
            new_params = jax.lax.cond(
                do_sync, sync_branch, lambda p: p, new_local["params"]
            )
            new_count = jnp.where(do_sync, 0, count).astype(jnp.int32)
            unsq = lambda a: a[None]
            out_state = {
                "params": jax.tree_util.tree_map(unsq, new_params),
                "frozen": state["frozen"],
                "buffers": jax.tree_util.tree_map(
                    unsq, new_local["buffers"]
                ),
                "opt": jax.tree_util.tree_map(unsq, new_local["opt"]),
            }
            loss = jax.lax.pmean(metrics["loss"], "dp")
            return out_state, new_count, {"loss": loss}

        state_specs = {
            "params": P("dp"),
            "frozen": P(),
            "buffers": P("dp"),
            "opt": P("dp"),
        }
        self._sharded = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(state_specs, P(), P("dp"), P(), P()),
            out_specs=(state_specs, P(), P()),
            check_vma=False,
        )
        self.compiled = jax.jit(
            self._sharded, donate_argnums=(0,) if donate else ()
        )
        self._rng = default_generator().split()
        self._stall = _flight.StepWatch("train_step", mesh.devices.flat[0])

    def __call__(self, *batch):
        watch = self._stall
        t0 = watch.enter()
        with RecordEvent("train::step"), mesh_scope(self.mesh):
            arrs = tuple(
                b._array if isinstance(b, Tensor) else jnp.asarray(b)
                for b in batch
            )
            shardings = shard_batch(arrs, self.mesh, ("dp",))
            arrs = jax.tree_util.tree_map(jax.device_put, arrs, shardings)
            watch.phase("train::shard_batch", t0)
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
            self._rng, sub = jax.random.split(self._rng)
            t0 = time.perf_counter_ns()
            self.state, self._count, metrics = self.compiled(
                self.state, self._count, arrs, lr, sub
            )
            watch.phase("train::step_dispatch", t0)
            _mon.counter("train/localsgd_steps").inc()
        watch.leave()
        return metrics

    def sync(self, gather=True):
        """Average replicas and write back into the eager objects."""
        import numpy as np

        mean0 = lambda a: jnp.mean(
            jnp.asarray(np.asarray(a)).astype(jnp.float32), axis=0
        ).astype(a.dtype) if a.dtype in (
            jnp.float32, jnp.bfloat16, jnp.float16
        ) else jnp.asarray(np.asarray(a))[0]
        state = {
            "params": jax.tree_util.tree_map(mean0, self.state["params"]),
            "frozen": jax.tree_util.tree_map(
                lambda a: jnp.asarray(np.asarray(a)), self.state["frozen"]
            ),
            "buffers": jax.tree_util.tree_map(mean0, self.state["buffers"]),
            "opt": jax.tree_util.tree_map(mean0, self.state["opt"]),
        }
        fjit.restore_state(self.model, state, self.optimizer)
        return self


def sharded_train_step(model, optimizer, loss_fn, mesh, rules=None,
                       batch_axes=("dp",), donate=True, strategy=None,
                       **kwargs):
    """Build a mesh-partitioned train step, consuming a fleet strategy.

    With ``strategy.localsgd`` on, returns a LocalSGDTrainStep (divergent
    replicas + periodic sync); otherwise a GSPMD ShardedTrainStep.
    """
    opts = consume_strategy(strategy)
    if opts.get("localsgd"):
        if rules is not None or tuple(batch_axes) != ("dp",) or kwargs:
            raise NotImplementedError(
                "localsgd replicas are whole-model (no tensor sharding): "
                "rules/batch_axes/extra step options are not supported "
                f"(got rules={rules}, batch_axes={batch_axes}, "
                f"kwargs={sorted(kwargs)})"
            )
        loss_fn2 = _amp_wrap(loss_fn, strategy) if opts["amp"] else loss_fn
        return LocalSGDTrainStep(
            model, optimizer, loss_fn2, mesh,
            k_steps=opts["localsgd_k"], recompute=opts["recompute"],
            donate=donate,
        )
    return ShardedTrainStep(
        model, optimizer, loss_fn, mesh, rules=rules,
        batch_axes=batch_axes, donate=donate, strategy=strategy, **kwargs,
    )

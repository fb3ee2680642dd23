"""Static-graph Executor + Scope.

Reference parity: paddle/fluid/framework/executor.cc:180 (Executor::Run op
loop) + framework/scope.h:46 (Scope) + python/paddle/fluid/executor.py:474.

TPU-native design (SURVEY.md §7 step 2): instead of interpreting ops one by
one (the reference's hot loop, executor.cc:428), the whole block is traced
into ONE jax function and compiled by XLA per (program version, feed
shapes/dtypes) — the op loop collapses into a single fused HLO module, so
op-boundary overhead and intermediate materialization vanish. Gradient ops
("grad::<type>") are interpreted via jax.vjp of the forward kernel during
tracing — per-op grad kernels never need hand-writing. Persistable vars
(parameters, optimizer state, RNG-updated stats) are threaded in/out of the
compiled function and written back to the Scope after each run.

Control-flow ops (while/cond/scan, operators/controlflow/ in the reference)
consume nested blocks and lower to lax.while_loop / lax.cond / lax.scan:
sub-blocks are traced recursively into the same XLA module. Their grad ops
re-trace the sub-block as a pure closure over (explicit) inputs and
jax.vjp through it — lax.cond and lax.scan are reverse-differentiable by
construction; lax.while_loop is not (use scan for trainable loops).
"""
from __future__ import annotations

import contextlib
import threading
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..flags import flag
from ..framework import random as _random
from ..monitor import flight_recorder as _flight
from ..monitor import tracing as _tracing
from ..monitor.opprof import op_scope_name as _op_scope
from ..runtime.compiled import CompiledStore
from ..framework.place import Place, _default_place
from ..framework.tensor import Tensor
from ..ops.registry import kernel
from ..profiler import RecordEvent, bump_counter
from .program import Program, default_main_program, default_startup_program


class Scope:
    """name → host/device array map (framework/scope.h:46)."""

    def __init__(self):
        self._vars: dict[str, jax.Array] = {}

    def set(self, name, value):
        self._vars[name] = jnp.asarray(value)

    def get(self, name):
        return self._vars[name]

    def has(self, name):
        return name in self._vars

    def var_names(self):
        return list(self._vars)

    def drop(self, name):
        self._vars.pop(name, None)

    def clear(self):
        self._vars.clear()


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


_BLOCK_OPS = ("while", "cond", "scan")

# nullcontext is stateless — one shared instance keeps the steady-state
# dispatch path allocation-free
_NULL_CTX = contextlib.nullcontext()


def _walk_ops(program, block_idx, seen=None):
    """Yield (block, op) over a block and all nested sub-blocks."""
    from .control_flow import BLOCK_ATTR_KEYS

    if seen is None:
        seen = set()
    if block_idx in seen:
        return
    seen.add(block_idx)
    blk = program.blocks[block_idx]
    for op in blk.ops:
        yield blk, op
        for key, val in op.attrs.items():
            if key in BLOCK_ATTR_KEYS and isinstance(val, int):
                yield from _walk_ops(program, val, seen)


def _op_key(base_key, op, it=None):
    key = jax.random.fold_in(base_key, op.attrs["__rng_id__"])
    if it is not None:
        key = jax.random.fold_in(key, it)
    return key


def op_in_names(op):
    """Positional input names of an op.

    The reference's OpDesc keys io by named slots (framework.proto:42
    name-maps); this runtime canonically uses one "X" slot, but ops MAY
    declare named multi-slot inputs via the ``__in_slots__`` attr (an
    ordered slot list) — the kernel then receives the slots' vars
    concatenated in that order. Same for outputs via ``__out_slots__``.
    """
    slots = op.attrs.get("__in_slots__")
    if slots:
        return [n for s in slots for n in op.inputs.get(s, [])]
    return op.inputs.get("X", [])


def op_out_names(op):
    slots = op.attrs.get("__out_slots__")
    if slots:
        return [n for s in slots for n in op.outputs.get(s, [])]
    return op.outputs.get("Out", [])


class _LazyFetchList(list):
    """``run()`` fetch result: a list whose elements materialize to numpy
    on first access.

    ``return_numpy=True`` used to force a blocking ``np.asarray`` on every
    fetch every step; now the device->host sync happens at first element
    access, so a training loop that only inspects the loss every
    ``print_period`` steps dispatches the intervening steps without ever
    blocking on a transfer, and ``train_from_dataset`` overlaps batch
    N+1's H2D copy with step N's dispatch.
    """

    def _materialize(self, i):
        v = list.__getitem__(self, i)
        if not isinstance(v, np.ndarray):
            # the device->host sync the laziness deferred happens HERE —
            # span it so a trace shows exactly which access paid it
            with RecordEvent("executor::fetch_sync"):
                v = np.asarray(v)
            list.__setitem__(self, i, v)
        return v

    def _materialize_all(self):
        for i in range(len(self)):
            self._materialize(i)
        return self

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self._materialize(j)
                    for j in range(*i.indices(len(self)))]
        return self._materialize(i)

    def __iter__(self):
        for i in range(len(self)):
            yield self._materialize(i)

    # C-level list paths that bypass __getitem__ must not leak raw device
    # arrays: materialize everything first, then defer to list
    def pop(self, i=-1):
        self._materialize_all()
        return list.pop(self, i)

    def copy(self):
        return list(self._materialize_all())

    def index(self, *a):
        return list.index(self._materialize_all(), *a)

    def remove(self, v):
        return list.remove(self._materialize_all(), v)

    def __reversed__(self):
        return list.__reversed__(self._materialize_all())

    def count(self, v):
        return list.count(self._materialize_all(), v)

    def __contains__(self, v):
        return list.__contains__(self._materialize_all(), v)

    def __eq__(self, other):
        return list.__eq__(self._materialize_all(), other)

    __hash__ = None

    def __add__(self, other):
        return list(self._materialize_all()) + list(other)

    def __radd__(self, other):
        return list(other) + list(self._materialize_all())

    def __mul__(self, n):
        return list.__mul__(self._materialize_all(), n)

    __rmul__ = __mul__

    def __repr__(self):
        return list.__repr__(self._materialize_all())

    def __reduce__(self):  # pickle ships numpy, never device handles
        return (list, (list(self._materialize_all()),))


def _feed_shape(v):
    """Shape of a feed value without materializing it (Tensor, device
    array, ndarray, or nested list) — the memory-admission cache key's
    per-run component, so it must stay allocation-light."""
    a = getattr(v, "_array", None)
    if a is not None:
        return tuple(a.shape)
    s = getattr(v, "shape", None)
    if s is not None:
        return tuple(s)
    return tuple(np.shape(v))


def _plan_key(program):
    tok = getattr(program, "_identity_token", None)
    if tok is None:
        tok = id(program)
    return (tok, program._version)


class RunPlan:
    """Static dispatch plan for one (program identity, version), computed
    once and reused by every ``run()`` on that program state.

    Everything the executor used to re-derive per call by walking all ops
    — the referenced-persistable analysis, the statically-written
    persistable set (the donation candidates), rng-id assignment, the
    captured-constant list — lives here, so the steady-state hot path
    reduces to dict lookups plus the jitted call (TVM's split of one-time
    compilation from cheap repeated dispatch, arXiv 1802.04799). Any
    program mutation that matters goes through ``append_op``/
    ``_new_block``, which bump ``_version`` and so key a fresh plan;
    flipping a var's ``persistable`` flag without adding ops is the one
    mutation this cache cannot see.
    """

    __slots__ = ("key", "block", "op_list", "persist_candidates",
                 "written_names", "constants")

    def __init__(self, program):
        self.key = _plan_key(program)
        block = program.global_block()
        self.block = block
        self.op_list = list(block.ops)
        self.constants = list(getattr(program, "_constants", {}).items())

        # ONE walk over all ops (incl. nested control-flow blocks) collects
        # what three walks used to: referenced names, written persistables,
        # and rng-id assignment state.
        referenced = {}  # name -> owning block for persistable lookup
        written = set()
        next_id = 0
        rng_missing = []
        for blk, op in _walk_ops(program, 0):
            for names in list(op.inputs.values()) + list(op.outputs.values()):
                for n in names:
                    referenced.setdefault(n, blk)
            for n in op_out_names(op):
                if n and blk.has_var(n) and blk.var(n).persistable:
                    written.add(n)
            rid = op.attrs.get("__rng_id__")
            if rid is not None:
                next_id = max(next_id, rid + 1)
            elif op.attrs.get("__rng__"):
                rng_missing.append(op)
        # rng ids are assigned at build time (op_append.py) so grad ops
        # share their forward op's id; assign here only for ops that
        # predate that (e.g. hand-built/deserialized programs)
        for op in rng_missing:
            op.attrs["__rng_id__"] = next_id
            next_id += 1

        # persistable vars any op touches: the per-run persist_in is this
        # list filtered by scope membership — no op traversal at dispatch
        self.persist_candidates = tuple(sorted(
            n for n, blk in referenced.items()
            if blk.has_var(n) and blk.var(n).persistable
        ))
        self.written_names = frozenset(written)


class _BlockRunner:
    """Traces a program's ops into jax, recursively through sub-blocks."""

    def __init__(self, program):
        self.program = program
        self._pw_cache = {}

    # -- control-flow lowering ---------------------------------------------

    # salt folded into the key chain at every loop entry so nested loops
    # (scan-in-scan, dropout-under-cond-in-while) never reuse a key path
    _LOOP_SALT = 0x6F09

    def _persist_writes(self, blk):
        """Persistable vars written by the block's ops (recursing into
        nested control-flow blocks, whose writes propagate out the same
        way) — the scope-threading set for executor.cc:428-style scope
        semantics: these become extra block outputs so the update reaches
        the top-level Scope instead of dying with the sub-block."""
        if blk.idx in self._pw_cache:
            return self._pw_cache[blk.idx]
        names = []
        for op in blk.ops:
            if op.type in _BLOCK_OPS:
                for key in ("__body_block__", "__true_block__",
                            "__false_block__", "__cond_block__"):
                    bidx = op.attrs.get(key)
                    if bidx is not None:
                        names.extend(
                            self._persist_writes(self.program.blocks[bidx])
                        )
                continue
            for n in op_out_names(op):
                if n and blk.has_var(n) and blk.var(n).persistable:
                    names.append(n)
        out = sorted(set(names))
        self._pw_cache[blk.idx] = out
        return out

    def _record_pw(self, pw, values, env, written_persist):
        for n, v in zip(pw, values):
            env[n] = v
            if written_persist is not None:
                written_persist[n] = v

    def _run_while(self, op, env, base_key, outer_it=None,
                   written_persist=None):
        attrs = op.attrs
        n_loop = attrs["__n_loop__"]
        in_names = op.inputs["X"]
        loop_in = in_names[:n_loop]
        cond_blk = self.program.blocks[attrs["__cond_block__"]]
        body_blk = self.program.blocks[attrs["__body_block__"]]
        pw = self._persist_writes(body_blk)

        if outer_it is not None:
            base_key = jax.random.fold_in(base_key, outer_it)
        loop_key = jax.random.fold_in(base_key, self._LOOP_SALT)
        init = tuple(env[n] for n in loop_in)
        pw_init = tuple(env[n] for n in pw)

        def cond_f(carry_it):
            it, carry, pw_vals = carry_it
            sub = dict(env)
            sub.update(zip(pw, pw_vals))
            sub.update(zip(attrs["__cond_formals__"], carry))
            # None: a persistable write in a while's *condition* block has
            # no carry slot — it still fails loudly
            self.exec_ops(cond_blk.ops, sub,
                          jax.random.fold_in(loop_key, it), None,
                          block=cond_blk)
            pred = sub[attrs["__cond_out__"]]
            return jnp.reshape(pred, ()).astype(bool)

        def body_f(carry_it):
            it, carry, pw_vals = carry_it
            sub = dict(env)
            sub.update(zip(pw, pw_vals))
            sub.update(zip(attrs["__body_formals__"], carry))
            # per-iteration key: stochastic ops (sampling decoders) draw
            # fresh randomness each step, including in nested blocks
            self.exec_ops(body_blk.ops, sub,
                          jax.random.fold_in(loop_key, it), {},
                          block=body_blk)
            return (it + 1, tuple(sub[n] for n in attrs["__body_outs__"]),
                    tuple(sub[n] for n in pw))

        _, final, pw_final = lax.while_loop(
            cond_f, body_f, (jnp.asarray(0, jnp.int32), init, pw_init)
        )
        self._record_pw(pw, pw_final, env, written_persist)
        return list(final)

    def _run_cond(self, op, env, base_key, outer_it=None,
                  written_persist=None):
        attrs = op.attrs
        pred = env[op.inputs["X"][0]]
        true_blk = self.program.blocks[attrs["__true_block__"]]
        false_blk = self.program.blocks[attrs["__false_block__"]]
        # union: a branch that does not write a stat passes it through, so
        # both lax.cond branches emit the same structure
        pw = sorted(set(self._persist_writes(true_blk))
                    | set(self._persist_writes(false_blk)))

        def branch(blk, out_names):
            def f():
                sub = dict(env)
                # iteration context passes straight through a branch
                self.exec_ops(blk.ops, sub, base_key, {}, block=blk,
                              iter_idx=outer_it)
                return (tuple(sub[n] for n in out_names)
                        + tuple(sub[n] for n in pw))
            return f

        outs = lax.cond(
            jnp.reshape(pred, ()).astype(bool),
            branch(true_blk, attrs["__true_outs__"]),
            branch(false_blk, attrs["__false_outs__"]),
        )
        n_reg = len(outs) - len(pw)
        self._record_pw(pw, outs[n_reg:], env, written_persist)
        return list(outs[:n_reg])

    def _run_scan(self, op, env, base_key, outer_it=None,
                  written_persist=None):
        attrs = op.attrs
        n_c, n_s = attrs["__n_carry__"], attrs["__n_seq__"]
        in_names = op.inputs["X"]
        body_blk = self.program.blocks[attrs["__body_block__"]]
        pw = self._persist_writes(body_blk)

        if outer_it is not None:
            base_key = jax.random.fold_in(base_key, outer_it)
        loop_key = jax.random.fold_in(base_key, self._LOOP_SALT)
        init = tuple(env[n] for n in in_names[:n_c])
        seqs = tuple(env[n] for n in in_names[n_c:n_c + n_s])
        pw_init = tuple(env[n] for n in pw)

        def body_f(carry_it, xs):
            it, carry, pw_vals = carry_it
            sub = dict(env)
            sub.update(zip(pw, pw_vals))
            sub.update(zip(attrs["__carry_formals__"], carry))
            sub.update(zip(attrs["__seq_formals__"], xs or ()))
            self.exec_ops(body_blk.ops, sub,
                          jax.random.fold_in(loop_key, it), {},
                          block=body_blk)
            new_carry = tuple(sub[n] for n in attrs["__carry_outs__"])
            y = tuple(sub[n] for n in attrs["__y_outs__"])
            return (it + 1, new_carry, tuple(sub[n] for n in pw)), y

        (_, final, pw_final), ys = lax.scan(
            body_f, (jnp.asarray(0, jnp.int32), init, pw_init),
            seqs if seqs else None, length=attrs.get("__length__"),
        )
        self._record_pw(pw, pw_final, env, written_persist)
        return list(final) + list(ys)

    def _block_op_closure(self, op, env, base_key, outer_it=None):
        """Pure fn over the op's explicit inputs, for jax.vjp (grad ops)."""
        in_names = op.inputs["X"]

        def closure(*arrays):
            local = dict(env)
            local.update(zip(in_names, arrays))
            if op.type == "cond":
                outs = self._run_cond(op, local, base_key, outer_it)
            elif op.type == "scan":
                outs = self._run_scan(op, local, base_key, outer_it)
            else:  # while
                outs = self._run_while(op, local, base_key, outer_it)
            return tuple(outs)

        return closure

    # -- main interpreter ---------------------------------------------------

    def exec_ops(self, op_list, env, base_key, written_persist, block=None,
                 iter_idx=None):
        for op_index, op in enumerate(op_list):
            try:
                self._exec_one(op, env, base_key, written_persist, block,
                               iter_idx, op_index)
            except Exception as e:
                # PADDLE_ENFORCE behavior (platform/enforce.h): append the
                # failing op's context to the message, preserving the
                # original exception type; innermost op wins for nested
                # control-flow blocks
                marker = "[operator <"
                if e.args and isinstance(e.args[0], str) and marker in e.args[0]:
                    raise
                ctx = (
                    f"[operator < {op.type} > error] "
                    f"inputs={op.inputs.get('X', [])} "
                    f"outputs={op.outputs.get('Out', [])}"
                )
                head = e.args[0] if e.args else ""
                e.args = (f"{head}\n  {ctx}",) + tuple(e.args[1:])
                raise

    def _exec_one(self, op, env, base_key, written_persist, block=None,
                  iter_idx=None, op_index=None):
            in_names = op_in_names(op)
            out_names = op_out_names(op)
            attrs = {k: v for k, v in op.attrs.items() if not k.startswith("__")}

            if op.type in _BLOCK_OPS:
                results = getattr(self, f"_run_{op.type}")(
                    op, env, base_key, iter_idx,
                    written_persist=written_persist,
                )
            elif op.type.startswith("grad::"):
                fwd_type = op.type[len("grad::"):]
                n_in = op.attrs["__n_fwd_in__"]
                fwd_in = [env[n] for n in in_names[:n_in]]
                out_grad_names = in_names[n_in:]
                if fwd_type in _BLOCK_OPS:
                    if fwd_type == "while":
                        raise RuntimeError(
                            "while_loop is not reverse-differentiable on "
                            "XLA (unbounded trip count); build trainable "
                            "loops with paddle_tpu.static.nn.scan instead"
                        )
                    # the grad op carries the forward op's attrs (incl. the
                    # sub-block indices) and its input list is the forward
                    # X — enough to rebuild the forward closure
                    from .program import OpDesc

                    fwd_op = OpDesc(
                        fwd_type, {"X": in_names[:n_in]}, {"Out": []},
                        op.attrs,
                    )
                    fwd_fn = self._block_op_closure(
                        fwd_op, env, base_key, iter_idx
                    )
                    outs, vjp_fn = jax.vjp(fwd_fn, *fwd_in)
                else:
                    f_attrs = dict(attrs)
                    f_attrs.pop("__rng__", None)
                    if op.attrs.get("__rng__"):
                        f_attrs["key"] = _op_key(base_key, op, iter_idx)
                    fwd_fn = kernel(fwd_type)
                    outs, vjp_fn = jax.vjp(partial(fwd_fn, **f_attrs), *fwd_in)
                outs_list = list(outs) if isinstance(outs, (tuple, list)) else [outs]
                cots = []
                for i, o in enumerate(outs_list):
                    gname = out_grad_names[i] if i < len(out_grad_names) else ""
                    if gname and gname in env:
                        cots.append(env[gname].astype(o.dtype))
                    elif jnp.issubdtype(o.dtype, np.floating):
                        cots.append(jnp.zeros(o.shape, o.dtype))
                    else:
                        cots.append(np.zeros(o.shape, dtype=jax.dtypes.float0))
                if fwd_type in _BLOCK_OPS:
                    cot = tuple(cots)  # closure output is always a tuple
                else:
                    cot = tuple(cots) if len(cots) > 1 else cots[0]
                grads = vjp_fn(cot)
                results = []
                for g in grads:
                    results.append(
                        None if (g is None or g.dtype == jax.dtypes.float0) else g
                    )
            else:
                f_attrs = dict(attrs)
                if op.attrs.get("__rng__"):
                    f_attrs["key"] = _op_key(base_key, op, iter_idx)
                fn_k = kernel(op.type)
                arrays = [env[n] for n in in_names]
                # named_scope → HLO metadata, so device profiles attribute
                # fused kernels back to the framework op; the RecordEvent
                # costs only at trace time (once per compile) and gives the
                # reference-style per-op host table (profiler.h:126). The
                # scope carries the STAMPED identity op.type#<block>/<index>
                # (monitor/opprof grammar) so a trace row maps back to one
                # Program op, not just an op type — same-type ops in
                # different blocks stay distinguishable.
                scope_name = op.type if op_index is None else _op_scope(
                    op.type, block.idx if block is not None else 0, op_index)
                with RecordEvent(f"op::{op.type}"), \
                        jax.named_scope(scope_name):
                    out = fn_k(*arrays, **f_attrs)
                results = list(out) if isinstance(out, (tuple, list)) else [out]

            for name, value in zip(out_names, results):
                if not name or value is None:
                    continue
                env[name] = value
                if block is None:
                    continue
                if block.has_var(name) and block.var(name).persistable:
                    if written_persist is None:
                        # a context with no write-back path (a while's
                        # condition block): fail loudly instead of
                        # silently dropping the update
                        raise NotImplementedError(
                            f"op {op.type!r} writes persistable var "
                            f"{name!r} inside a while-condition block; "
                            "stateful updates belong in the loop body"
                        )
                    # sub-block writes reach the Scope via the enclosing
                    # cond/scan/while op's persist-thread outputs
                    # (_persist_writes), matching the reference executor's
                    # scope write-through (executor.cc:428)
                    written_persist[name] = value


def _trace_block(program, block, op_list, feed_names, fetch_names,
                 donate_names, hold_names):
    """Build the pure function for the top block. Returns
    fn(feeds, donated, held, key) -> (fetches, donated_out, extra_written).

    ``donated`` carries the persistable inputs the jit donates (the
    statically-written ones): their updated values ALWAYS come back,
    positionally, in ``donated_out``, so XLA aliases each update into its
    now-dead input buffer — parameters and optimizer state update in place
    instead of doubling HBM traffic each step. ``held`` carries read-only
    persistables (never donated, never returned). ``extra_written`` holds
    persistable writes outside the donated set (vars the run creates that
    were absent from the scope, or all writes when donation is off)."""
    runner = _BlockRunner(program)
    donate_set = frozenset(donate_names)

    def fn(feed_arrays, donated, held, base_key):
        env = {}
        env.update(zip(feed_names, feed_arrays))
        env.update(zip(donate_names, donated))
        env.update(zip(hold_names, held))
        written_persist = {}
        runner.exec_ops(op_list, env, base_key, written_persist, block=block)
        fetches = [env[n] for n in fetch_names]
        # env[n] is the var's final value whether or not the op that
        # writes it ran this trace (grad ops may emit None): a donated
        # input must always have an output aliased onto it
        donated_out = [env[n] for n in donate_names]
        extra = {n: v for n, v in written_persist.items()
                 if n not in donate_set}
        return fetches, donated_out, extra

    return fn


class Executor:
    """fluid.Executor equivalent. Two-level cache: a RunPlan per (program
    identity, version) holds the one-time op-walk analysis; compiled
    executables are keyed separately by (plan key, fetch/feed/persist
    signature) in the SHARED compiled-callable runtime
    (:mod:`paddle_tpu.runtime.compiled`) so re-feeding new shapes
    recompiles without re-planning — and so AOT compile, cost capture,
    LRU bounding, and the donation-safe demote-to-jit fallback follow
    the one policy every dispatch site shares."""

    def __init__(self, place: Place | None = None):
        self.place = place or _default_place()
        # the compiled-block cache: serving replica pools run one
        # Executor from N worker threads (Predictor.clone shares it so
        # compiles are shared) — the store's bookkeeping lock makes the
        # LRU pop-and-reinsert safe while dispatch stays unlocked
        # (concurrent device execution is the point of the pool)
        self._compiled = CompiledStore(
            "executor", cost_label="executor",
            hit_counter="executor::jit_cache_hit",
            miss_counter="executor::jit_cache_miss")
        self._plans = {}
        self._plan_cache_limit = 64  # RunPlan LRU bound
        self._cache_lock = threading.Lock()  # RunPlan bookkeeping

    # legacy cache surface (tests and notebooks poke these): a LIVE
    # mutable view of the entries (clear/del invalidate for real, so
    # the historical force-a-recompile workflow still works) and the
    # flag-governed LRU bound, both owned by the shared runtime store
    @property
    def _cache(self):
        return self._compiled.mapping()

    @property
    def _cache_limit(self):
        return self._compiled.capacity

    @_cache_limit.setter
    def _cache_limit(self, value):
        self._compiled.capacity = value

    def _plan_for(self, program):
        """RunPlan cache lookup (LRU, counter-instrumented). Returns
        (plan, "hit"|"miss") so run() can put the cache disposition in
        the flight-recorder event without re-deriving it."""
        key = _plan_key(program)
        with self._cache_lock:
            plan = self._plans.get(key)
            if plan is not None:
                self._plans[key] = self._plans.pop(key)  # refresh LRU order
                bump_counter("executor::plan_cache_hit")
                return plan, "hit"
        bump_counter("executor::plan_cache_miss")
        plan = RunPlan(program)
        with self._cache_lock:
            self._plans[key] = plan
            while len(self._plans) > self._plan_cache_limit:
                self._plans.pop(next(iter(self._plans)))
        return plan, "miss"

    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True):
        program = program or default_main_program()
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope or global_scope()

        fetch_names = [v if isinstance(v, str) else v.name for v in fetch_list]
        feed_names = sorted(feed.keys())

        # IR verification gate (FLAGS_program_verify): a malformed program
        # fails HERE with the op index/type/var named — before any plan,
        # trace, or XLA lowering sees it. The verdict caches on the
        # Program per version (program.py Program.verify), so the steady
        # state pays one flag read + one dict lookup; failures also land
        # in the flight recorder as `program_verify` events. The gate
        # judges the program AS WRITTEN — the IR optimizer below runs
        # after it, so strict-mode findings (e.g. dead code) reject
        # before any rewrite could paper over them.
        verify_level = str(flag("program_verify")).strip().lower()
        if verify_level not in ("", "0", "off", "false", "no"):
            with RecordEvent("executor::program_verify"):
                program.verify(
                    feed_names=feed_names, fetch_list=fetch_names,
                    level="strict" if verify_level == "strict" else "on")

        # IR optimizer gate (FLAGS_ir_opt_level): rewrite the program onto
        # the fused registry kernels (+ DCE, + remat at level 2) BEFORE the
        # memplan gate and lowering, so admission and compilation see what
        # will actually run. optimize_program clones (the caller's program
        # is never mutated), caches per program version, and hands back
        # the ORIGINAL object when nothing was rewritten — so the
        # RunPlan/compile caches below key on a stable identity either way.
        try:
            ir_level = int(str(flag("ir_opt_level")).strip() or "0")
        except ValueError:
            ir_level = 0
        if ir_level > 0:
            from ..analysis import optimizer as _iropt

            with RecordEvent("executor::ir_opt"):
                program = _iropt.optimize_program(
                    program, feed_names, fetch_names, level=ir_level,
                    feed_shapes={n: _feed_shape(feed[n])
                                 for n in feed_names}).program

        # Static peak-HBM admission (FLAGS_memory_budget_check): plan the
        # program's liveness footprint and compare it against the device
        # HBM budget BEFORE any plan/lower/compile — an over-budget
        # program (or a liveness-unsafe donation) fails here with the
        # high-water op and top tensors named instead of OOMing
        # mid-compile. Verdicts cache per program version (the verifier-
        # cache discipline), so steady state pays feed-shape tuples plus
        # one dict lookup.
        mem_plan = None
        budget_level = str(flag("memory_budget_check")).strip().lower()
        if budget_level not in ("", "0", "off", "false", "no"):
            from ..analysis import memory as _memory

            feed_shapes = {n: _feed_shape(feed[n]) for n in feed_names}
            with RecordEvent("executor::memory_plan"):
                mem_plan = _memory.check_memory_budget(
                    program, feed_names, fetch_names,
                    feed_shapes=feed_shapes,
                    level="strict" if budget_level == "strict"
                    else "warn")

        with RecordEvent("executor::plan"):
            plan, plan_disposition = self._plan_for(program)
            block = plan.block

            # init captured constants
            for cname, cval in plan.constants:
                if not scope.has(cname):
                    scope.set(cname, cval)

        with RecordEvent("executor::feed"):  # H2D feed staging
            feed_arrays = []
            for n in feed_names:
                v = feed[n]
                if isinstance(v, Tensor):
                    arr = v._array
                elif isinstance(v, jax.Array):
                    arr = v  # device-resident feed (prefetch path): as is
                else:
                    arr = jnp.asarray(np.asarray(
                        v,
                        dtype=block.var(n).dtype if block.has_var(n) else None,
                    ))
                feed_arrays.append(arr)

        with RecordEvent("executor::dispatch_prep"):
            # persistable inputs: the plan's candidates filtered by scope
            # membership — dict lookups only, no op traversal
            persist_in = tuple(
                n for n in plan.persist_candidates
                if n not in feed and scope.has(n)
            )

            # the donation flag is part of the key: toggling it at runtime
            # (the documented debugging workflow) must not silently reuse
            # an entry compiled with the other donation mode
            donate_enabled = bool(flag("executor_buffer_donation"))
            sig = (
                plan.key, tuple(fetch_names), tuple(feed_names),
                tuple((tuple(a.shape), str(a.dtype)) for a in feed_arrays),
                persist_in, donate_enabled,
            )
        def _build():
            # donation POLICY (shared flag semantics, one compile key):
            # donate the persistables the program statically writes
            # (params, optimizer state) — XLA aliases each update into
            # the input buffer. Read-only persistables are held
            # undonated.
            if donate_enabled:
                dn = tuple(
                    n for n in persist_in if n in plan.written_names)
            else:
                dn = ()
            hn = tuple(n for n in persist_in if n not in dn)
            traced = _trace_block(program, block, plan.op_list,
                                  feed_names, fetch_names, dn, hn)
            jitted = jax.jit(
                traced, donate_argnums=(1,) if dn else ())
            return jitted, (dn, hn)

        # the shared runtime owns the rest: LRU bookkeeping (thread-safe
        # for replica pools), the double-checked one-time AOT compile with
        # cost capture, and the donation-safe demote-to-jit fallback
        entry, jit_disposition = self._compiled.get_or_build(sig, _build)
        donate_names, hold_names = entry.meta
        first_run = jit_disposition == "miss"

        # flight-recorder breadcrumb: which program ran, and whether the
        # caches served it — a post-mortem can see a retrace storm (jit
        # misses racing run counts) or an unexpected re-plan at a glance.
        # cache_key is the shared runtime identity the CostRecord ledger
        # and /tracez cite for the same dispatch.
        program_id = f"{plan.key[0]}@v{plan.key[1]}"
        _flight.record_event(
            "executor_run_begin", program=program_id,
            plan_cache=plan_disposition, jit_cache=jit_disposition,
            cache_key=entry.cache_key,
            feeds=len(feed_names), fetches=len(fetch_names),
            donated=len(donate_names))
        # a serving dispatch (or any traced caller) sees compile-vs-
        # execute without threading a handle down here: the cache
        # disposition lands on whatever span is current (no-op outside
        # a trace — one contextvar read)
        _tracing.annotate(
            program=program_id, plan_cache=plan_disposition,
            jit_cache=jit_disposition, cache_key=entry.cache_key)

        donated = [scope.get(n) for n in donate_names]
        held = [scope.get(n) for n in hold_names]
        base_key = _random.split_key()
        # first run per signature traces + compiles (the per-op events fire
        # inside the trace); later runs are pure dispatch. The nested
        # jit_compile span isolates the XLA trace+compile cost from the
        # steady-state device step in the exported timeline.
        phase = "executor::compile_and_run" if first_run else "executor::run"
        # the dispatch span is steady-state ONLY: on first_run the same
        # interval is the jit_compile span, and letting dispatch wrap the
        # compile would skew its max/ave aggregates by orders of magnitude
        compile_span = (RecordEvent("executor::jit_compile") if first_run
                        else _NULL_CTX)
        dispatch_span = (_NULL_CTX if first_run
                         else RecordEvent("executor::dispatch"))
        try:
            with RecordEvent(phase), compile_span, dispatch_span:
                fetches, donated_out, extra = self._compiled.dispatch(
                    entry, feed_arrays, donated, held, base_key,
                    donated=donated,
                    capture_meta={"program": program_id})
        except Exception as e:
            _flight.record_event(
                "executor_run_error", program=program_id,
                error=f"{type(e).__name__}: {e}"[:500])
            if donate_names:
                # the donated scope buffers may already be consumed and
                # cannot be restored; say so instead of letting the next
                # scope.get surface a bare 'Array has been deleted'
                note = (
                    f"run() failed after donating {len(donate_names)} "
                    "persistable buffer(s); their scope state may be "
                    "invalidated. Re-run startup/state loading before "
                    "continuing, or set FLAGS_executor_buffer_donation=0 "
                    "to debug with donation off."
                )
                head = e.args[0] if e.args else ""
                e.args = (f"{head}\n  {note}",) + tuple(e.args[1:])
            raise
        # (the executed-work ledger bump and the trace's flops/cache_key
        # annotation happened inside the shared runtime's dispatch)
        if first_run and mem_plan is not None:
            # accuracy closure: the AOT compile just captured XLA's own
            # memory_analysis — ledger predicted-vs-actual so the planner
            # is certified against what the compiler actually built
            # (plan_accuracy on the CostRecord, /costz, /statz gauge)
            from ..analysis import memory as _memory

            _memory.note_actual(entry.record, mem_plan)
        if donate_names:
            bump_counter("executor::donated_buffers", len(donate_names))
            # a fetch may share its buffer with a value the scope holds and
            # donates NEXT run — directly (fetching a written persistable)
            # or via XLA output aliasing (fetching a no-op transform of
            # one). Sever every alias so fetch results survive and host
            # views of them stay stable; training fetches are small
            # (losses/metrics), so the copies are noise next to the step.
            fetches = [jnp.copy(f) for f in fetches]

        nan_scan = flag("check_nan_inf")
        if nan_scan and not donate_names:
            # nothing was donated: scan BEFORE writeback so a NaN abort
            # preserves the pre-step scope state for inspection (the
            # historical debugging behavior; with donation the pre-step
            # buffers are already dead, so writeback must come first)
            self._scan_nan_inf(program, fetch_names, fetches, extra)

        with RecordEvent("executor::writeback"):
            # Scope ownership transfer: the donated inputs are dead after
            # the call (XLA reused their buffers); the scope now owns the
            # returned arrays, so no stale reference survives for a later
            # read.
            for name, value in zip(donate_names, donated_out):
                scope.set(name, value)
            for name, value in extra.items():
                scope.set(name, value)

        if nan_scan and donate_names:
            # FLAGS_check_nan_inf: post-run scan of everything the block
            # produced, naming the first non-finite variable (the
            # variable-level analog of nan_inf_utils_detail.cc's per-op
            # output scan; the op is identified by its output var name)
            written_all = dict(zip(donate_names, donated_out))
            written_all.update(extra)
            self._scan_nan_inf(program, fetch_names, fetches, written_all)

        _flight.record_event("executor_run_end", program=program_id, ok=True)
        _flight.notify_progress("executor_run")

        if return_numpy:
            # lazy: the device->host sync happens at first element access,
            # so the caller can enqueue the next step first
            return _LazyFetchList(fetches)
        return [Tensor._from_array(f) for f in fetches]

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Drive the compiled step over a Dataset's batch stream
        (fluid/executor.py:1597 train_from_dataset).

        Where the reference hands the whole Dataset to C++ trainer threads
        (MultiTrainer), here the Dataset's parse workers stream fixed-shape
        batches (io/feed.py) and each batch runs through the jitted
        whole-block step — one compile, N dispatches. Batches are
        device-prefetched (DatasetBase._iter_device_batches) so batch
        N+1's H2D transfer overlaps step N's dispatch, and the lazy
        fetches only sync at print_period. Returns the number of batches
        consumed.
        """
        if dataset is None:
            raise ValueError("train_from_dataset needs a dataset")
        program = program or default_main_program()
        scope = scope or global_scope()
        if thread:
            dataset.set_thread(thread)
        fetch_list = fetch_list or []
        fetch_names = [v if isinstance(v, str) else v.name
                       for v in fetch_list]
        labels = fetch_info or fetch_names
        feed_names = dataset._feed_names()
        n = 0
        batches = (dataset._iter_device_batches()
                   if hasattr(dataset, "_iter_device_batches")
                   else dataset._iter_batches())
        for batch in batches:
            feed = dict(zip(feed_names, batch))
            fetches = self.run(program, feed=feed, fetch_list=fetch_list,
                               scope=scope)
            n += 1
            if fetch_list and (debug or n % print_period == 0):
                msg = ", ".join(
                    f"{lbl}={np.asarray(v).ravel()[:4]}"
                    for lbl, v in zip(labels, fetches)
                )
                print(f"[train_from_dataset] batch {n}: {msg}")
        return n

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Inference twin of train_from_dataset (fluid/executor.py:1658);
        identical driving loop — the program simply contains no optimizer
        ops."""
        return self.train_from_dataset(
            program, dataset, scope, thread, debug, fetch_list, fetch_info,
            print_period,
        )

    @staticmethod
    def _scan_nan_inf(program, fetch_names, fetches, written):
        from ..errors import FatalError, op_error_context

        def first_bad(named):
            for name, arr in named:
                a = np.asarray(arr)
                if np.issubdtype(a.dtype, np.floating) and not np.all(
                    np.isfinite(a)
                ):
                    return name
            return None

        bad = first_bad(
            list(zip(fetch_names, fetches)) + list(written.items())
        )
        if bad is None:
            return
        # FLAGS_check_nan_inf_action decides what detection does (raise /
        # warn-and-continue / dump-then-raise) — shared policy with the
        # checkify train-step path, see flight_recorder.nan_event_action
        if _flight.nan_event_action(
                f"var:{bad}",
                f"variable {bad!r} contains NaN/Inf after the block ran",
        ) is None:
            return  # warn: the run continues
        producer = None
        for _, op in _walk_ops(program, 0):
            if bad in [n for ns in op.outputs.values() for n in ns]:
                producer = op
                break
        ctx = op_error_context(producer) if producer is not None else None
        raise FatalError(
            f"check_nan_inf: variable {bad!r} contains NaN/Inf after the "
            f"block ran",
            op_context=ctx,
        )

    # startup program: run initializer ops host-side (not jitted — once)
    def run_startup(self, startup_program=None, scope=None):
        startup_program = startup_program or default_startup_program()
        scope = scope or global_scope()
        block = startup_program.global_block()
        for op in block.ops:
            out_names = op.outputs.get("Out", [])
            if op.type == "init_param":
                init = op.attrs["initializer"]
                shape = op.attrs["shape"]
                dtype = op.attrs["dtype"]
                if not scope.has(out_names[0]):
                    scope.set(out_names[0], init(shape, dtype))
            else:
                fn = kernel(op.type)
                attrs = {k: v for k, v in op.attrs.items() if not k.startswith("__")}
                if op.attrs.get("__rng__"):
                    attrs["key"] = _random.split_key()
                arrays = [scope.get(n) for n in op.inputs.get("X", [])]
                out = fn(*arrays, **attrs)
                results = list(out) if isinstance(out, (tuple, list)) else [out]
                for n, v in zip(out_names, results):
                    if n:
                        scope.set(n, v)

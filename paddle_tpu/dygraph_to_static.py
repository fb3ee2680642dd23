"""Dygraph-to-static AST transforms (value-dependent control flow).

Reference parity: python/paddle/fluid/dygraph/dygraph_to_static/ — the
AST transformer stack (ifelse_transformer.py, loop_transformer.py,
logical_transformer.py, program_translator.py). The reference rewrites
Python `if`/`while`/`and`/`or` over Variables into conditional_block /
while ops; here they rewrite into runtime converter calls that dispatch
on tracedness:

- concrete (eager) values  → plain Python control flow, unchanged
  semantics;
- traced values (inside a compiled step / to_static trace) →
  lax.cond / lax.while_loop / jnp.logical_*, which is how XLA wants
  data-dependent control flow expressed.

Supported v1 surface (unsupported shapes are left untouched and only
fail if the predicate is actually traced, with a clear message):

- ``if``/``elif``/``else`` whose branches assign local names (the
  modified names become the merged outputs) or where both branches end
  in ``return``;
- ``while`` loops whose body assigns local names (the loop carry);
- ``and`` / ``or`` / ``not`` inside the transformed function.
"""
from __future__ import annotations

import ast
import functools
import inspect
import textwrap

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .framework.tensor import Tensor

__all__ = [
    "convert_ifelse",
    "convert_while_loop",
    "convert_logical_and",
    "convert_logical_or",
    "convert_logical_not",
    "convert_print",
    "convert_assert",
    "convert_cast",
    "convert_to_static",
    "UNDEF",
]


class _Undefined:
    """Sentinel for names not yet bound when a transformed control-flow
    region starts (the reference's UndefinedVar,
    dygraph_to_static/variable_trans_func.py)."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def __repr__(self):
        return "<paddle_tpu UNDEF>"

    def __bool__(self):
        raise NameError(
            "variable is used before assignment inside transformed "
            "control flow"
        )


UNDEF = _Undefined()


# ---------------------------------------------------------------------------
# runtime converters (dygraph_to_static/convert_operators.py equivalents)
# ---------------------------------------------------------------------------


def _arr(v):
    return v._array if isinstance(v, Tensor) else v


def _is_traced(v):
    return isinstance(_arr(v), jax.core.Tracer)


def _unwrap_tree(tree):
    return jax.tree_util.tree_map(
        _arr, tree, is_leaf=lambda x: isinstance(x, Tensor)
    )


def _canon(a):
    """Canonicalize python/weak scalar leaves to strong-typed arrays so
    lax.cond branch outputs and lax.while carries unify (a flag assigned
    ``True`` in one branch must match the carried bool[] in the other)."""
    if isinstance(a, (bool, int, float)) or (
        hasattr(a, "weak_type") and a.weak_type and getattr(a, "ndim", None) == 0
    ):
        arr = jnp.asarray(a)
        return lax.convert_element_type(arr, arr.dtype)  # strips weak_type
    return a


def _canon_tree(tree):
    return jax.tree_util.tree_map(_canon, tree)


def _rewrap_like(arrays, template):
    # None/UNDEF kept as leaves on both sides so positions stay aligned
    # when a branch merge produced a placeholder for a missing value
    is_leaf = lambda x: isinstance(x, Tensor) or x is None or x is UNDEF  # noqa: E731
    flat_t, treedef = jax.tree_util.tree_flatten(template, is_leaf=is_leaf)
    flat_a, _ = jax.tree_util.tree_flatten(arrays, is_leaf=is_leaf)
    out = [
        Tensor._from_array(a) if isinstance(t, Tensor) and a is not None else a
        for a, t in zip(flat_a, flat_t)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def convert_ifelse(pred, true_fn, false_fn, args=()):
    """ifelse_transformer target: branch on a maybe-traced predicate.

    ``args`` are the branch-local carries (names the branches modify),
    passed as parameters so self-referential updates like ``s = s + x``
    read the pre-branch value instead of an unbound closure local.
    """
    if not _is_traced(pred):
        p = _arr(pred)
        taken = bool(np.asarray(p)) if hasattr(p, "dtype") else bool(p)
        return true_fn(*args) if taken else false_fn(*args)
    p = jnp.reshape(_arr(pred), ()).astype(bool)

    # trace both branches; unify pytrees of Tensors/arrays. The first
    # trace of true_fn doubles as the Tensor-vs-array structure template
    # (no extra call — branches may be expensive to trace).
    sample = [None]

    def _missing(v):
        return v is None or v is UNDEF

    def mk(fn, capture=False, specs=None):
        def f(_):
            out = fn(*args)
            if capture:
                sample[0] = out
            res = _canon_tree(_unwrap_tree(out))
            if specs is not None:
                flat, td = jax.tree_util.tree_flatten(res, is_leaf=_missing)
                flat = [
                    (jnp.zeros(s.shape, s.dtype) if s is not None else None)
                    if _missing(x)
                    else (
                        x.astype(s.dtype)
                        if s is not None and hasattr(x, "astype")
                        and x.dtype != s.dtype else x
                    )
                    for x, s in zip(flat, specs)
                ]
                res = jax.tree_util.tree_unflatten(td, flat)
            return res
        return f

    def probe(fn):
        """Abstractly trace a branch, tolerating missing (None/UNDEF)
        leaves: returns (treedef, [spec-or-None per leaf])."""
        store = {}

        def g(_):
            res = _canon_tree(_unwrap_tree(fn(*args)))
            flat, td = jax.tree_util.tree_flatten(res, is_leaf=_missing)
            store["td"] = td
            store["missing"] = [_missing(x) for x in flat]
            return tuple(
                jnp.zeros((), jnp.float32) if _missing(x) else x
                for x in flat
            )

        ab = jax.eval_shape(g, None)
        return store["td"], [
            None if m else s for m, s in zip(store["missing"], ab)
        ]

    try:
        out = lax.cond(p, mk(true_fn, capture=True), mk(false_fn), None)
    except TypeError:
        # branch unification (the reference's RETURN_NO_VALUE /
        # variable_trans_func merging): dtype drift (`i + 1` promoting an
        # int32 carry under x64) unifies to the promoted dtype; a missing
        # value in one branch (early-return value / name unbound on the
        # not-taken path) gets a dead-path zero placeholder. Anything else
        # still raises loudly.
        td_t, specs_t = probe(true_fn)
        td_f, specs_f = probe(false_fn)
        if td_t != td_f:
            raise
        specs = []
        for a, b in zip(specs_t, specs_f):
            if a is None and b is None:
                specs.append(None)
            elif a is None or b is None:
                specs.append(b if a is None else a)
            else:
                if a.shape != b.shape:
                    raise
                specs.append(jax.ShapeDtypeStruct(
                    a.shape, jnp.promote_types(a.dtype, b.dtype)
                ))
        out = lax.cond(
            p, mk(true_fn, capture=True, specs=specs),
            mk(false_fn, specs=specs), None,
        )
    return _rewrap_like(out, sample[0])


def convert_while_loop(cond_fn, body_fn, loop_vars):
    """loop_transformer target: while over a maybe-traced condition.

    Note the XLA contract: a traced while_loop is not reverse-
    differentiable (use the scan construct for trainable loops).
    """
    if any(v is UNDEF for v in loop_vars) and not any(
        _is_traced(v) for v in loop_vars if v is not UNDEF
    ):
        # a name assigned inside the loop but unbound before it: in the
        # python path it binds on the first iteration. (In the traced path
        # below, the placeholder probe seeds it — or UNDEF.__bool__ raises
        # a clear NameError if the body reads it before assignment.)
        env = list(loop_vars)
        while bool(np.asarray(_arr(cond_fn(*env)))):
            out = body_fn(*env)
            env = list(out) if isinstance(out, tuple) else [out]
        return tuple(env) if len(env) > 1 else env[0]

    first = cond_fn(*loop_vars)
    if not _is_traced(first) and not any(_is_traced(v) for v in loop_vars):
        vars_ = tuple(loop_vars)
        while bool(np.asarray(_arr(cond_fn(*vars_)))):
            out = body_fn(*vars_)
            vars_ = tuple(out) if isinstance(out, tuple) else (out,)
        return vars_ if len(vars_) > 1 else vars_[0]

    template = tuple(loop_vars)
    init = tuple(_canon(_arr(v)) for v in loop_vars)

    def cond(c):
        vs = _rewrap_like(c, template)
        return jnp.reshape(_arr(cond_fn(*vs)), ()).astype(bool)

    def body(c):
        vs = _rewrap_like(c, template)
        out = body_fn(*vs)
        out = out if isinstance(out, tuple) else (out,)
        return tuple(_canon(_arr(v)) for v in out)

    # a missing carry (None/UNDEF — e.g. an early-return value assigned
    # only inside the loop): probe one body step for its concrete spec and
    # seed a dead-path zero placeholder, mirroring the reference's
    # fill_constant placeholder vars (variable_trans_func.py)
    missing = [
        i for i, v in enumerate(init) if v is None or v is UNDEF
    ]
    if missing:
        def _probe_body():
            out = body_fn(*template)
            out = out if isinstance(out, tuple) else (out,)
            flat = [_arr(v) for v in out]
            return tuple(
                jnp.zeros((), jnp.float32)
                if (x is None or x is UNDEF) else x
                for x in flat
            )

        ab = jax.eval_shape(_probe_body)
        init = tuple(
            jnp.zeros(ab[i].shape, ab[i].dtype) if i in missing else v
            for i, v in enumerate(init)
        )

    # unify carry dtypes with what one body step produces (e.g. `i + 1`
    # promoting an int32 init to int64 under x64); iterate to a fixpoint
    # since promoting the init can promote further body outputs
    for _ in range(3):
        out_shapes = jax.tree_util.tree_leaves(jax.eval_shape(body, init))
        changed = False
        new_init = []
        for a, s in zip(init, out_shapes):
            arr = jnp.asarray(a)
            if arr.dtype != s.dtype:
                pd = jnp.promote_types(arr.dtype, s.dtype)
                if pd != arr.dtype:
                    arr = arr.astype(pd)
                    changed = True
            new_init.append(arr)
        init = tuple(new_init)
        if not changed:
            break

    final = lax.while_loop(cond, body, init)
    out = _rewrap_like(final, template)
    return out if len(template) > 1 else out[0]


def convert_logical_and(x_fn, y_fn):
    x = x_fn()
    if not _is_traced(x):
        xa = _arr(x)
        if hasattr(xa, "dtype") and np.asarray(xa).size == 1:
            if not bool(np.asarray(xa)):
                return x  # python short-circuit semantics
            return y_fn()
        if not hasattr(xa, "dtype"):
            return x and y_fn()
    y = y_fn()
    return Tensor._from_array(
        jnp.logical_and(
            jnp.asarray(_arr(x)).astype(bool),
            jnp.asarray(_arr(y)).astype(bool),
        )
    )


def convert_logical_or(x_fn, y_fn):
    x = x_fn()
    if not _is_traced(x):
        xa = _arr(x)
        if hasattr(xa, "dtype") and np.asarray(xa).size == 1:
            if bool(np.asarray(xa)):
                return x
            return y_fn()
        if not hasattr(xa, "dtype"):
            return x or y_fn()
    y = y_fn()
    return Tensor._from_array(
        jnp.logical_or(
            jnp.asarray(_arr(x)).astype(bool),
            jnp.asarray(_arr(y)).astype(bool),
        )
    )


def convert_logical_not(x):
    if not _is_traced(x) and not hasattr(_arr(x), "dtype"):
        return not x
    return Tensor._from_array(jnp.logical_not(
        jnp.asarray(_arr(x)).astype(bool)
    ))


def convert_print(*args, **kwargs):
    """print_transformer target (dygraph_to_static/print_transformer.py):
    a print over traced values becomes a device-side debug print (the
    reference lowers to the Print op); plain python print otherwise.
    The traced path honors sep/end (jax.debug.print emits one line per
    call, so a non-default end is appended into the payload); the file
    kwarg only applies on the python path."""
    if any(_is_traced(a) for a in args):
        esc = lambda s: s.replace("{", "{{").replace("}", "}}")  # noqa: E731
        sep = esc(kwargs.get("sep", " "))
        end = kwargs.get("end", "\n")
        fmt = sep.join(["{}"] * len(args))
        if end != "\n":
            fmt += esc(end)
        jax.debug.print(fmt, *[_arr(a) for a in args])
    else:
        print(*args, **kwargs)


def convert_assert(cond, msg=None):
    """assert_transformer target: a traced assert becomes a host callback
    that raises when the condition is false at run time (the reference's
    Assert op PADDLE_ENFORCEs in-kernel); eager asserts stay python."""
    if not _is_traced(cond):
        c = _arr(cond)
        ok = bool(np.asarray(c)) if hasattr(c, "dtype") else bool(c)
        if not ok:
            raise AssertionError(msg if msg is not None else "assert failed")
        return

    def _check(ok):
        if not bool(np.asarray(ok)):
            raise AssertionError(
                msg if msg is not None
                else "Assert failed inside compiled function"
            )

    jax.debug.callback(_check, jnp.reshape(_arr(cond), ()).astype(bool))


_CAST_DTYPES = {"int": "int64", "float": "float32", "bool": "bool"}


def convert_cast(ty, x):
    """cast_transformer target: int(x)/float(x)/bool(x)/len(x) over a
    traced tensor become dtype casts / static shape reads (the reference
    rewrites them to cast ops); python builtins otherwise."""
    if ty == "len":
        a = _arr(x)
        if hasattr(a, "shape") and getattr(a, "ndim", 0) >= 1:
            return a.shape[0]  # shapes are static under XLA tracing
        return len(x)
    if _is_traced(x):
        return Tensor._from_array(_arr(x).astype(_CAST_DTYPES[ty]))
    return {"int": int, "float": float, "bool": bool}[ty](x)


# ---------------------------------------------------------------------------
# AST transformer (ifelse_transformer.py / loop_transformer.py)
# ---------------------------------------------------------------------------


def _assign_const(name, value):
    return ast.Assign(targets=[ast.Name(id=name, ctx=ast.Store())],
                      value=ast.Constant(value))


def _flag_guard(flags, body):
    """``if not (f1 or f2): body`` — skip-the-rest guard shared by the
    return and break/continue transformers."""
    test = ast.Name(id=flags[0], ctx=ast.Load())
    if len(flags) > 1:
        test = ast.BoolOp(
            op=ast.Or(),
            values=[ast.Name(id=f, ctx=ast.Load()) for f in flags],
        )
    return ast.If(
        test=ast.UnaryOp(op=ast.Not(), operand=test),
        body=body or [ast.Pass()], orelse=[],
    )


def _scan_bc(stmts):
    """(has_break, has_continue) bound to the CURRENT loop: descends ifs
    and with/try blocks but not nested loops or function scopes."""
    has_b = has_c = False
    for s in stmts:
        if isinstance(s, ast.Break):
            has_b = True
        elif isinstance(s, ast.Continue):
            has_c = True
        elif isinstance(s, ast.If):
            for blk in (s.body, s.orelse):
                b, c = _scan_bc(blk)
                has_b |= b
                has_c |= c
        elif isinstance(s, ast.With):
            b, c = _scan_bc(s.body)
            has_b |= b
            has_c |= c
        elif isinstance(s, ast.Try):
            for blk in [s.body, s.orelse, s.finalbody] + [h.body for h in s.handlers]:
                b, c = _scan_bc(blk)
                has_b |= b
                has_c |= c
    return has_b, has_c


def _bc_only_under_ifs(stmts):
    """True when every current-loop break/continue sits under plain
    if/else nesting (the supported shape); with/try wrapping keeps python
    semantics."""
    for s in stmts:
        if isinstance(s, (ast.With, ast.Try)):
            blks = [getattr(s, "body", [])]
            if isinstance(s, ast.Try):
                blks += [s.orelse, s.finalbody] + [h.body for h in s.handlers]
            if any(any(_scan_bc(b)) for b in blks):
                return False
        elif isinstance(s, ast.If):
            if not (_bc_only_under_ifs(s.body) and _bc_only_under_ifs(s.orelse)):
                return False
    return True


def _is_range_for(node):
    return (
        isinstance(node.target, ast.Name)
        and isinstance(node.iter, ast.Call)
        and isinstance(node.iter.func, ast.Name)
        and node.iter.func.id == "range"
        and not node.iter.keywords
        and 1 <= len(node.iter.args) <= 3
    )


def _range_for_to_while(node, uid):
    """Desugar ``for i in range(...)`` to the explicit while form (the
    loop_transformer.py for→while lowering), shared by the break/continue
    and control-flow phases so both see identical loop-variable semantics.
    Returns (prelude_stmts, while_node) or None when the step is
    dynamic/negative (python semantics kept)."""
    args = node.iter.args
    start = args[0] if len(args) >= 2 else ast.Constant(0)
    stop = args[1] if len(args) >= 2 else args[0]
    step = args[2] if len(args) == 3 else ast.Constant(1)
    if len(args) == 3 and not (
        isinstance(step, ast.Constant) and isinstance(step.value, int)
        and step.value > 0
    ):
        return None
    it = f"_pt_for_{uid}"
    stop_name = f"_pt_stop_{uid}"
    init = ast.Assign(targets=[ast.Name(id=it, ctx=ast.Store())],
                      value=start)
    # snapshot the bound: python evaluates range() args exactly once, so a
    # body that mutates the bound variable must not change the trip count
    init_stop = ast.Assign(
        targets=[ast.Name(id=stop_name, ctx=ast.Store())], value=stop
    )
    # pre-bind the loop target ONLY if currently unbound (an empty range
    # must not clobber a prior value) — it then is a well-defined XLA
    # loop carry
    pre_bind = ast.Try(
        body=[ast.Assign(
            targets=[ast.Name(id=node.target.id, ctx=ast.Store())],
            value=ast.Name(id=node.target.id, ctx=ast.Load()),
        )],
        handlers=[ast.ExceptHandler(
            type=ast.Name(id="NameError", ctx=ast.Load()), name=None,
            body=[ast.Assign(
                targets=[ast.Name(id=node.target.id, ctx=ast.Store())],
                value=ast.Name(id=it, ctx=ast.Load()),
            )],
        )],
        orelse=[], finalbody=[],
    )
    test = ast.Compare(
        left=ast.Name(id=it, ctx=ast.Load()), ops=[ast.Lt()],
        comparators=[ast.Name(id=stop_name, ctx=ast.Load())],
    )
    bind = ast.Assign(
        targets=[node.target], value=ast.Name(id=it, ctx=ast.Load())
    )
    bump = ast.AugAssign(
        target=ast.Name(id=it, ctx=ast.Store()), op=ast.Add(), value=step
    )
    loop = ast.While(test=test, body=[bind] + node.body + [bump], orelse=[])
    return [init, init_stop, pre_bind], loop


class _ReturnTransformer(ast.NodeTransformer):
    """Early/mid-function returns (return_transformer.py): every
    ``return e`` becomes ``retv = e; retf = True`` (plus ``break`` when
    inside a loop), statements after a maybe-returning construct are
    guarded by ``if not retf``, and the function ends with a single
    ``return retv`` — so traced conditionals can merge return paths."""

    _counter = [0]

    def visit_FunctionDef(self, node):
        self.generic_visit(node)  # nested defs get their own flags first
        rets = [
            s for stmt in node.body for s in _walk_same_scope(stmt)
            if isinstance(s, ast.Return)
        ]
        if not rets or (len(rets) == 1 and node.body[-1] is rets[0]):
            return node
        self._counter[0] += 1
        uid = self._counter[0]
        flag, val = f"_pt_retf_{uid}", f"_pt_retv_{uid}"
        new_body, _ = self._rewrite(list(node.body), flag, val, in_loop=False)
        node.body = (
            [_assign_const(flag, False), _assign_const(val, None)]
            + new_body
            + [ast.Return(value=ast.Name(id=val, ctx=ast.Load()))]
        )
        ast.fix_missing_locations(node)
        return node

    @staticmethod
    def _contains_return(stmt):
        return any(isinstance(s, ast.Return) for s in _walk_same_scope(stmt))

    def _rewrite(self, stmts, flag, val, in_loop):
        out = []
        for i, s in enumerate(stmts):
            rest = stmts[i + 1:]
            if isinstance(s, ast.Return):
                out.append(ast.Assign(
                    targets=[ast.Name(id=val, ctx=ast.Store())],
                    value=s.value or ast.Constant(None),
                ))
                out.append(_assign_const(flag, True))
                if in_loop:
                    out.append(ast.Break())
                return out, True  # statements after a return are dead
            if isinstance(s, ast.If) and self._contains_return(s):
                s.body = self._rewrite(s.body, flag, val, in_loop)[0] or [ast.Pass()]
                s.orelse = self._rewrite(s.orelse, flag, val, in_loop)[0]
                out.append(s)
                if rest:
                    out.append(_flag_guard(
                        [flag], self._rewrite(rest, flag, val, in_loop)[0]
                    ))
                return out, True
            if isinstance(s, (ast.While, ast.For)) and self._contains_return(s):
                s.body = self._rewrite(s.body, flag, val, in_loop=True)[0]
                out.append(s)
                if in_loop:
                    # the return exited the INNER loop via break; the
                    # enclosing loop must stop too, or later outer
                    # iterations would overwrite the return value
                    out.append(ast.If(
                        test=ast.Name(id=flag, ctx=ast.Load()),
                        body=[ast.Break()], orelse=[],
                    ))
                if rest:
                    out.append(_flag_guard(
                        [flag], self._rewrite(rest, flag, val, in_loop)[0]
                    ))
                return out, True
            out.append(s)
        return out, False


class _BreakContinueTransformer(ast.NodeTransformer):
    """break/continue desugaring (break_continue_transformer.py):
    ``break`` sets a flag that both guards the rest of the iteration and
    joins the loop condition; ``continue`` sets a per-iteration flag that
    guards the rest of the iteration. The flag form contains no
    break/continue, so the control-flow transformer can lower the loop to
    lax.while_loop when values are traced."""

    _counter = [0]

    def visit_While(self, node):
        self.generic_visit(node)  # inner loops first
        has_b, has_c = _scan_bc(node.body)
        if not (has_b or has_c) or node.orelse:
            return node
        if not _bc_only_under_ifs(node.body):
            return node  # with/try-wrapped: keep python semantics
        self._counter[0] += 1
        uid = self._counter[0]
        brk = f"_pt_brk_{uid}" if has_b else None
        cnt = f"_pt_cnt_{uid}" if has_c else None
        new_body = self._rewrite(list(node.body), brk, cnt)
        prelude = []
        if cnt:
            new_body = [_assign_const(cnt, False)] + new_body
            # pre-loop binding so the flag is a well-formed XLA loop carry
            prelude.append(_assign_const(cnt, False))
        if brk:
            prelude.append(_assign_const(brk, False))
            node.test = ast.BoolOp(op=ast.And(), values=[
                ast.UnaryOp(op=ast.Not(),
                            operand=ast.Name(id=brk, ctx=ast.Load())),
                node.test,
            ])
        node.body = new_body
        out = prelude + [node]
        for x in out:
            ast.copy_location(x, node)
            ast.fix_missing_locations(x)
        return out

    def visit_For(self, node):
        self.generic_visit(node)
        has_b, has_c = _scan_bc(node.body)
        if not (has_b or has_c) or node.orelse:
            return node
        # only the range() form lowers further (the control-flow phase's
        # visit_For); anything else keeps python break/continue semantics
        # (incl. generators, which must not be exhausted past the break)
        if not _is_range_for(node):
            return node
        if not _bc_only_under_ifs(node.body):
            return node
        # two-phase: rewrite CONTINUE first, inside the for body only, so
        # the loop-variable bump added by the while desugar is NOT skipped
        # (python's continue still advances the iterator); then desugar to
        # the shared while form and let visit_While rewrite BREAK, which
        # must guard the bump (python's break leaves the loop variable at
        # its break-time value — `for i in range(10): if i == 3: break`
        # ends with i == 3, not 9)
        a = node.iter.args
        if len(a) == 3 and not (
            isinstance(a[2], ast.Constant) and isinstance(a[2].value, int)
            and a[2].value > 0
        ):
            return node  # dynamic/negative step: python semantics (checked
            # BEFORE any rewrite so a bail leaves the body untouched)
        prelude = []
        if has_c:
            self._counter[0] += 1
            cnt = f"_pt_cnt_bc{self._counter[0]}"
            body_c = self._rewrite(list(node.body), None, cnt)
            node.body = [_assign_const(cnt, False)] + body_c
            prelude.append(_assign_const(cnt, False))  # XLA carry init
        self._counter[0] += 1
        for_prelude, loop = _range_for_to_while(node, f"bc{self._counter[0]}")
        prelude = for_prelude + prelude
        res = self.visit_While(loop) if has_b else loop
        res = res if isinstance(res, list) else [res]
        out = prelude + res
        for x in out:
            ast.copy_location(x, node)
            ast.fix_missing_locations(x)
        return out

    def _rewrite(self, stmts, brk, cnt):
        """Flag-selective pass: a None flag leaves that statement kind in
        place for a later pass (visit_For rewrites continue before the
        for→while desugar so the loop-variable bump stays un-guarded, then
        visit_While rewrites break so the bump IS guarded)."""
        flags = [f for f in (brk, cnt) if f]
        out = []
        for i, s in enumerate(stmts):
            rest = stmts[i + 1:]
            if isinstance(s, ast.Break):
                if brk is None:
                    out.append(s)
                    continue
                out.append(_assign_const(brk, True))
                return out
            if isinstance(s, ast.Continue):
                if cnt is None:
                    out.append(s)
                    continue
                out.append(_assign_const(cnt, True))
                return out
            if isinstance(s, ast.If):
                hb, hc = _scan_bc([s])
                if (hb and brk) or (hc and cnt):
                    s.body = self._rewrite(s.body, brk, cnt) or [ast.Pass()]
                    s.orelse = self._rewrite(s.orelse, brk, cnt)
                    out.append(s)
                    if rest:
                        out.append(_flag_guard(
                            flags, self._rewrite(rest, brk, cnt)
                        ))
                    return out
            out.append(s)
        return out


def _walk_same_scope(node):
    """ast.walk that does NOT descend into nested function/class scopes
    (their locals are not this scope's assignments) — including when the
    root itself is one (a nested def appearing as a body statement)."""
    yield node
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                         ast.Lambda, ast.ClassDef)):
        return
    for child in ast.iter_child_nodes(node):
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
            continue
        yield from _walk_same_scope(child)


def _assigned_names(nodes):
    """Names bound by assignment/augassign within nodes (current scope)."""
    out = []
    for node in nodes:
        for sub in _walk_same_scope(node):
            if isinstance(sub, ast.Assign):
                for t in sub.targets:
                    out.extend(_target_names(t))
            elif isinstance(sub, (ast.AugAssign, ast.AnnAssign)):
                out.extend(_target_names(sub.target))
    seen = []
    for n in out:
        if n not in seen:
            seen.append(n)
    return seen


def _prelude(names):
    """`try: n = n / except NameError: n = _pt_jst.UNDEF` per name — the
    UndefinedVar seeding (variable_trans_func.py) so branch/loop closures
    can always read and return every merged name."""
    stmts = []
    for n in names:
        stmts.append(ast.Try(
            body=[ast.Assign(
                targets=[ast.Name(id=n, ctx=ast.Store())],
                value=ast.Name(id=n, ctx=ast.Load()),
            )],
            handlers=[ast.ExceptHandler(
                type=ast.Name(id="NameError", ctx=ast.Load()),
                name=None,
                body=[ast.Assign(
                    targets=[ast.Name(id=n, ctx=ast.Store())],
                    value=ast.Attribute(
                        value=ast.Name(id="_pt_jst", ctx=ast.Load()),
                        attr="UNDEF", ctx=ast.Load(),
                    ),
                )],
            )],
            orelse=[], finalbody=[],
        ))
    return stmts


def _target_names(t):
    if isinstance(t, ast.Name):
        return [t.id]
    if isinstance(t, (ast.Tuple, ast.List)):
        out = []
        for e in t.elts:
            out.extend(_target_names(e))
        return out
    return []


def _loaded_names(node):
    return {
        sub.id for sub in ast.walk(node)
        if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load)
    }


class _ControlFlowTransformer(ast.NodeTransformer):
    def __init__(self):
        self._n = 0

    def _uid(self):
        self._n += 1
        return self._n

    # -- if/else ------------------------------------------------------------
    def visit_If(self, node):
        self.generic_visit(node)
        uid = self._uid()

        def ends_in_return(body):
            return bool(body) and isinstance(body[-1], ast.Return)

        has_return = any(
            isinstance(s, ast.Return)
            for b in (node.body, node.orelse) for stmt in b
            for s in _walk_same_scope(stmt)
        )
        if has_return:
            # supported: both branches ARE a single return (the common
            # `if c: return a` / `else: return b` tail); otherwise leave
            # untouched (plain python — fails only on traced preds)
            if (
                len(node.body) == 1 and ends_in_return(node.body)
                and len(node.orelse) == 1 and ends_in_return(node.orelse)
            ):
                t = ast.Lambda(
                    args=_no_args(), body=node.body[0].value or
                    ast.Constant(None),
                )
                f = ast.Lambda(
                    args=_no_args(), body=node.orelse[0].value or
                    ast.Constant(None),
                )
                call = _call("convert_ifelse", [node.test, t, f])
                return ast.copy_location(ast.Return(value=call), node)
            return node

        modified = _assigned_names(node.body + node.orelse)
        if not modified:
            return node  # side-effect-only branches: leave to tracing

        tname, fname = f"_pt_true_{uid}", f"_pt_false_{uid}"
        ret = ast.Return(
            value=ast.Tuple(
                elts=[ast.Name(id=n, ctx=ast.Load()) for n in modified],
                ctx=ast.Load(),
            ) if len(modified) > 1 else ast.Name(id=modified[0],
                                                ctx=ast.Load())
        )
        # the modified names come in as PARAMETERS (seeded from the outer
        # scope) so branch bodies can read-then-write them
        branch_args = ast.arguments(
            posonlyargs=[], args=[ast.arg(arg=n) for n in modified],
            kwonlyargs=[], kw_defaults=[], defaults=[],
        )
        t_def = ast.FunctionDef(
            name=tname, args=branch_args,
            body=(node.body or [ast.Pass()]) + [ret],
            decorator_list=[], type_params=[],
        )
        f_def = ast.FunctionDef(
            name=fname, args=ast.arguments(
                posonlyargs=[], args=[ast.arg(arg=n) for n in modified],
                kwonlyargs=[], kw_defaults=[], defaults=[],
            ),
            body=(node.orelse or [ast.Pass()]) + [ret],
            decorator_list=[], type_params=[],
        )
        assign = ast.Assign(
            targets=[
                ast.Tuple(
                    elts=[ast.Name(id=n, ctx=ast.Store()) for n in modified],
                    ctx=ast.Store(),
                ) if len(modified) > 1 else ast.Name(id=modified[0],
                                                     ctx=ast.Store())
            ],
            value=_call(
                "convert_ifelse",
                [node.test, ast.Name(id=tname, ctx=ast.Load()),
                 ast.Name(id=fname, ctx=ast.Load()),
                 ast.Tuple(
                     elts=[ast.Name(id=n, ctx=ast.Load()) for n in modified],
                     ctx=ast.Load(),
                 )],
            ),
        )
        return [
            ast.copy_location(x, node)
            for x in _prelude(modified) + [t_def, f_def, assign]
        ]

    # -- while --------------------------------------------------------------
    def visit_While(self, node):
        self.generic_visit(node)
        # same-scope walk: the branch closures generated by visit_If contain
        # `return` statements that belong to THEIR scope, not the loop's
        if node.orelse or any(
            isinstance(s, (ast.Break, ast.Continue, ast.Return))
            for stmt in node.body for s in _walk_same_scope(stmt)
        ):
            return node  # unsupported: keep python semantics
        uid = self._uid()
        # the carry is EVERY name the body assigns — a write-only var's
        # final value must survive the loop for post-loop readers
        carry = _assigned_names(node.body)
        if not carry:
            return node

        args = ast.arguments(
            posonlyargs=[], args=[ast.arg(arg=n) for n in carry],
            kwonlyargs=[], kw_defaults=[], defaults=[],
        )
        cname, bname = f"_pt_wcond_{uid}", f"_pt_wbody_{uid}"
        c_def = ast.FunctionDef(
            name=cname, args=args,
            body=[ast.Return(value=node.test)], decorator_list=[],
            type_params=[],
        )
        ret = ast.Return(
            value=ast.Tuple(
                elts=[ast.Name(id=n, ctx=ast.Load()) for n in carry],
                ctx=ast.Load(),
            )
        )
        b_def = ast.FunctionDef(
            name=bname, args=args, body=node.body + [ret],
            decorator_list=[], type_params=[],
        )
        assign = ast.Assign(
            targets=[
                ast.Tuple(
                    elts=[ast.Name(id=n, ctx=ast.Store()) for n in carry],
                    ctx=ast.Store(),
                ) if len(carry) > 1 else ast.Name(id=carry[0],
                                                 ctx=ast.Store())
            ],
            value=_call(
                "convert_while_loop",
                [ast.Name(id=cname, ctx=ast.Load()),
                 ast.Name(id=bname, ctx=ast.Load()),
                 ast.Tuple(
                     elts=[ast.Name(id=n, ctx=ast.Load()) for n in carry],
                     ctx=ast.Load(),
                 )],
            ),
        )
        return [
            ast.copy_location(x, node)
            for x in _prelude(carry) + [c_def, b_def, assign]
        ]

    # -- for over range -----------------------------------------------------
    def visit_For(self, node):
        """``for i in range(...)`` desugars to the while form, which then
        lowers through visit_While (loop_transformer.py's for→while). The
        desugaring itself is shared with the break/continue phase
        (_range_for_to_while) so both phases agree on loop-variable
        semantics."""
        self.generic_visit(node)
        if (
            node.orelse
            or not _is_range_for(node)
            or any(
                isinstance(s, (ast.Break, ast.Continue, ast.Return))
                for stmt in node.body for s in _walk_same_scope(stmt)
            )
        ):
            return node
        uid = self._uid()
        lowered = _range_for_to_while(node, str(uid))
        if lowered is None:
            return node  # negative/dynamic step: keep python semantics
        prelude, loop = lowered
        res = self.visit_While(loop)
        res = res if isinstance(res, list) else [res]
        return [ast.copy_location(x, node) for x in prelude + res]

    # -- print / assert / casts ---------------------------------------------
    def visit_Call(self, node):
        self.generic_visit(node)
        if isinstance(node.func, ast.Name):
            if node.func.id == "print":
                return ast.copy_location(ast.Call(
                    func=ast.Attribute(
                        value=ast.Name(id="_pt_jst", ctx=ast.Load()),
                        attr="convert_print", ctx=ast.Load(),
                    ),
                    args=node.args, keywords=node.keywords,
                ), node)
            if (
                node.func.id in ("int", "float", "bool", "len")
                and len(node.args) == 1 and not node.keywords
                and not isinstance(node.args[0], ast.Starred)
            ):
                return ast.copy_location(
                    _call("convert_cast",
                          [ast.Constant(node.func.id), node.args[0]]),
                    node,
                )
        return node

    def visit_Assert(self, node):
        self.generic_visit(node)
        args = [node.test] + ([node.msg] if node.msg is not None else [])
        return ast.copy_location(
            ast.Expr(value=_call("convert_assert", args)), node
        )

    # -- and/or/not ---------------------------------------------------------
    def visit_BoolOp(self, node):
        self.generic_visit(node)
        fn = ("convert_logical_and" if isinstance(node.op, ast.And)
              else "convert_logical_or")
        out = node.values[-1]
        for v in reversed(node.values[:-1]):
            out = _call(
                fn,
                [ast.Lambda(args=_no_args(), body=v),
                 ast.Lambda(args=_no_args(), body=out)],
            )
        return ast.copy_location(out, node)

    def visit_UnaryOp(self, node):
        self.generic_visit(node)
        if isinstance(node.op, ast.Not):
            return ast.copy_location(
                _call("convert_logical_not", [node.operand]), node
            )
        return node


def _call(name, args):
    return ast.Call(
        func=ast.Attribute(
            value=ast.Name(id="_pt_jst", ctx=ast.Load()),
            attr=name, ctx=ast.Load(),
        ),
        args=args, keywords=[],
    )


def _no_args():
    return ast.arguments(posonlyargs=[], args=[], kwonlyargs=[],
                         kw_defaults=[], defaults=[])


_no_args_def = _no_args


def convert_to_static(fn):
    """Rewrite ``fn``'s control flow (program_translator.py role).

    Returns the transformed function, or ``fn`` unchanged when the
    source is unavailable or the transform does not apply.
    """
    try:
        src = textwrap.dedent(inspect.getsource(fn))
        tree = ast.parse(src)
        fdef = tree.body[0]
        fdef.decorator_list = []  # the decorator would recurse
        # phase order matters: returns become flag+break first, then
        # break/continue become flag+guard form, then control flow lowers
        # to the runtime converters (the reference stacks its transformers
        # the same way, program_translator.py transform pipeline)
        tree = _ReturnTransformer().visit(tree)
        tree = _BreakContinueTransformer().visit(tree)
        new = _ControlFlowTransformer().visit(tree)
        ast.fix_missing_locations(new)
        code = compile(new, f"<dygraph_to_static:{fn.__qualname__}>",
                       "exec")
        import sys

        this = sys.modules[__name__]
        glb = dict(fn.__globals__)
        glb["_pt_jst"] = this
        # freevars of the original become globals of the rebuilt module-
        # level def: seed them with the current cell contents (snapshot
        # semantics — the reference's ProgramTranslator captures the
        # same way)
        for name, cell in zip(fn.__code__.co_freevars,
                              fn.__closure__ or ()):
            try:
                glb[name] = cell.cell_contents
            except ValueError:
                pass  # empty cell (e.g. recursive self-reference)
        loc = {}
        exec(code, glb, loc)  # noqa: S102 — AST we just built
        transformed = loc[fdef.name]
        functools.update_wrapper(transformed, fn)
        transformed.__wrapped_original__ = fn
        return transformed
    except (OSError, TypeError, SyntaxError):
        return fn

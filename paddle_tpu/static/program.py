"""Static-graph Program IR.

Reference parity: paddle/fluid/framework/framework.proto:212 (ProgramDesc →
BlockDesc → OpDesc/VarDesc) and python/paddle/fluid/framework.py (Program/
Block/Variable). TPU-native: the IR is the unit of *capture*, not of
interpretation — the Executor lowers a whole block to one jax.jit'd XLA
module (SURVEY.md §7 step 2), so OpDesc stays lightweight (type, name-keyed
io maps, attrs) and per-op kernels are the registry's pure JAX functions.
Serialization via to_dict/from_dict + json (framework.proto equivalent).
"""
from __future__ import annotations

import contextlib
import itertools
import json
from typing import Any, Dict, List

import numpy as np

from ..framework.dtype import convert_dtype, dtype_name
from ..framework.tensor import Tensor


class VarDesc:
    def __init__(self, name, shape=None, dtype="float32", persistable=False,
                 stop_gradient=True, is_data=False):
        self.name = name
        self.shape = list(shape) if shape is not None else None
        self.dtype = dtype_name(convert_dtype(dtype))
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data

    def to_dict(self):
        return dict(name=self.name, shape=self.shape, dtype=self.dtype,
                    persistable=self.persistable, stop_gradient=self.stop_gradient,
                    is_data=self.is_data)

    @classmethod
    def from_dict(cls, d):
        return cls(**d)


class OpDesc:
    """type + name-keyed input/output lists + attrs (framework.proto:42)."""

    def __init__(self, op_type: str, inputs: Dict[str, List[str]],
                 outputs: Dict[str, List[str]], attrs: Dict[str, Any]):
        self.type = op_type
        self.inputs = inputs
        self.outputs = outputs
        self.attrs = dict(attrs)

    def input_names(self):
        return [n for ns in self.inputs.values() for n in ns]

    def output_names(self):
        return [n for ns in self.outputs.values() for n in ns]

    def to_dict(self):
        attrs = {}
        for k, v in self.attrs.items():
            if isinstance(v, np.ndarray):
                attrs[k] = {"__ndarray__": v.tolist(), "dtype": str(v.dtype)}
            else:
                attrs[k] = v
        return dict(type=self.type, inputs=self.inputs, outputs=self.outputs, attrs=attrs)

    @classmethod
    def from_dict(cls, d):
        attrs = {}
        for k, v in d["attrs"].items():
            if isinstance(v, dict) and "__ndarray__" in v:
                attrs[k] = np.asarray(v["__ndarray__"], dtype=v["dtype"])
            else:
                attrs[k] = v
        return cls(d["type"], d["inputs"], d["outputs"], attrs)


class Block:
    """BlockDesc (framework.proto:174): ordered op list + var map."""

    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = {}
        self.ops: List[OpDesc] = []

    # -- var management -----------------------------------------------------
    def create_var(self, name=None, shape=None, dtype="float32", persistable=False,
                   stop_gradient=True, is_data=False):
        name = name or self.program._unique_name("tmp")
        var = Variable(self, name, shape, dtype, persistable, stop_gradient, is_data)
        self.vars[name] = var
        return var

    def create_parameter(self, name, shape, dtype="float32", initializer=None,
                         trainable=True):
        var = self.create_var(name=name, shape=shape, dtype=dtype, persistable=True,
                              stop_gradient=not trainable)
        var.is_parameter = True
        var.initializer = initializer
        return var

    def var(self, name):
        blk = self
        while blk is not None:
            if name in blk.vars:
                return blk.vars[name]
            blk = self.program.blocks[blk.parent_idx] if blk.parent_idx >= 0 else None
        raise KeyError(f"variable {name!r} not found in block {self.idx}")

    def has_var(self, name):
        try:
            self.var(name)
            return True
        except KeyError:
            return False

    def append_op(self, op_type, inputs, outputs, attrs=None):
        op = OpDesc(op_type, inputs, outputs, attrs or {})
        self.ops.append(op)
        self.program._version += 1
        return op

    def to_dict(self):
        return dict(
            idx=self.idx,
            parent_idx=self.parent_idx,
            vars=[v.desc_dict() for v in self.vars.values()],
            ops=[op.to_dict() for op in self.ops],
        )


class Variable(Tensor):
    """Symbolic variable in a Block (fluid/framework.py Variable).

    Inherits Tensor so the whole mode-aware ops API (paddle_tpu.ops.*) can
    operate on it; storage-dependent members are overridden to be symbolic.
    """

    __slots__ = ("_meta",)

    def __init__(self, block, name, shape, dtype, persistable, stop_gradient, is_data):
        # No storage: bypass Tensor.__init__ entirely.
        self._array = None
        self.grad = None
        self.persistable = persistable
        self.name = name
        self._node = None
        self._out_index = 0
        self.stop_gradient = stop_gradient
        self._meta = dict(
            block=block, shape=list(shape) if shape is not None else None,
            dtype=dtype_name(convert_dtype(dtype)), is_data=is_data,
            is_parameter=False, initializer=None,
        )

    # symbolic metadata accessors -------------------------------------------
    @property
    def block(self):
        return self._meta["block"]

    @property
    def shape(self):
        return self._meta["shape"]

    @property
    def dtype(self):
        import jax.numpy as jnp

        return jnp.dtype(self._meta["dtype"])

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def size(self):
        return int(np.prod([d for d in self.shape])) if self.shape else 1

    @property
    def is_parameter(self):
        return self._meta["is_parameter"]

    @is_parameter.setter
    def is_parameter(self, v):
        self._meta["is_parameter"] = v

    @property
    def initializer(self):
        return self._meta["initializer"]

    @initializer.setter
    def initializer(self, v):
        self._meta["initializer"] = v

    def desc_dict(self):
        m = self._meta
        return VarDesc(self.name, m["shape"], m["dtype"], self.persistable,
                       self.stop_gradient, m["is_data"]).to_dict()

    # storage-dependent methods are invalid symbolically --------------------
    def numpy(self):
        raise RuntimeError(
            f"Variable {self.name!r} is symbolic; run it through an Executor to get values"
        )

    def item(self):
        raise RuntimeError("symbolic Variable has no value")

    def set_value(self, value):
        from .executor import global_scope

        arr = value.numpy() if isinstance(value, Tensor) else np.asarray(value)
        global_scope().set(self.name, arr)

    def get_value(self):
        from .executor import global_scope

        return Tensor(global_scope().get(self.name))

    def backward(self, *a, **k):
        raise RuntimeError("call paddle_tpu.static.append_backward on the loss instead")

    def __repr__(self):
        m = self._meta
        return f"Variable(name={self.name}, shape={m['shape']}, dtype={m['dtype']})"

    def __hash__(self):
        return id(self)


_program_token_counter = itertools.count()


class Program:
    """ProgramDesc (framework.proto:212)."""

    def __init__(self):
        self.blocks = [Block(self, 0)]
        self._name_counter = {}
        self._version = 0
        self.random_seed = None
        # process-unique identity for executor compile caching: id() can
        # be reused after GC, silently aliasing two programs at the same
        # version in the cache
        self._identity_token = next(_program_token_counter)

    def global_block(self) -> Block:
        return self.blocks[0]

    def verify(self, feed_names=(), fetch_list=(), level="on"):
        """Run the program-IR verifier (analysis/) over this program.

        Returns the :class:`~paddle_tpu.analysis.VerifyReport` when the
        program is well-formed (possibly carrying warnings); raises
        :class:`~paddle_tpu.analysis.VerifyError` naming the offending
        block/op index/op type/var otherwise. ``level="strict"``
        additionally promotes dead-code findings to errors.

        The verdict is cached per (program version, feeds, fetches,
        level) — any mutation through ``append_op``/``_create_block``
        bumps ``_version`` and re-verifies — so ``Executor.run``'s
        automatic call (``FLAGS_program_verify``) costs one dict lookup
        in steady state.
        """
        fetch_names = tuple(
            v if isinstance(v, str) else v.name for v in (fetch_list or ()))
        # var-count fingerprint: create_var does NOT bump _version (only
        # append_op/_create_block do), but adding a var can flip a verify
        # verdict — e.g. declaring the persistable a cached VerifyError
        # complained about. len(dict) is O(1), so this stays a few ns per
        # block. (A persistable-flag flip on an EXISTING var remains
        # invisible — the same documented blind spot as RunPlan's.)
        n_vars = sum(len(b.vars) for b in self.blocks)
        feeds = tuple(sorted(feed_names or ()))
        key = (self._version, n_vars, feeds, fetch_names, level)
        # __dict__ access: from_dict builds programs via __new__, so the
        # cache attr may not exist yet
        cache = self.__dict__.setdefault("_verify_cache", {})
        hit = cache.get(key)
        if hit is not None:
            # LRU refresh: without it a rotation of >capacity distinct
            # feed/fetch combos FIFO-thrashes and re-runs the full pass
            # (~ms) on every dispatch
            cache.pop(key, None)
            cache[key] = hit
            if isinstance(hit, Exception):
                # fresh traceback each raise: re-raising the cached
                # instance as-is would append frames to its __traceback__
                # forever (and share the mutable chain across threads)
                raise hit.with_traceback(None)
            return hit
        from ..analysis import VerifyError, verify_program

        try:
            report = verify_program(self, feeds, fetch_names, level)
        except VerifyError as e:
            self._verify_record(key, error=e)
            raise
        self._verify_record(key, report=report)
        return report

    def _verify_record(self, key, report=None, error=None):
        """Cache a verification verdict (bounded) + flight breadcrumb."""
        cache = self.__dict__.setdefault("_verify_cache", {})
        cache[key] = error if error is not None else report
        # LRU-bounded (hits move-to-end above); entries are small reports,
        # so the bound covers a predictor serving many fetch subsets
        while len(cache) > 64:
            # replica pools verify from N threads: a concurrent evict of
            # the same oldest key must be a no-op, not a KeyError
            try:
                cache.pop(next(iter(cache)), None)
            except (StopIteration, RuntimeError):
                break
        try:  # the black box must never break verification itself
            from ..monitor import flight_recorder as _flight

            tok = getattr(self, "_identity_token", None)
            fields = dict(
                program=f"{tok if tok is not None else id(self)}@v{key[0]}",
                ok=error is None,
                warnings=len(report.warnings) if report is not None else 0,
            )
            if error is not None:
                fields["error"] = str(error)[:500]
            _flight.record_event("program_verify", **fields)
        except Exception:
            pass

    def plan_memory(self, feed_names=(), fetch_list=(), feed_shapes=None,
                    top_k=8):
        """Static liveness + peak-HBM plan for this program
        (:func:`paddle_tpu.analysis.plan_memory`): predicted peak
        resident bytes, the high-water op index, the per-op resident
        curve, and the top-K largest live tensors — computed from the
        IR alone, before any lowering. ``feed_shapes`` (``{name: shape
        tuple}``) concretizes ``-1`` batch dims. ``Executor.run``
        enforces the device HBM budget against this plan behind
        ``FLAGS_memory_budget_check``."""
        from ..analysis import plan_memory as _plan

        fetch_names = tuple(
            v if isinstance(v, str) else v.name for v in (fetch_list or ()))
        return _plan(self, tuple(feed_names or ()), fetch_names,
                     feed_shapes=feed_shapes, top_k=top_k)

    def current_block(self) -> Block:
        return self.blocks[_current_block_idx[-1]] if _current_block_idx else self.blocks[0]

    def _create_block(self, parent_idx=None) -> Block:
        """New nested block (BlockDesc with parent, framework.proto:174) —
        the unit consumed by control-flow ops (while/cond/scan)."""
        parent = self.current_block().idx if parent_idx is None else parent_idx
        blk = Block(self, len(self.blocks), parent)
        self.blocks.append(blk)
        self._version += 1
        return blk

    def _unique_name(self, prefix):
        i = self._name_counter.get(prefix, 0)
        self._name_counter[prefix] = i + 1
        return f"{prefix}_{i}"

    def list_vars(self):
        for blk in self.blocks:
            yield from blk.vars.values()

    def all_parameters(self):
        return [v for v in self.list_vars() if getattr(v, "is_parameter", False)]

    def clone(self, for_test=False):
        data = self.to_dict()
        prog = Program.from_dict(data)
        if for_test:
            for blk in prog.blocks:
                for op in blk.ops:
                    if "training" in op.attrs:
                        op.attrs["training"] = False
        prog._name_counter = dict(self._name_counter)
        return prog

    # serialization ---------------------------------------------------------
    def to_dict(self):
        d = dict(blocks=[b.to_dict() for b in self.blocks], version=1)
        consts = getattr(self, "_constants", None)
        if consts:
            # captured eager constants (op_append.capture_constant) are part
            # of the program's meaning — without them a deserialized
            # program cannot run (every numpy literal in a control-flow
            # body becomes one)
            d["constants"] = {
                k: {"__ndarray__": np.asarray(v).tolist(),
                    "dtype": str(np.asarray(v).dtype)}
                for k, v in consts.items()
            }
        return d

    @classmethod
    def from_dict(cls, data):
        prog = cls.__new__(cls)
        prog.blocks = []
        prog._name_counter = {}
        prog._version = 0
        prog.random_seed = None
        for bd in data["blocks"]:
            blk = Block(prog, bd["idx"], bd["parent_idx"])
            prog.blocks.append(blk)
            for vd in bd["vars"]:
                v = VarDesc.from_dict(vd)
                var = Variable(blk, v.name, v.shape, v.dtype, v.persistable,
                               v.stop_gradient, v.is_data)
                blk.vars[v.name] = var
            blk.ops = [OpDesc.from_dict(od) for od in bd["ops"]]
        if data.get("constants"):
            prog._constants = {
                k: np.asarray(v["__ndarray__"], dtype=v["dtype"])
                for k, v in data["constants"].items()
            }
        return prog

    def serialize_to_string(self) -> bytes:
        return json.dumps(self.to_dict()).encode()

    @classmethod
    def parse_from_string(cls, s: bytes):
        return cls.from_dict(json.loads(s.decode()))

    def __repr__(self):
        n_ops = sum(len(b.ops) for b in self.blocks)
        return f"Program(blocks={len(self.blocks)}, ops={n_ops})"


# -- global default/startup programs + guards (fluid/framework.py) ----------

_default_main_program = Program()
_default_startup_program = Program()
_current_block_idx: list = []
_static_mode = [False]


def default_main_program() -> Program:
    return _default_main_program


def default_startup_program() -> Program:
    return _default_startup_program


def reset_default_programs():
    global _default_main_program, _default_startup_program
    _default_main_program = Program()
    _default_startup_program = Program()


@contextlib.contextmanager
def block_guard(block):
    """Make ``block`` the current append target (control-flow sub-blocks)."""
    _current_block_idx.append(block.idx)
    try:
        yield block
    finally:
        _current_block_idx.pop()


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    global _default_main_program, _default_startup_program
    prev_main, prev_startup = _default_main_program, _default_startup_program
    _default_main_program = main_program
    if startup_program is not None:
        _default_startup_program = startup_program
    try:
        yield
    finally:
        _default_main_program, _default_startup_program = prev_main, prev_startup


def enable_static():
    _static_mode[0] = True


def disable_static():
    _static_mode[0] = False


def in_static_mode() -> bool:
    return _static_mode[0]


def in_dynamic_mode() -> bool:
    return not _static_mode[0]


def data(name, shape, dtype="float32", lod_level=0):
    """paddle.static.data — declare a feed variable."""
    blk = default_main_program().global_block()
    var = blk.create_var(name=name, shape=shape, dtype=dtype, is_data=True)
    var.stop_gradient = True
    return var

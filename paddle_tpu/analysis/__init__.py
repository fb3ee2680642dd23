"""Static analysis: program-IR verifier + framework-aware source lint.

Two halves (ISSUE 13, in the TVM/compiler-first spirit of PAPERS.md):

- :mod:`verifier` / :mod:`passes` — a pass framework over the static
  Program IR (``static/program.py``). ``verify_program`` (also exposed as
  ``Program.verify``) checks def-before-use, duplicate/undeclared-alias
  writes, kernel dtype consistency, dead ops/vars, and control-flow block
  well-formedness BEFORE the executor lowers the block to XLA — a
  malformed program becomes a structured :class:`VerifyError` naming the
  op index, op type, and variable instead of an opaque trace error.
  ``Executor.run`` verifies automatically behind ``FLAGS_program_verify``
  (the verdict is cached per program version, so steady-state dispatch
  pays one dict lookup).
- :mod:`lint` — AST lint rules encoding recurring review findings
  (stale trace-time flag reads, unlocked shared-counter mutation, host
  syncs in decode/dispatch hot loops, weak-typed python-scalar captures,
  per-token cache materialization in decode/dispatch loops).
  CLI: ``tools/graphlint.py``; waivers: ``tools/graphlint_waivers.txt``.
- :mod:`memory` (Memplan, ISSUE 14) — interval-based liveness + peak-HBM
  planning over the same IR: :func:`plan_memory` predicts the peak
  resident bytes, high-water op, and top live tensors of a run BEFORE
  any lowering, honoring the ``__inplace__`` aliasing convention, and
  the liveness-aware donation-safety analysis rejects
  declared-then-read donated buffers. ``Executor.run`` enforces the
  device HBM budget through :func:`check_memory_budget` behind
  ``FLAGS_memory_budget_check``, and every real compile closes the loop
  via :func:`note_actual` (``plan_accuracy`` vs XLA memory_analysis).
- :mod:`optimizer` (IR optimizer, ISSUE 16) — the REWRITE half over the
  same IR: a :class:`PassManager` of fusion passes (conv2d+batch_norm+
  relu, residual-add+layer_norm, dequantized-int8 matmul/mul onto the
  fused registry kernels), generalized constant folding + dead-op
  elimination (the former Predictor-local ``inference/passes.py``
  pipeline), and liveness-driven rematerialization that consults the
  memplan resident curve to fit an over-budget program into HBM.
  ``Executor.run`` and the Predictor drive :func:`optimize_program`
  behind ``FLAGS_ir_opt_level``; every pass verifies pre/post and
  replans memory, reporting per-pass stats to counters and ``/statz``.
"""
from .verifier import (  # noqa: F401
    Finding,
    VerifyError,
    VerifyReport,
    register_pass,
    verifier_passes,
    verify_program,
)
from .lint import (  # noqa: F401
    LintFinding,
    lint_file,
    lint_paths,
    lint_rules,
    lint_source,
)
from .memory import (  # noqa: F401
    DonationError,
    MemoryBudgetError,
    MemoryFinding,
    MemoryPlan,
    accuracy_records,
    check_memory_budget,
    hbm_budget_bytes,
    note_actual,
    plan_memory,
)
from .optimizer import (  # noqa: F401
    OptResult,
    PassManager,
    PassStats,
    measure_pass_deltas,
    optimize_program,
    optimizer_passes,
    optimizer_stats,
    register_opt_pass,
)
from .waivers import Waiver, load_waivers, match_waiver  # noqa: F401

__all__ = [
    "DonationError",
    "MemoryBudgetError",
    "MemoryFinding",
    "MemoryPlan",
    "accuracy_records",
    "check_memory_budget",
    "hbm_budget_bytes",
    "note_actual",
    "plan_memory",
    "OptResult",
    "PassManager",
    "PassStats",
    "measure_pass_deltas",
    "optimize_program",
    "optimizer_passes",
    "optimizer_stats",
    "register_opt_pass",
    "Finding",
    "VerifyError",
    "VerifyReport",
    "register_pass",
    "verifier_passes",
    "verify_program",
    "LintFinding",
    "lint_file",
    "lint_paths",
    "lint_rules",
    "lint_source",
    "Waiver",
    "load_waivers",
    "match_waiver",
]

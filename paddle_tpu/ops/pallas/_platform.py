"""Shared gates for pallas kernel dispatch.

Every pallas kernel's ``*_supported`` predicate asks these questions
here, so the gates cannot drift apart.
"""
from __future__ import annotations

import sys
import threading
from importlib import import_module

import jax

_PALLAS = "jax.experimental.pallas"


def on_tpu_platform() -> bool:
    """True when the default jax backend is a TPU. A backend that fails
    to initialise raises here: "not a TPU, use the reference" would hide
    the device from every kernel."""
    return jax.devices()[0].platform == "tpu"


def can_emit_mosaic() -> bool:
    """True when a kernel may emit its Mosaic call at this point of a
    trace: the backend is a TPU and no multi-device mesh is in scope.
    jax refuses to partition a Mosaic call automatically ("wrap the call
    in a shard_map"), and no kernel here carries a partitioning rule, so
    under ``parallel.mesh_scope`` the kernels hand the op to XLA, which
    GSPMD does partition."""
    if not on_tpu_platform():
        return False
    from ...parallel.mesh import get_mesh

    mesh = get_mesh()
    return mesh is None or mesh.size == 1


def prefetch_pallas() -> None:
    """Start the first import of the Pallas TPU modules on a thread of
    its own, where a Mosaic call may be emitted and nothing has imported
    them yet. The kernel modules import them inside their calls, so a
    process that emits no kernel never pays for them; the first import
    is 1.4-1.5 s (jax's MLIR dialects), and a model whose layers will
    emit one pays it inside its first program's trace, on the thread a
    server's warm-up waits for. A layer that knows at construction that
    it may emit a kernel calls this, and the import passes while the
    weights are made (PERF.md, PR 47). The package is in ``sys.modules``
    from the import's first instant, so the next layer starts no second
    thread, and an import that is still running when a kernel asks for
    the module makes that ``import`` wait."""
    if _PALLAS not in sys.modules and can_emit_mosaic():
        threading.Thread(target=import_module, args=(_PALLAS + ".tpu",),
                         daemon=True, name="pallas-import").start()

"""Shared gates for pallas kernel dispatch.

Every pallas kernel's ``*_supported`` predicate asks these questions
here, so the gates cannot drift apart.
"""
from __future__ import annotations

import jax


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

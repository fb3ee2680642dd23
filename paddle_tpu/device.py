"""Device memory/observability facade (paddle.device surface).

Reference parity: python/paddle/device/ + paddle.device.cuda memory APIs
(memory_allocated/max_memory_allocated/memory_reserved, synchronize,
device_count, Stream/Event no-ops) over the reference's allocator
telemetry (memory/allocation/allocator_facade.cc stats).

TPU-native: XLA owns the device arena — there is no framework allocator
to query, but the PJRT device exposes the arena's live/peak/limit
counters (``Device.memory_stats()``), which is exactly what the
reference's facade reports. The CPU backend publishes no counters, so
there the functions return 0 rather than raising, matching paddle's
behavior on hosts without the accelerator runtime.
"""
from __future__ import annotations

import jax

from .framework.place import get_device, set_device  # noqa: F401

__all__ = [
    "set_device", "get_device", "device_count", "get_device_name",
    "synchronize", "memory_allocated", "max_memory_allocated",
    "memory_reserved", "memory_stats", "empty_cache", "is_compiled_with_cuda",
]


def device_count() -> int:
    return len(jax.local_devices())


def _dev(device=None):
    devs = jax.local_devices()
    if device is None:
        return devs[0]
    if isinstance(device, int):
        return devs[device]
    if isinstance(device, str):
        # accept the formats paddle's own get_device emits: "tpu:0",
        # "cpu", "gpu:1"
        idx = int(device.rsplit(":", 1)[1]) if ":" in device else 0
        return devs[idx]
    return device


def get_device_name(device=None) -> str:
    d = _dev(device)
    return getattr(d, "device_kind", str(d))


def synchronize(device=None):
    """Block until previously dispatched work on the device finishes
    (paddle.device.cuda.synchronize parity; XLA dispatch is async)."""
    jax.block_until_ready(jax.device_put(0, _dev(device)))


def memory_stats(device=None) -> dict:
    """The PJRT arena counters (allocator_facade stats equivalent);
    empty dict when the backend publishes none (CPU)."""
    return _dev(device).memory_stats() or {}


def memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    """Bytes the runtime currently holds from the device (the reference's
    allocator-reserved-pool semantics, memory/allocation/allocator_facade).

    PJRT publishes no reserved-pool counter, so the closest truthful
    figure is ``peak_bytes_in_use`` — the arena's high-water mark, a floor
    on what the runtime holds. Returns 0 when the backend publishes no
    counters at all. NOT ``bytes_limit``: that is total addressable HBM
    capacity, and reporting it here would make reserved look like the
    whole chip (use ``memory_stats()['bytes_limit']`` for capacity).
    """
    s = memory_stats(device)
    return int(s.get("peak_bytes_in_use", 0))


def empty_cache():
    """paddle.device.cuda.empty_cache parity. XLA's arena is not
    framework-managed; the real lever is dropping dead jax array
    references, so this triggers a host GC pass (which releases device
    buffers whose Python owners died)."""
    import gc

    gc.collect()


def is_compiled_with_cuda() -> bool:
    return False  # TPU build

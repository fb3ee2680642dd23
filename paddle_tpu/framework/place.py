"""Device places.

Reference parity: paddle/fluid/platform/place.h:26-123 — the `Place` variant
(CPUPlace/CUDAPlace/XPUPlace). Here TPUPlace is the first-class accelerator
place; device memory itself is managed by XLA, so a Place only selects a
jax.Device for tensor placement and compilation targets.
"""
from __future__ import annotations

import functools

import jax


class Place:
    """Base place. Equality is by (kind, device_id)."""

    kind = "undefined"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self.kind, self._device_id))

    def __repr__(self):
        return f"Place({self.kind}:{self._device_id})"

    # -- jax integration ----------------------------------------------------
    def jax_device(self) -> jax.Device:
        """The jax device this place names. A place that names a device
        the process cannot see raises — it never resolves to another
        kind of device."""
        devs = _devices_for_kind(self.kind)
        if self._device_id >= len(devs):
            raise RuntimeError(
                f"{self!r}: only {len(devs)} {self.kind} device(s) visible"
            )
        return devs[self._device_id]


class CPUPlace(Place):
    kind = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    kind = "tpu"


class CUDAPlace(Place):
    """Accepted for script compatibility; resolves to the accelerator."""

    kind = "tpu"


@functools.cache
def _devices_for_kind(kind: str):
    if kind == "cpu":
        return jax.devices("cpu")
    return [d for d in jax.devices() if d.platform != "cpu"]


def is_compiled_with_tpu() -> bool:
    return any(d.platform != "cpu" for d in jax.devices())


# paddle.device API ---------------------------------------------------------
_expected_place: Place | None = None


def _default_place() -> Place:
    global _expected_place
    if _expected_place is None:
        _expected_place = TPUPlace(0) if is_compiled_with_tpu() else CPUPlace()
    return _expected_place


def set_device(device: str | Place) -> Place:
    """set_device("tpu") / set_device("tpu:1") / set_device("cpu").
    Raises if the process cannot see the named device."""
    global _expected_place
    if isinstance(device, Place):
        place = device
    else:
        name, _, idx = device.partition(":")
        if name == "cpu":
            place = CPUPlace()
        elif name in ("tpu", "xpu", "gpu", "cuda"):
            place = TPUPlace(int(idx) if idx else 0)
        else:
            raise ValueError(f"unknown device {device!r}")
    place.jax_device()
    _expected_place = place
    return place


def get_device() -> str:
    p = _default_place()
    return p.kind if p.kind == "cpu" else f"{p.kind}:{p.get_device_id()}"

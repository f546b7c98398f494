"""Device/place abstraction.

Reference parity: paddle/phi/common/place.h (phi::Place, CPUPlace, GPUPlace,
CustomPlace) and the north star's `XLAPlace`. On TPU the place maps directly
onto a `jax.Device`; streams/contexts are subsumed by XLA's execution model,
so a Place here is a thin named handle used for `.to()` / `paddle.device`
parity rather than a stream owner.
"""
from __future__ import annotations

import functools

import jax


class Place:
    """Base place: a named device handle."""

    _kind = "undefined"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    @property
    def jax_device(self):
        # the host backend exists beside an accelerator; ask for it by
        # name (jax.devices() lists only the default backend's devices)
        devs = jax.devices("cpu") if self._kind == "cpu" else \
            [d for d in jax.devices() if _kind_matches(d, self._kind)]
        if not devs:
            raise RuntimeError(
                f"{self!r}: JAX reports no {self._kind!r} device "
                f"(platform {jax.devices()[0].platform!r}); a place names "
                "a device that exists, it does not fall back to another")
        return devs[self._device_id % len(devs)]

    def __eq__(self, other):
        return (isinstance(other, Place) and self._kind == other._kind
                and self._device_id == other._device_id)

    def __hash__(self):
        return hash((self._kind, self._device_id))

    def __repr__(self):
        return f"Place({self._kind}:{self._device_id})"


def _kind_matches(device, kind: str) -> bool:
    plat = device.platform.lower()
    if kind in ("tpu", "xla"):
        return plat == "tpu"
    return plat == kind


class CPUPlace(Place):
    _kind = "cpu"

    def __repr__(self):
        return "Place(cpu)"


class TPUPlace(Place):
    _kind = "tpu"

    def __repr__(self):
        return f"Place(tpu:{self._device_id})"


# North-star naming: XLAPlace is the Paddle-side name for the TPU device.
XLAPlace = TPUPlace
# CUDAPlace parity shim: on this framework it is the accelerator place.
CUDAPlace = TPUPlace


@functools.lru_cache(maxsize=None)
def _accelerator_available() -> bool:
    return any(d.platform.lower() == "tpu" for d in jax.devices())


_current_place = None


def set_device(device) -> Place:
    """paddle.set_device — accepts 'cpu', 'tpu', 'tpu:0', 'gpu' (alias of the
    accelerator), 'xla'. Naming a device JAX does not report is an
    error: set_device('tpu') without a TPU raises."""
    global _current_place
    place = _parse_place(device)
    place.jax_device          # raises when no such device exists
    _current_place = place
    return _current_place


def get_device() -> str:
    p = _default_place()
    return f"{p._kind}:{p.get_device_id()}" if p._kind != "cpu" else "cpu"


def _parse_place(device) -> Place:
    if isinstance(device, Place):
        return device
    s = str(device).lower()
    if ":" in s:
        kind, _, idx = s.partition(":")
        idx = int(idx)
    else:
        kind, idx = s, 0
    if kind == "cpu":
        return CPUPlace(idx)
    if kind in ("tpu", "gpu", "xla", "cuda", "xpu"):
        # ported XPU scripts select via set_device('xpu:N') — map to the
        # accelerator place like the XPUPlace class shim
        return TPUPlace(idx)
    raise ValueError(f"unknown device {device!r}")


def _default_place() -> Place:
    if _current_place is not None:
        return _current_place
    return TPUPlace(0) if _accelerator_available() else CPUPlace(0)


def is_compiled_with_cuda() -> bool:  # parity stub
    return False


def is_compiled_with_xpu() -> bool:  # parity stub
    return False


def is_compiled_with_tpu() -> bool:
    return _accelerator_available()


def is_compiled_with_rocm() -> bool:  # parity stub
    return False


class CUDAPinnedPlace(Place):
    """Parity shim: pinned host memory is an explicit-staging CUDA
    concept; on TPU host arrays are staged by the runtime. Behaves as
    the CPU place."""
    _kind = "cpu"

    def __repr__(self):
        return "CUDAPinnedPlace"


class XPUPlace(Place):
    """Parity shim: no XPU in this stack; accepted for ported code and
    mapped to the accelerator place."""
    _kind = "tpu"

    def __repr__(self):
        return f"XPUPlace({self._device_id})"

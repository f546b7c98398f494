"""paddle.device parity (python/paddle/device/__init__.py)."""
from __future__ import annotations

import jax

from ..framework.place import (  # noqa: F401
    is_compiled_with_xpu,
)
from ..framework.place import (
    set_device, get_device, CPUPlace, TPUPlace, XLAPlace, CUDAPlace,
    is_compiled_with_cuda, is_compiled_with_tpu, is_compiled_with_rocm,
)


def get_available_device():
    devs = jax.devices()
    return [f"{'cpu' if d.platform == 'cpu' else 'tpu'}:{d.id}" for d in devs]


_BUILTIN_PLATFORMS = ("cpu", "gpu", "cuda", "rocm", "tpu")


def get_available_custom_device():
    """Devices from registered PJRT plugins (the TPU-native CustomDevice
    mechanism — see register_custom_device)."""
    return [f"{d.platform}:{d.id}" for d in jax.devices()
            if d.platform not in _BUILTIN_PLATFORMS]


def device_count():
    return len(jax.devices())


def get_all_device_type():
    return sorted({("cpu" if d.platform == "cpu" else "tpu")
                   for d in jax.devices()})


def get_all_custom_device_type():
    return sorted({d.platform for d in jax.devices()
                   if d.platform not in _BUILTIN_PLATFORMS})


def register_custom_device(device_type: str, library_path: str):
    """Register a third-party accelerator plugin.

    Reference parity: the CustomDevice plugin mechanism
    (paddle/phi/backends/custom/custom_device.cc + CustomRuntime C ABI,
    loaded from PADDLE_CUSTOM_DEVICE_ROOT). The TPU-native equivalent of
    that C ABI is a PJRT plugin: a shared library implementing the PJRT
    C API, which XLA loads and exposes as a jax backend. Must be called
    BEFORE any computation initializes the backends.
    """
    # jax 0.9.0 has no public name for either call (jax.extend.backend
    # offers neither); both exist in jax._src.xla_bridge on it
    from jax._src import xla_bridge as _xb
    if _xb.backends_are_initialized():
        raise RuntimeError(
            "register_custom_device must be called before the first "
            "jax computation (backends already initialized)")
    _xb.register_plugin(device_type, library_path=library_path)
    return device_type


class cuda:
    """paddle.device.cuda parity shim → accelerator queries."""

    @staticmethod
    def device_count():
        return sum(1 for d in jax.devices() if d.platform != "cpu")

    @staticmethod
    def synchronize(device=None):
        # XLA dispatch is async; block on a trivial transfer
        import jax.numpy as jnp
        jnp.zeros(()).block_until_ready()

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def max_memory_allocated(device=None):
        try:
            d = jax.devices()[0]
            stats = d.memory_stats()
            return stats.get("peak_bytes_in_use", 0)
        except Exception:
            return 0

    @staticmethod
    def memory_allocated(device=None):
        try:
            d = jax.devices()[0]
            stats = d.memory_stats()
            return stats.get("bytes_in_use", 0)
        except Exception:
            return 0

    @staticmethod
    def max_memory_reserved(device=None):
        """Peak bytes the allocator arena held (XLA: reservable limit is
        the arena; peak_bytes_in_use is the closest observable)."""
        try:
            stats = jax.devices()[0].memory_stats()
            return stats.get("peak_bytes_in_use",
                             stats.get("bytes_limit", 0))
        except Exception:
            return 0

    @staticmethod
    def memory_reserved(device=None):
        try:
            stats = jax.devices()[0].memory_stats()
            return stats.get("bytes_reserved", stats.get("bytes_in_use", 0))
        except Exception:
            return 0

    @staticmethod
    def memory_stats(device=None):
        """Raw per-device allocator stats dict (XLA memory_stats)."""
        try:
            return dict(jax.devices()[0].memory_stats() or {})
        except Exception:
            return {}


# paddle.device.tpu mirrors the cuda shim (same queries, honest name);
# device.xpu too (ported Kunlun scripts query it before falling back)
tpu = cuda
xpu = cuda


def _attach_stream_api():
    """paddle.device.cuda.Stream/Event/current_stream/... mirror the
    device-level stream facades (upstream python/paddle/device/cuda/
    __init__.py exports them from the cuda namespace too). Deferred:
    Stream/Event are defined later in this module."""
    cuda.Stream = staticmethod(Stream)
    cuda.Event = staticmethod(Event)
    cuda.current_stream = staticmethod(current_stream)
    cuda.stream_guard = staticmethod(stream_guard)
    cuda.get_device_properties = staticmethod(get_device_properties)
    cuda.get_device_name = staticmethod(get_device_name)
    cuda.get_device_capability = staticmethod(get_device_capability)


def synchronize(device=None):
    cuda.synchronize(device)


class Event:
    """paddle.device.Event parity (reference: paddle/phi/backends/
    event.h + python/paddle/device/__init__.py Event). XLA has no user
    streams; record() snapshots a host timestamp after draining the
    async dispatch queue, so elapsed_time between two recorded events
    brackets real device work — the role CUDA events play in paddle
    timing code."""

    def __init__(self, device=None, enable_timing=True, blocking=False,
                 interprocess=False):
        self._t = None

    def record(self, stream=None):
        import time as _time
        synchronize()
        self._t = _time.perf_counter()

    def query(self):
        return self._t is not None

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end_event):
        if self._t is None or end_event._t is None:
            raise RuntimeError("both events must be recorded")
        return (end_event._t - self._t) * 1000.0


class Stream:
    """paddle.device.Stream parity (reference: phi stream wrappers).
    XLA owns scheduling/overlap (its latency-hiding scheduler is the
    stream assignment pass of the reference's InterpreterCore), so
    streams are ordering facades: record/wait compose with Event,
    synchronize drains the dispatch queue."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def record_event(self, event=None):
        ev = event or Event()
        ev.record(self)
        return ev

    def wait_event(self, event):
        synchronize()

    def wait_stream(self, stream):
        synchronize()

    def synchronize(self):
        synchronize()

    def query(self):
        return True


_current_stream = Stream()


def current_stream(device=None):
    return _current_stream


def set_stream(stream):
    global _current_stream
    prev = _current_stream
    _current_stream = stream
    return prev


class stream_guard:
    """Context manager parity for paddle.device.stream_guard."""

    def __init__(self, stream):
        self._s = stream

    def __enter__(self):
        self._prev = set_stream(self._s)
        return self._s

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False


class _DeviceProperties:
    """Parity shape of paddle.device.cuda.get_device_properties output."""

    def __init__(self, name, total_memory, multi_processor_count=1,
                 major=0, minor=0):
        self.name = name
        self.total_memory = total_memory
        self.multi_processor_count = multi_processor_count
        self.major = major
        self.minor = minor

    def __repr__(self):
        return (f"_DeviceProperties(name='{self.name}', "
                f"total_memory={self.total_memory})")


def get_device_properties(device=None):
    d = jax.devices()[0]
    try:
        total = (d.memory_stats() or {}).get("bytes_limit", 0)
    except Exception:
        total = 0
    return _DeviceProperties(str(d), total)


def get_device_name(device=None):
    return str(jax.devices()[0])


def get_device_capability(device=None):
    """No CUDA compute capability on TPU; (0, 0) keeps ported
    `major >= N` feature gates conservative."""
    return (0, 0)


_attach_stream_api()

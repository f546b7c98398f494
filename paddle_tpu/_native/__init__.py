"""ctypes bindings for the native runtime library (csrc/).

Reference parity: the C++ runtime layer of the reference —
paddle/phi/core/distributed/store/tcp_store.cc (TCPStore),
paddle/phi/core/flags.cc (flag registry), paddle/fluid/memory stats, and the
DataLoader shared-memory worker path. pybind11 is not in this image, so the
boundary is a C ABI loaded via ctypes.

The library auto-builds from csrc/ on first load when the .so is missing or
was built from other sources: a SHA-256 of the csrc/ inputs is stored beside
the .so and compared (modification times mean nothing after a copy or a
checkout). Builds take <5s with the baked-in g++.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import pickle
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(os.path.dirname(_HERE))
_CSRC = os.path.join(_REPO, "csrc")
_SO = os.path.join(_HERE, "libpaddle_tpu_rt.so")
_SO_HASH = _SO + ".srchash"

_lib = None
_built_this_process = False
_build_lock = threading.Lock()


class NativeUnavailable(RuntimeError):
    pass


# sources of the separate capi library (make capi) — not inputs of the
# core runtime .so, so they must not trigger its staleness/rebuild
_CAPI_ONLY = ("capi.cc", "pd_inference_c_api.h")


def _sources():
    return [os.path.join(_CSRC, f) for f in sorted(os.listdir(_CSRC))
            if f.endswith((".cc", ".h")) and f not in _CAPI_ONLY]


def _source_hash() -> str:
    h = hashlib.sha256()
    for path in _sources():
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _stored_hash():
    try:
        with open(_SO_HASH) as f:
            return f.read().strip()
    except FileNotFoundError:
        return None


def _needs_build() -> bool:
    if not os.path.isdir(_CSRC):
        return not os.path.exists(_SO)  # prebuilt .so without sources is fine
    if not os.path.exists(_SO):
        return True
    return _stored_hash() != _source_hash()


def _build():
    """Compile to a temp file and atomically rename, under an flock, so
    concurrently launched ranks never dlopen a half-written .so."""
    import fcntl
    lock_path = _SO + ".lock"
    with open(lock_path, "w") as lock_f:
        fcntl.flock(lock_f, fcntl.LOCK_EX)
        try:
            if not _needs_build():  # another process built it while we waited
                return
            # capi.cc links libpython and builds separately (make capi);
            # the core runtime lib must stay python-free
            global _built_this_process
            src_hash = _source_hash()
            srcs = [p for p in _sources() if p.endswith(".cc")]
            tmp = f"{_SO}.tmp.{os.getpid()}"
            cmd = ["g++", "-O2", "-std=c++17", "-fPIC",
                   "-fvisibility=hidden", "-Wall", "-pthread", "-shared",
                   "-o", tmp] + srcs + ["-lrt"]
            subprocess.run(cmd, check=True, capture_output=True, text=True)
            os.replace(tmp, _SO)
            with open(f"{_SO_HASH}.tmp.{os.getpid()}", "w") as f:
                f.write(src_hash)
            os.replace(f.name, _SO_HASH)
            _built_this_process = True
        finally:
            fcntl.flock(lock_f, fcntl.LOCK_UN)


def load():
    """Load (building if needed) the native library; raises NativeUnavailable
    if the toolchain or sources are missing."""
    global _lib
    if _lib is not None:
        return _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        if not os.path.isdir(_CSRC) and not os.path.exists(_SO):
            raise NativeUnavailable("csrc/ missing and no prebuilt .so")
        try:
            if _needs_build():
                _build()
        except (subprocess.CalledProcessError, FileNotFoundError) as e:
            detail = getattr(e, "stderr", "") or str(e)
            raise NativeUnavailable(f"native build failed: {detail}") from e
        lib = ctypes.CDLL(_SO)
        _declare(lib)
        _lib = lib
    # Mirror any flags defined before the lib was loaded (deferred so plain
    # `import paddle_tpu` never pays a compile).
    try:
        from ..framework import flags as _flags
        _flags.resync_native()
    except Exception:
        pass
    return _lib


def is_loaded() -> bool:
    return _lib is not None


def build_record() -> dict:
    """What this process did about the native library: whether it was
    loaded at all, whether this process compiled it, and the hash of the
    csrc/ inputs the loaded binary was built from."""
    if _lib is None:
        return {"loaded": False, "built": False,
                "note": "not needed: nothing on this run's path loaded it"}
    return {"loaded": True, "built": _built_this_process,
            "source_sha256": _stored_hash(),
            "sources": [os.path.basename(p) for p in _sources()]
            if os.path.isdir(_CSRC) else []}


def available() -> bool:
    try:
        load()
        return True
    except NativeUnavailable:
        return False


def _declare(lib):
    c = ctypes
    lib.pd_last_error.restype = c.c_char_p
    lib.pd_free.argtypes = [c.c_void_p]
    # flags
    lib.pd_flag_define.argtypes = [c.c_char_p, c.c_int, c.c_char_p,
                                   c.c_double, c.c_char_p]
    lib.pd_flag_set_num.argtypes = [c.c_char_p, c.c_double]
    lib.pd_flag_set_str.argtypes = [c.c_char_p, c.c_char_p]
    lib.pd_flag_get_num.argtypes = [c.c_char_p]
    lib.pd_flag_get_num.restype = c.c_double
    lib.pd_flag_get_str.argtypes = [c.c_char_p]
    lib.pd_flag_get_str.restype = c.c_void_p  # manual decode+free
    # stats
    for fn in ("pd_stats_record_alloc", "pd_stats_record_free"):
        getattr(lib, fn).argtypes = [c.c_char_p, c.c_int64]
    for fn in ("pd_stats_current", "pd_stats_peak", "pd_stats_alloc_count"):
        getattr(lib, fn).argtypes = [c.c_char_p]
        getattr(lib, fn).restype = c.c_int64
    lib.pd_stats_reset_peak.argtypes = [c.c_char_p]
    # tcp store
    lib.pd_store_server_start.argtypes = [c.c_int]
    lib.pd_store_server_start.restype = c.c_void_p
    lib.pd_store_server_port.argtypes = [c.c_void_p]
    lib.pd_store_server_stop.argtypes = [c.c_void_p]
    lib.pd_store_client_connect.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.pd_store_client_connect.restype = c.c_void_p
    lib.pd_store_client_free.argtypes = [c.c_void_p]
    lib.pd_store_set.argtypes = [c.c_void_p, c.c_char_p,
                                 c.POINTER(c.c_uint8), c.c_int64]
    lib.pd_store_get.argtypes = [c.c_void_p, c.c_char_p, c.c_int,
                                 c.POINTER(c.POINTER(c.c_uint8)),
                                 c.POINTER(c.c_int64)]
    lib.pd_store_add.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.pd_store_add.restype = c.c_int64
    lib.pd_store_wait.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
    lib.pd_store_delete.argtypes = [c.c_void_p, c.c_char_p]
    lib.pd_store_delete.restype = c.c_int64
    lib.pd_store_num_keys.argtypes = [c.c_void_p]
    lib.pd_store_num_keys.restype = c.c_int64
    # shm channel
    lib.pd_shm_create.argtypes = [c.c_char_p, c.c_int64]
    lib.pd_shm_create.restype = c.c_void_p
    lib.pd_shm_open.argtypes = [c.c_char_p]
    lib.pd_shm_open.restype = c.c_void_p
    lib.pd_shm_push.argtypes = [c.c_void_p, c.POINTER(c.c_uint8), c.c_int64,
                                c.c_int]
    lib.pd_shm_pop.argtypes = [c.c_void_p, c.POINTER(c.POINTER(c.c_uint8)),
                               c.c_int]
    lib.pd_shm_pop.restype = c.c_int64
    lib.pd_shm_close_write.argtypes = [c.c_void_p]
    lib.pd_shm_free.argtypes = [c.c_void_p, c.c_int]
    # host alloc
    lib.pd_host_alloc.argtypes = [c.c_int64, c.c_char_p]
    lib.pd_host_alloc.restype = c.c_void_p
    lib.pd_host_free.argtypes = [c.c_void_p, c.c_int64, c.c_char_p]


def _err(lib) -> str:
    return lib.pd_last_error().decode(errors="replace")


# ------------------------------------------------------------- TCPStore ---
class TCPStore:
    """Rendezvous KV store (parity: paddle.distributed.TCPStore /
    phi TCPStore). is_master starts the in-process server daemon; every
    rank (master included) talks through a client connection."""

    def __init__(self, host: str, port: int, is_master: bool = False,
                 world_size: int = 1, timeout: float = 90.0):
        lib = load()
        self._lib = lib
        self._server = None
        self.host = host
        self.timeout_ms = int(timeout * 1000)
        if is_master:
            self._server = lib.pd_store_server_start(port)
            if not self._server:
                raise RuntimeError(f"TCPStore server: {_err(lib)}")
            port = lib.pd_store_server_port(self._server)
        self.port = port
        self._client = lib.pd_store_client_connect(
            host.encode(), port, self.timeout_ms)
        if not self._client:
            raise RuntimeError(f"TCPStore connect: {_err(lib)}")
        self.world_size = world_size

    def set(self, key: str, value) -> None:
        if isinstance(value, str):
            value = value.encode()
        buf = (ctypes.c_uint8 * len(value)).from_buffer_copy(value)
        rc = self._lib.pd_store_set(self._client, key.encode(), buf,
                                    len(value))
        if rc != 0:
            raise RuntimeError(f"TCPStore.set: {_err(self._lib)}")

    def get(self, key: str, timeout_ms: int | None = None) -> bytes:
        out = ctypes.POINTER(ctypes.c_uint8)()
        n = ctypes.c_int64()
        rc = self._lib.pd_store_get(
            self._client, key.encode(),
            self.timeout_ms if timeout_ms is None else timeout_ms,
            ctypes.byref(out), ctypes.byref(n))
        if rc != 0:
            raise KeyError(f"TCPStore.get({key!r}): {_err(self._lib)}")
        data = ctypes.string_at(out, n.value)
        self._lib.pd_free(out)
        return data

    def add(self, key: str, delta: int) -> int:
        v = self._lib.pd_store_add(self._client, key.encode(), delta)
        if v == -(2 ** 63):
            raise RuntimeError(f"TCPStore.add: {_err(self._lib)}")
        return v

    def wait(self, keys, timeout_ms: int | None = None) -> None:
        if isinstance(keys, str):
            keys = [keys]
        for k in keys:
            rc = self._lib.pd_store_wait(
                self._client, k.encode(),
                self.timeout_ms if timeout_ms is None else timeout_ms)
            if rc != 0:
                raise TimeoutError(f"TCPStore.wait({k!r}) timed out")

    def delete_key(self, key: str) -> bool:
        return self._lib.pd_store_delete(self._client, key.encode()) > 0

    def num_keys(self) -> int:
        return self._lib.pd_store_num_keys(self._client)

    def barrier(self, name: str, world_size: int | None = None,
                timeout_ms: int | None = None) -> None:
        """All ranks add 1 then wait for the count to reach world_size."""
        ws = world_size or self.world_size
        n = self.add(f"__barrier/{name}", 1)
        if n >= ws:
            self.set(f"__barrier/{name}/done", b"1")
        self.wait(f"__barrier/{name}/done", timeout_ms)

    def close(self) -> None:
        if self._client:
            self._lib.pd_store_client_free(self._client)
            self._client = None
        if self._server:
            self._lib.pd_store_server_stop(self._server)
            self._server = None

    def __del__(self):  # best-effort
        try:
            self.close()
        except Exception:
            pass


# ----------------------------------------------------------- ShmChannel ---
class ShmChannel:
    """Bounded byte-message channel in POSIX shared memory (parity: the
    reference DataLoader's use_shared_memory worker transport)."""

    def __init__(self, name: str, capacity: int = 64 << 20,
                 create: bool = False):
        lib = load()
        self._lib = lib
        self.name = name
        self._owner = create
        if create:
            self._h = lib.pd_shm_create(name.encode(), capacity)
        else:
            self._h = lib.pd_shm_open(name.encode())
        if not self._h:
            raise RuntimeError(f"ShmChannel({name!r}): {_err(lib)}")

    def push(self, data: bytes, timeout_ms: int = 60000) -> None:
        buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
        rc = self._lib.pd_shm_push(self._h, buf, len(data), timeout_ms)
        if rc != 0:
            raise RuntimeError(f"ShmChannel.push: {_err(self._lib)}")

    def pop(self, timeout_ms: int = 60000):
        """Returns bytes, or None when the channel is closed and drained."""
        out = ctypes.POINTER(ctypes.c_uint8)()
        n = self._lib.pd_shm_pop(self._h, ctypes.byref(out), timeout_ms)
        if n == -3:
            return None
        if n < 0:
            raise TimeoutError(f"ShmChannel.pop: {_err(self._lib)}")
        data = ctypes.string_at(out, n)
        self._lib.pd_free(out)
        return data

    def push_obj(self, obj, timeout_ms: int = 60000) -> None:
        self.push(pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL),
                  timeout_ms)

    def pop_obj(self, timeout_ms: int = 60000):
        data = self.pop(timeout_ms)
        return None if data is None else pickle.loads(data)

    def close_write(self) -> None:
        self._lib.pd_shm_close_write(self._h)

    def close(self) -> None:
        if self._h:
            self._lib.pd_shm_free(self._h, 1 if self._owner else 0)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ------------------------------------------------------------- stats API ---
def stats_current(pool: str = "host") -> int:
    return load().pd_stats_current(pool.encode())


def stats_peak(pool: str = "host") -> int:
    return load().pd_stats_peak(pool.encode())


def stats_alloc_count(pool: str = "host") -> int:
    return load().pd_stats_alloc_count(pool.encode())


def stats_reset_peak(pool: str = "host") -> None:
    load().pd_stats_reset_peak(pool.encode())


def record_alloc(pool: str, nbytes: int) -> None:
    load().pd_stats_record_alloc(pool.encode(), nbytes)


def record_free(pool: str, nbytes: int) -> None:
    load().pd_stats_record_free(pool.encode(), nbytes)


# ------------------------------------------------------- native flags ---
FLAG_BOOL, FLAG_INT, FLAG_DOUBLE, FLAG_STRING = 0, 1, 2, 3


def flag_define(name: str, type_code: int, str_default: str = "",
                num_default: float = 0.0, help_: str = "") -> bool:
    """Returns True if an env var FLAGS_<name> overrode the default."""
    return bool(load().pd_flag_define(
        name.encode(), type_code, str_default.encode(), num_default,
        help_.encode()))


def flag_set(name: str, value) -> None:
    lib = load()
    if isinstance(value, str):
        rc = lib.pd_flag_set_str(name.encode(), value.encode())
    else:
        rc = lib.pd_flag_set_num(name.encode(), float(value))
    if rc != 0:
        raise KeyError(_err(lib))


def flag_get_num(name: str) -> float:
    return load().pd_flag_get_num(name.encode())


def flag_get_str(name: str):
    lib = load()
    p = lib.pd_flag_get_str(name.encode())
    if not p:
        return None
    s = ctypes.string_at(p).decode()
    lib.pd_free(p)
    return s

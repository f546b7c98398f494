"""Runtime glue: jit-safe recording, device memory watermarks, the
process auto-sink, and per-rank heartbeats.

The contract with jitted code: metrics NEVER force a device sync. A
traced value reaches the registry through `jax.debug.callback` (async,
host-side, ordered by the runtime) and ONLY when telemetry is enabled at
trace time — `jit_callback` with telemetry disabled emits nothing into
the jaxpr, so the disabled mode costs literally zero inside compiled
programs (asserted by tests/test_observability.py).
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Callable, Optional

from .metrics import enabled, get_registry

__all__ = ["jit_callback", "device_memory_stats", "configure",
           "maybe_export", "export_record", "telemetry_path",
           "RankHeartbeat", "rank_identity", "set_identity",
           "export_identity", "watch_compiles", "compile_log", "jit_tag",
           "watch_gc", "gc_log"]


# ------------------------------------------------------- rank identity ------
# Fleet observability (docs/OBSERVABILITY.md "Fleet view") joins telemetry
# across ranks, which only works if every exported line says which rank
# wrote it. The identity is sourced once from the launcher env
# (PADDLE_TRAINER_ID/RANK, PADDLE_TRAINERS_NUM/WORLD_SIZE,
# PADDLE_TPU_TOPOLOGY) and merged into every JSONL record by the sink;
# single-process runs (no rank env) keep their line schema unchanged.
_identity: Optional[dict] = None


def _env_identity() -> dict:
    rank = os.environ.get("PADDLE_TRAINER_ID", os.environ.get("RANK"))
    if rank is None:
        return {}
    out = {"rank": int(rank)}
    ws = os.environ.get("PADDLE_TRAINERS_NUM",
                        os.environ.get("WORLD_SIZE"))
    if ws is not None:
        out["world_size"] = int(ws)
    topo = os.environ.get("PADDLE_TPU_TOPOLOGY")
    if topo:
        out["topology"] = topo
    return out


def rank_identity() -> dict:
    """This process's fleet identity: `{"rank", "world_size",
    "topology"}` (any subset; `{}` outside a launcher). Cached on first
    read; `set_identity` overrides."""
    global _identity
    if _identity is None:
        try:
            _identity = _env_identity()
        except (TypeError, ValueError):
            _identity = {}
    return dict(_identity)


def export_identity() -> dict:
    """The identity exporters stamp on every record: the full
    rank_identity() under a launcher, `{}` otherwise. Gated on a
    ``rank`` being present so a process-local topology stamp
    (`HybridTrainStep` in a single-process run) cannot change the
    single-process line schema — outside a launcher, telemetry lines
    and Prometheus labels stay exactly as they always were."""
    ident = rank_identity()
    return ident if "rank" in ident else {}


def set_identity(rank: Optional[int] = None,
                 world_size: Optional[int] = None,
                 topology: Optional[str] = None) -> dict:
    """Override/extend the cached identity (the hybrid engine names its
    mesh topology here so rank files record the layout they ran under).
    Only the given fields change; returns the resulting identity. An
    already-attached process sink picks the change up immediately."""
    global _identity
    ident = rank_identity()
    if rank is not None:
        ident["rank"] = int(rank)
    if world_size is not None:
        ident["world_size"] = int(world_size)
    if topology is not None:
        ident["topology"] = str(topology)
    _identity = ident
    with _Sink.lock:
        if _sink.exporter is not None:
            _sink.exporter.identity = export_identity()
    return dict(ident)


def jit_callback(fn: Callable, *traced_args):
    """Record traced values host-side from inside a jitted function.

    `fn(*host_values)` runs on the host with numpy arrays once the
    device values materialize (jax.debug.callback: async, no sync).
    When telemetry is disabled AT TRACE TIME this is a literal no-op —
    nothing enters the program. Callers re-jit (new step object / new
    signature) to pick up a toggled switch; already-compiled programs
    keep the behavior they were traced with.
    """
    if not enabled():
        return
    import jax

    def _guarded(*vals):
        if not enabled():  # runtime toggle after trace: drop silently
            return
        try:
            fn(*vals)
        except Exception:
            pass  # telemetry must never kill a training step

    jax.debug.callback(_guarded, *traced_args)


# -------------------------------------------------------- compile log ------
# JAX reports every trace, lowering and backend compile, and every hit
# and miss of its persistent cache, through jax.monitoring. Kept here as
# a time-stamped log: a compile that lands where none should (inside a
# serving window) is then an entry that says when, how long and, where
# the dispatcher tagged it (`jit_tag`), for which program signature.
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}
# one set-up of a serving cell logs 14 k events (every nested trace is
# one), and the log has to hold a run's set-up, window and what follows
_COMPILE_LOG_CAPACITY = 65536
_compile_log: collections.deque = collections.deque(
    maxlen=_COMPILE_LOG_CAPACITY)
_compile_watch_lock = threading.Lock()
_compile_watched = False


class _JitTag(threading.local):
    sig = None


_jit_tag = _JitTag()


class jit_tag:
    """``with jit_tag(sig):`` around a jitted call: whatever JAX traces
    or compiles on this thread meanwhile is logged under `sig`."""

    __slots__ = ("_sig", "_prev")

    def __init__(self, sig):
        self._sig = sig

    def __enter__(self):
        self._prev, _jit_tag.sig = _jit_tag.sig, self._sig
        return self

    def __exit__(self, *exc):
        _jit_tag.sig = self._prev
        return False


def _on_compile_event(event, seconds=0.0, **_kw):
    kind = _COMPILE_EVENTS.get(event)
    if kind is None or not enabled():
        return
    seconds = float(seconds)
    sig = _jit_tag.sig
    _compile_log.append((time.perf_counter(), kind, seconds,
                         None if sig is None else str(sig)))
    reg = get_registry()
    if kind == "trace":
        reg.counter("jit.traces").inc()
        reg.counter("jit.trace_seconds", unit="s").inc(seconds)
    elif kind in ("lower", "compile"):
        reg.counter("jit.compile_seconds", unit="s").inc(seconds)
    elif kind == "cache_hit":
        reg.counter("jit.cache_hits").inc()
    else:
        reg.counter("jit.cache_misses").inc()


def watch_compiles():
    """Listen to JAX's own compile events (idempotent; the package does
    it on import). Each is kept in `compile_log()` and summed into the
    counters jit.traces, jit.trace_seconds, jit.compile_seconds (lowering
    plus backend compile, cache retrieval included), jit.cache_hits and
    jit.cache_misses."""
    global _compile_watched
    with _compile_watch_lock:
        if _compile_watched:
            return
        _compile_watched = True
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_compile_event)
    monitoring.register_event_listener(_on_compile_event)


def stamped_between(records, key, since=None, until=None) -> list:
    """A copy of `records` (a deque that other threads append to),
    oldest first; with a bound given, those whose `key` stamp lies in
    [`since`, `until`) on ``time.perf_counter``."""
    out = list(records)       # one atomic copy under the GIL
    if since is None and until is None:
        return out
    lo = float("-inf") if since is None else since
    hi = float("inf") if until is None else until
    return [r for r in out if lo <= r[key] < hi]


def compile_log(since: Optional[float] = None,
                until: Optional[float] = None) -> list:
    """The logged compile events, oldest first: ``{"t": perf_counter at
    receipt, "kind": trace|lower|compile|cache_hit|cache_miss,
    "seconds", "sig"}`` (those received in [`since`, `until`) when
    given). A duration event is received when its work ENDS."""
    return [{"t": t, "kind": kind, "seconds": seconds, "sig": sig}
            for t, kind, seconds, sig
            in stamped_between(_compile_log, 0, since, until)]


# ------------------------------------------------------------ gc log ------
# CPython's collector stops every thread for as long as a collection
# lasts: a full (generation-2) one over a serving process's heap takes a
# third of a second, so a long tick or an outlying first-token wait may
# be one. `gc.callbacks` names each collection's start and stop; they
# are stamped here on `time.perf_counter` (the ticks' clock) and kept as
# a log like the compile log's. A young collection is over in tens of
# microseconds and there are hundreds a second: it costs one float
# store and one comparison here and leaves nothing.
_GC_LOG_CAPACITY = 4096
_GC_KEEP_SECONDS = 1e-3    # younger generations: kept from this length
_gc_log: collections.deque = collections.deque(maxlen=_GC_LOG_CAPACITY)
_gc_watched = False
_gc_t0 = 0.0
_gc_ann = None             # the open `host.gc` annotation, if any
_gc_annotation = None      # jax.profiler.TraceAnnotation, once watched


def _on_gc(phase, info):
    global _gc_t0, _gc_ann
    if phase == "start":
        if info["generation"] == 2 and enabled():
            # on the profiler's clock, so that a device idle gap under
            # a full collection has a name in the trace
            _gc_ann = _gc_annotation("host.gc", generation=2)
            _gc_ann.__enter__()
        _gc_t0 = time.perf_counter()
        return
    seconds = time.perf_counter() - _gc_t0
    generation = info["generation"]
    if generation != 2 and seconds < _GC_KEEP_SECONDS:
        return
    if _gc_ann is not None:
        _gc_ann.__exit__(None, None, None)
        _gc_ann = None
    if not enabled():
        return
    # a flat dict of numbers: the collector does not track it
    _gc_log.append({"t": _gc_t0, "generation": generation,
                    "seconds": seconds, "collected": info["collected"]})
    reg = get_registry()
    reg.counter("host.gc_seconds", unit="s").inc(
        seconds, generation=str(generation))
    reg.counter("host.gc_collections").inc(generation=str(generation))


def watch_gc():
    """Stamp the garbage collector's pauses (idempotent; the package
    does it on import, beside `watch_compiles`). Every generation-2
    collection, and any younger one of a millisecond or more, is kept in
    `gc_log()`, counted in host.gc_seconds{generation} and
    host.gc_collections{generation}, and a generation-2 collection runs
    under a `host.gc` annotation on the profiler's trace of the thread
    that met it."""
    global _gc_watched, _gc_annotation
    with _compile_watch_lock:
        if _gc_watched:
            return
        _gc_watched = True
    import gc
    from jax.profiler import TraceAnnotation
    _gc_annotation = TraceAnnotation
    gc.callbacks.append(_on_gc)


def gc_log(since: Optional[float] = None,
           until: Optional[float] = None) -> list:
    """The logged collections, oldest first: ``{"t": perf_counter at
    the collection's START, "generation", "seconds", "collected"}``
    (those that began in [`since`, `until`) when given). The process
    stood still from `t` for `seconds`."""
    return [dict(r) for r in stamped_between(_gc_log, "t", since, until)]


def device_memory_stats() -> dict:
    """Best-effort device memory watermark, no sync.

    On real accelerators `Device.memory_stats()` reports allocator
    watermarks; the CPU backend returns None, so we fall back to the
    bytes of every live jax.Array (an upper bound that tracks leaks the
    same way).  Returns {"bytes_in_use", "peak_bytes_in_use", "source"}.
    """
    import jax
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        stats = None
    if stats:
        return {"bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(
                    stats.get("peak_bytes_in_use",
                              stats.get("bytes_in_use", 0))),
                "source": "memory_stats"}
    try:
        live = sum(a.nbytes for a in jax.live_arrays())
    except Exception:
        live = 0
    return {"bytes_in_use": int(live), "peak_bytes_in_use": int(live),
            "source": "live_arrays"}


# --------------------------------------------------------------- sink ------
class _Sink:
    lock = threading.Lock()
    exporter = None          # JsonlExporter
    every = 1                # export every N maybe_export calls
    _calls = 0


_sink = _Sink()
_atexit_registered = False


def _close_sink_at_exit():
    """Interpreter-teardown flush: the last partial snapshot (or span)
    written just before exit must reach disk even when the owner never
    called configure(None). JsonlExporter.close() is idempotent, so a
    sink closed earlier by hand is a no-op here."""
    with _Sink.lock:
        exp, _sink.exporter = _sink.exporter, None
    if exp is not None:
        exp.close()


def configure(jsonl_path: Optional[str] = None, every: int = 1):
    """Attach (or detach, with None) the process JSONL telemetry sink.

    Instrumented hot paths call `maybe_export(step=...)` once per step;
    with a sink configured that appends one registry snapshot every
    `every` calls. Env default: PADDLE_TPU_TELEMETRY_JSONL. The sink is
    flushed and closed at interpreter exit (atexit) if still attached.
    """
    global _atexit_registered
    from .exporters import JsonlExporter
    with _Sink.lock:
        if _sink.exporter is not None:
            _sink.exporter.close()
            _sink.exporter = None
        if jsonl_path:
            _sink.exporter = JsonlExporter(jsonl_path)
        _sink.every = max(1, int(every))
        _sink._calls = 0
    if not _atexit_registered:
        _atexit_registered = True
        import atexit
        atexit.register(_close_sink_at_exit)


def telemetry_path() -> Optional[str]:
    return _sink.exporter.path if _sink.exporter is not None else None


_env_checked = False


def _ensure_env_sink():
    global _env_checked
    if _env_checked or _sink.exporter is not None:
        return
    _env_checked = True
    path = os.environ.get("PADDLE_TPU_TELEMETRY_JSONL")
    if path:
        configure(path)


def maybe_export(step: Optional[int] = None):
    """Flush a registry snapshot to the configured JSONL sink (no-op
    when telemetry is disabled or no sink is configured)."""
    if not enabled():
        return
    _ensure_env_sink()
    with _Sink.lock:
        exp = _sink.exporter
        if exp is None:
            return
        _sink._calls += 1
        if (_sink._calls % _sink.every) != 0:
            return
        exp.export(step=step)


def export_record(rec: dict):
    """Write one raw record (span lines, one-off run metadata) through
    the process JSONL sink; silent no-op without a sink. This is how
    tracing.Span.end lands `{"kind": "span"}` lines in the same file as
    the metric samples."""
    if not enabled():
        return
    _ensure_env_sink()
    with _Sink.lock:
        exp = _sink.exporter
        if exp is None:
            return
        exp.write_record(rec)


# ---------------------------------------------------------- heartbeat ------
class RankHeartbeat:
    """Per-rank liveness lines so a wedged rank is diagnosable.

    Appends JSONL lines {"ts", "kind": "heartbeat", "rank"/"epoch", ...}
    at most once per `interval` seconds; `beat(**fields)` is safe to
    call every loop tick. interval <= 0 disables."""

    def __init__(self, path: str, interval: float = 1.0):
        self.path = path
        self.interval = float(interval)
        self._last = 0.0
        self._f = None
        if self.interval > 0:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            self._f = open(path, "a", buffering=1)

    def due(self) -> bool:
        """True when the next beat would actually write — check before
        building an expensive snapshot payload every loop tick."""
        return (self._f is not None
                and time.time() - self._last >= self.interval)

    def beat(self, force: bool = False, **fields) -> bool:
        if self._f is None:
            return False
        now = time.time()
        if not force and now - self._last < self.interval:
            return False
        try:  # heartbeat_stall fault: the process stays alive but its
            # heartbeat goes silent — the wedged-rank signature the
            # launcher's stale-heartbeat detector exists to catch
            from ..framework import faults as _faults
            fa = _faults.check("heartbeat_stall")
            if fa is not None:
                self._stalled_until = now + float(
                    fa.params.get("sleep", 3600.0))
        except Exception:
            pass
        if now < getattr(self, "_stalled_until", 0.0):
            return False
        self._last = now
        rec = {"ts": round(now, 3), "kind": "heartbeat"}
        rec.update(fields)
        try:
            self._f.write(json.dumps(rec) + "\n")
        except Exception:
            return False
        return True

    def close(self):
        if self._f is not None:
            try:
                self._f.close()
            except Exception:
                pass
            self._f = None

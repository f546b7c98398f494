"""Step telemetry shared by the compiled train steps and the Trainer.

One StepTelemetry object per step object records, per step and with no
forced device sync:

    train.step_time_seconds   histogram   wall time of one __call__*
    train.steps               counter
    train.tokens              counter     batch elements consumed
    train.tokens_per_sec      gauge
    train.mfu                 gauge       achieved / peak FLOP/s
    train.grad_norm           gauge       via jax.debug.callback (async)
    mem.bytes_in_use          gauge       device watermark (or live-array
    mem.peak_bytes_in_use     gauge       bytes on backends without
                                          allocator stats)
    comm.calls / comm.bytes   counter     labels op=..., axis=... —
                                          analytic accounting of the
                                          collectives XLA inserts for
                                          the declared shardings

*On an async-dispatch backend the __call__ wall time converges to the
true step time once the dispatch queue backpressures (steady state); the
first samples measure compile + dispatch.

MFU numerator: XLA's own cost model for the full step when the step
object exposes `cost_analysis` (hapi/flops.py's approach — exact for
what the program lowers to), computed ONCE per batch signature; falls
back to the 6·N·tokens analytic estimate.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

from .metrics import counter, enabled, gauge, histogram
from .runtime import device_memory_stats, jit_callback, maybe_export

__all__ = ["StepTelemetry", "peak_flops", "batch_tokens",
           "sharded_bytes"]


def sharded_bytes(leaves):
    """(global_bytes, per_replica_bytes) for a list of PLACED jax
    arrays: global is the full logical footprint, per_replica divides
    each leaf by the product of the mesh-axis sizes its NamedSharding
    spec names (the analytic per-device share — what ZeRO/TP sharding
    buys). Leaves without a NamedSharding count replicated."""
    import numpy as np
    tot = per = 0
    for v in leaves:
        shape = getattr(v, "shape", None)
        if shape is None:
            continue
        nb = int(np.prod(shape or (1,))) * np.dtype(v.dtype).itemsize
        tot += nb
        div = 1
        sh = getattr(v, "sharding", None)
        spec = getattr(sh, "spec", None)
        mesh = getattr(sh, "mesh", None)
        if spec is not None and mesh is not None:
            sizes = dict(getattr(mesh, "shape", {}) or {})
            for ax in spec:
                axes = ax if isinstance(ax, (tuple, list)) else (ax,)
                for a in axes:
                    if a is not None:
                        div *= int(sizes.get(a, 1))
        per += nb // max(div, 1)
    return tot, per


def peak_flops(dtype: str = "bfloat16") -> Optional[float]:
    """The chip's published peak (trainer.PEAK_BF16_FLOPS), or None on
    a device that has none: ``train.mfu`` is then not recorded."""
    from ..trainer import UnknownDevicePeak, device_peak_flops
    try:
        return device_peak_flops(dtype)
    except UnknownDevicePeak:
        return None


def batch_tokens(arrays) -> int:
    """Telemetry token count for a batch: B*T for integer id batches
    ([B, T] token ids), else the batch size. Shared by every step
    class so their tokens/s series agree."""
    import jax.numpy as jnp
    a = arrays[0]
    if a.ndim >= 2 and jnp.issubdtype(a.dtype, jnp.integer):
        return int(a.shape[0]) * int(a.shape[1])
    return int(a.shape[0]) if a.ndim else 1


class StepTelemetry:
    """Host-side recorder for one compiled train-step object."""

    def __init__(self, n_params: int, dtype: str = "float32",
                 n_devices: Optional[int] = None, prefix: str = "train",
                 comm_per_step: Optional[List[Tuple[str, str, int, int]]]
                 = None,
                 flops_fn: Optional[Callable[[], float]] = None,
                 mem_every: int = 1):
        self.prefix = prefix
        self.n_params = int(n_params)
        self.dtype = dtype
        if n_devices is None:
            import jax
            n_devices = jax.device_count()
        self.n_devices = int(n_devices)
        # (op, axis, calls, bytes) accounted once per step
        self.comm_per_step = list(comm_per_step or [])
        self._flops_fn = flops_fn
        self._flops_per_step: Optional[float] = None
        self._t0: Optional[float] = None
        self._step = 0
        self._mem_every = max(1, int(mem_every))

        self.h_step = histogram(f"{prefix}.step_time_seconds",
                                help="wall time per train step", unit="s")
        self.c_steps = counter(f"{prefix}.steps")
        self.c_tokens = counter(f"{prefix}.tokens")
        self.g_tps = gauge(f"{prefix}.tokens_per_sec")
        self.g_mfu = gauge(f"{prefix}.mfu")
        self.g_gnorm = gauge(f"{prefix}.grad_norm")
        self.g_mem = gauge("mem.bytes_in_use", unit="bytes")
        self.g_mem_peak = gauge("mem.peak_bytes_in_use", unit="bytes")
        self.c_comm_calls = counter("comm.calls")
        self.c_comm_bytes = counter("comm.bytes", unit="bytes")

    # -- traced side ----------------------------------------------------
    def grad_norm_callback(self, grads):
        """Call INSIDE the traced step with the grad list; emits an async
        host callback recording the global grad norm. No-op (nothing
        enters the jaxpr) when telemetry is disabled at trace time."""
        if not enabled():
            return
        import jax.numpy as jnp
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in grads)
        jit_callback(lambda v: self.g_gnorm.set(float(v)), jnp.sqrt(sq))

    # -- host side ------------------------------------------------------
    def step_start(self):
        if not enabled():
            return
        self._t0 = time.perf_counter()

    def step_end(self, tokens: int, export_step: Optional[int] = None):
        """Record the step. `tokens` = batch elements consumed (0 skips
        throughput/MFU). Flushes the process JSONL sink if configured."""
        if not enabled() or self._t0 is None:
            return
        dt = time.perf_counter() - self._t0
        self._t0 = None
        self._step += 1
        self.h_step.observe(dt)
        self.c_steps.inc()
        if tokens:
            self.c_tokens.inc(tokens)
            tps = tokens / dt if dt > 0 else 0.0
            self.g_tps.set(tps)
            peak = peak_flops(self.dtype)
            fps = self._flops_for(tokens) if peak else 0.0
            if fps:
                self.g_mfu.set((fps / dt) / (peak * self.n_devices)
                               if dt > 0 else 0.0)
        for op, axis, calls, nbytes in self.comm_per_step:
            self.c_comm_calls.inc(calls, op=op, axis=axis)
            self.c_comm_bytes.inc(nbytes, op=op, axis=axis)
        if (self._step % self._mem_every) == 0:
            mem = device_memory_stats()
            self.g_mem.set(mem["bytes_in_use"])
            self.g_mem_peak.set(mem["peak_bytes_in_use"])
        maybe_export(step=export_step if export_step is not None
                     else self._step)
        return dt

    def reset_flops(self, flops_fn: Optional[Callable[[], float]] = None):
        """Re-arm the (expensive) flops probe — call when the step's
        batch signature changes so MFU doesn't go stale at a new shape."""
        self._flops_fn = flops_fn if flops_fn is not None \
            else self._flops_fn
        self._flops_per_step = None

    def _flops_for(self, tokens: int) -> float:
        if self._flops_per_step is None and self._flops_fn is not None:
            fn, self._flops_fn = self._flops_fn, None  # one shot per arm
            try:
                self._flops_per_step = float(fn() or 0.0)
            except Exception:
                self._flops_per_step = 0.0
        if self._flops_per_step:
            return self._flops_per_step
        return 6.0 * self.n_params * tokens

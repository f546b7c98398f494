"""Shared helpers for the Pallas kernel modules."""
from __future__ import annotations

import contextlib
import logging
import threading

import numpy as np
import jax
from jax.sharding import PartitionSpec

from ..framework.flags import flag_value

_logger = logging.getLogger("paddle_tpu.kernels")

# Pallas index maps must return a uniform int type: with jax_enable_x64
# on (Paddle int64 parity), a bare `0` literal traces as i64 next to the
# i32 grid index and Mosaic fails to legalize `func.return` — use an
# explicit i32 zero.
_Z = np.int32(0)

_NEG_INF = np.float32(-1e30)


def use_pallas() -> bool:
    """Gate: FLAGS_use_pallas_kernels on AND (a non-CPU backend OR
    FLAGS_pallas_interpret for CPU-interpreter CI coverage)."""
    if not flag_value("use_pallas_kernels"):
        return False
    if flag_value("pallas_interpret"):
        return True
    return jax.default_backend() != "cpu"


def pallas_interpret() -> bool:
    """True when Pallas kernels should run in interpreter mode (CPU CI)."""
    return bool(flag_value("pallas_interpret"))


def pallas_dtype_ok(*arrays) -> bool:
    """Mosaic lowers f32/bf16/f16 (and int) — never f64, which leaks in
    easily with jax_enable_x64 on. Gate kernels back to XLA for those."""
    import jax.numpy as jnp
    for a in arrays:
        if a.dtype in (jnp.float64,):
            return False
    return True


# Mosaic kernels cannot be partitioned automatically: under GSPMD the
# TPU lowering refuses a pallas_call whose program spans more than one
# device ("wrap the call in a shard_map"; jax 0.9.0). A step that traces
# kernels for a multi-device mesh (the tensor-parallel serve programs,
# the hybrid-parallel train step) therefore declares its mesh for the
# duration of the trace, and every kernel call goes through
# `partitioned`, which wraps it in jax.shard_map with the batch dim over
# 'data' and the head dim over 'model'. Thread-local trace-time state:
# router replicas trace on their own threads.
_partition = threading.local()

_ROLE_AXIS = {"batch": "data", "heads": "model"}


@contextlib.contextmanager
def kernel_partition_scope(mesh):
    """Declare `mesh` as the one the programs traced inside are
    partitioned over (None: a single-device program)."""
    prev = getattr(_partition, "mesh", None)
    _partition.mesh = mesh
    try:
        yield
    finally:
        _partition.mesh = prev


def kernel_mesh():
    """The declared mesh when it spans more than one device, else None."""
    mesh = getattr(_partition, "mesh", None)
    if mesh is None or mesh.devices.size == 1:
        return None
    return mesh


def tp_shard_degree() -> int:
    """Size of the declared mesh's 'model' axis: with the head axis
    sharded over it each shard sees only H / tp heads, so the Pallas
    tiling constraints must hold PER SHARD. 1 = unsharded."""
    mesh = kernel_mesh()
    return int(mesh.shape.get("model", 1)) if mesh is not None else 1


def partitioned(fn, in_roles, out_roles, *args):
    """``fn(*args)`` for a function of arrays that issues Pallas calls.

    With no multi-device mesh declared this is a plain call. Otherwise
    the call is wrapped in ``jax.shard_map`` over the declared mesh.
    `in_roles`/`out_roles` give, per array, one role per dimension:
    ``"batch"`` (sharded over 'data'), ``"heads"`` (over 'model') or
    None; a whole entry of None replicates the array. A role is sharded
    only when EVERY dimension carrying it divides by its axis (query
    and KV head counts must split alike for the GQA mapping inside a
    shard to stay right); otherwise that role is replicated and XLA
    gathers the operand."""
    mesh = kernel_mesh()
    if mesh is None or getattr(_partition, "active", None) is not None:
        return fn(*args)
    sizes = {role: int(mesh.shape.get(axis, 1))
             for role, axis in _ROLE_AXIS.items()}
    for roles, a in zip(in_roles, args):
        for role, dim in zip(roles or (), a.shape):
            if role is not None and dim % sizes[role]:
                sizes[role] = 1

    def spec(roles):
        if roles is None:
            return PartitionSpec()
        return PartitionSpec(*[
            _ROLE_AXIS[r] if r is not None and sizes[r] > 1 else None
            for r in roles])

    def body(*shards):
        _partition.active = sizes      # trace-time: what this call split
        try:
            return fn(*shards)
        finally:
            _partition.active = None

    out_specs = tuple(spec(r) for r in out_roles) \
        if isinstance(out_roles, list) else spec(out_roles)
    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(spec(r) for r in in_roles),
        out_specs=out_specs, check_vma=False)(*args)


def shard_index():
    """Linear index of the shard being traced, over the axes the
    enclosing `partitioned` call really split (0 outside one)."""
    sizes = getattr(_partition, "active", None)
    if not sizes:
        return 0
    idx = 0
    for role, axis in _ROLE_AXIS.items():
        if sizes[role] > 1:
            idx = idx * sizes[role] + jax.lax.axis_index(axis)
    return idx


# one log line per (kernel, reason) per process — production losing the
# fast path must be visible without drowning the log at trace frequency
_fallbacks_noted = set()


def note_fallback(kernel: str, reason: str) -> None:
    """Record a wanted-but-lost Pallas fast path: the caller asked for
    the kernel (FLAGS_use_pallas_kernels on a non-CPU backend, or
    interpret mode) but a gate (dtype, GQA ratio, tiling constraint)
    forced the plain-XLA route. Counts
    ``kernels.pallas_fallbacks{kernel,reason}`` and logs ONCE per
    (kernel, reason) — a silent perf cliff becomes an observable one.
    Called at trace time only (the gate is static), so it adds nothing
    to the compiled program."""
    from ..observability import metrics as _obsm
    _obsm.counter("kernels.pallas_fallbacks").inc(kernel=kernel,
                                                  reason=reason)
    key = (kernel, reason)
    if key not in _fallbacks_noted:
        _fallbacks_noted.add(key)
        _logger.warning(
            "Pallas kernel %r fell back to XLA (%s); serving/training "
            "runs without the fast path for this shape/dtype — and "
            "keeps paying it on every execution of the compiled "
            "program (kernels.pallas_fallbacks counts trace-time gate "
            "decisions, one per compiled signature)",
            kernel, reason)


def mxu_precision(*operands):
    """Explicit contract precision for matmuls INSIDE Pallas kernels.

    paddle_tpu sets jax_default_matmul_precision="highest" globally for
    f32 CUDA-parity, but Mosaic rejects a bf16 tpu.matmul carrying fp32
    contract precision ("Bad lhs type", observed on v5e) — and for bf16
    operands the MXU multiplies natively, so "highest" buys nothing.
    DEFAULT for sub-f32 operands, HIGHEST for f32.
    """
    import jax.numpy as jnp
    for o in operands:
        if o.dtype in (jnp.bfloat16, jnp.float16):
            return jax.lax.Precision.DEFAULT
    return jax.lax.Precision.HIGHEST

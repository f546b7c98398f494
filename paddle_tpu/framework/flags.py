"""Typed global flag registry.

Reference parity: paddle/phi/core/flags.cc (gflags-style FLAGS_* registry,
env-settable) and python/paddle/base/framework.py::set_flags/get_flags.
Flags front JAX config + our framework knobs. Each flag has a type, default,
help string, and env override (FLAGS_<name>).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


@dataclass
class _Flag:
    name: str
    default: Any
    type: type
    help: str
    on_change: Optional[Callable[[Any], None]] = None
    value: Any = None


_REGISTRY: Dict[str, _Flag] = {}


def _coerce(ty, raw):
    if ty is bool:
        if isinstance(raw, str):
            return raw.lower() in ("1", "true", "yes", "on")
        return bool(raw)
    return ty(raw)


def _native_mirror(name, ty, value, help_=""):
    """Mirror a flag into the native registry (csrc/flags.cc) so native
    components see framework flag state. Deferred: no-op until something
    actually loads the native lib (so `import paddle_tpu` never triggers a
    compile); load() calls resync_native() to catch up."""
    try:
        from .. import _native
        if not _native.is_loaded():
            return
        code = {bool: _native.FLAG_BOOL, int: _native.FLAG_INT,
                float: _native.FLAG_DOUBLE}.get(ty, _native.FLAG_STRING)
        # define (idempotent; applies env default) then set the explicit
        # current value so set_flags wins over a stale FLAGS_* env override.
        if code == _native.FLAG_STRING:
            _native.flag_define(name, code, str(value), 0.0, help_)
            _native.flag_set(name, str(value))
        else:
            _native.flag_define(name, code, "", float(value), help_)
            _native.flag_set(name, float(value))
    except Exception:
        pass


def resync_native():
    """Push the whole Python registry into the native one (called by
    _native.load() after the library comes up)."""
    for f in _REGISTRY.values():
        _native_mirror(f.name, f.type, f.value, f.help)


def define_flag(name: str, default, help: str = "", type_: type | None = None,
                on_change=None):
    ty = type_ or type(default)
    env = os.environ.get(f"FLAGS_{name}")
    value = _coerce(ty, env) if env is not None else default
    flag = _Flag(name=name, default=default, type=ty, help=help,
                 on_change=on_change, value=value)
    _REGISTRY[name] = flag
    _native_mirror(name, ty, value, help)
    if on_change is not None and env is not None:
        on_change(value)
    return flag


def set_flags(flags: Dict[str, Any]):
    """paddle.set_flags"""
    for k, v in flags.items():
        k = k.removeprefix("FLAGS_")
        if k not in _REGISTRY:
            raise ValueError(f"unknown flag {k!r}")
        f = _REGISTRY[k]
        f.value = _coerce(f.type, v)
        _native_mirror(k, f.type, f.value, f.help)
        if f.on_change is not None:
            f.on_change(f.value)


def get_flags(flags) -> Dict[str, Any]:
    """paddle.get_flags"""
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for k in flags:
        k2 = k.removeprefix("FLAGS_")
        if k2 not in _REGISTRY:
            raise ValueError(f"unknown flag {k!r}")
        out[k] = _REGISTRY[k2].value
    return out


def flag_value(name: str):
    return _REGISTRY[name].value


def all_flags():
    return {k: f.value for k, f in _REGISTRY.items()}


def _set_debug_nans(v: bool):
    import jax
    jax.config.update("jax_debug_nans", bool(v))


# Core flags (parity names with paddle/phi/core/flags.cc where meaningful).
define_flag("check_nan_inf", False,
            "Scan op outputs for NaN/Inf (maps to jax_debug_nans).",
            on_change=_set_debug_nans)
define_flag("check_nan_inf_level", 0, "NaN check verbosity level.")
define_flag("allocator_strategy", "auto_growth",
            "Parity stub: XLA/TPU memory is arena-managed by the runtime.")
define_flag("cudnn_deterministic", False,
            "Deterministic kernels (TPU: XLA is deterministic by default).")
define_flag("use_pallas_kernels", True,
            "Use Pallas fused kernels (attention/LN/RoPE) when on TPU.")
define_flag("pallas_interpret", False,
            "Force Pallas kernels ON in interpreter mode (CPU CI coverage: "
            "runs every kernel's real Pallas path without TPU hardware).")
define_flag("flash_block_q", 128,
            "Flash-attention Q tile rows (on-device autotune knob).")
define_flag("flash_block_k", 128,
            "Flash-attention KV tile rows (on-device autotune knob).")
define_flag("host_init", False,
            "Sample parameter initializers on the host (numpy) instead of "
            "via device jax.random ops. Same statistical distributions and "
            "seed-determinism, different random stream. Removes every "
            "per-parameter device program from model construction.")
define_flag("max_inplace_grad_add", 0, "Parity stub.")
define_flag("eager_delete_tensor_gb", 0.0, "Parity stub; XLA GC is automatic.")
define_flag("shm_channel_capacity_mb", 64,
            "Per-DataLoader shared-memory ring capacity (native worker pool).")
define_flag("obs_xla_mfu", False,
            "Telemetry MFU numerator from XLA's cost model (one extra "
            "lowering per batch signature) instead of the 6*N analytic "
            "estimate.")
define_flag("fused_optimizer", True,
            "Fused multi-tensor optimizer path: eager Optimizer.step() "
            "flattens (param, grad, accumulator) leaves into dtype-"
            "bucketed flat buffers and updates them in ONE jitted, "
            "donated program (O(#dtype buckets) dispatches instead of "
            "O(#params)). Per-param math is the fallback for non-fusible "
            "configs (custom regularizer callables, Lamb, ...).")
define_flag("quantized_grad_comm", False,
            "int8 gradient collectives with per-bucket scales and an "
            "error-feedback residual (EQuARX-style, arXiv:2506.17615). "
            "Applies to collective.quantized_* and, when "
            "weight_update_sharding is on, to DistTrainStep's gradient "
            "reduction. ~4x comm-byte reduction; adds quantization "
            "noise bounded by the error-feedback loop.")
define_flag("grad_bucket_bytes", 32 * 1024 * 1024,
            "Target flat-bucket payload size for gradient collectives "
            "(collective.GradBucketer). Smaller buckets let XLA overlap "
            "communication with the optimizer update; larger buckets "
            "amortize per-collective latency.")
define_flag("check_distribution_args", False,
            "Validate distribution constructor arguments (e.g. negative "
            "Categorical weights) with a warning. Costs a host sync on "
            "device-resident weights, so it is debug-only.")


def _arm_faults(v):
    from . import faults
    faults.arm(v)


define_flag("fault_injection", "",
            "Deterministic fault-injection spec (docs/ROBUSTNESS.md): "
            "comma-separated 'site[:key=val|mode]...' entries, e.g. "
            "'ckpt_save:step=3:err,nan_loss:step=5'. Empty disarms. "
            "Sites: ckpt_save, ckpt_write, ckpt_slow, nan_loss, "
            "slow_step, rank_hang, sigterm, decode_wedge, serve_flood, "
            "collective_stall, heartbeat_stall.",
            on_change=_arm_faults)
define_flag("anomaly_guard", True,
            "Trainer anomaly guard: a NaN/Inf loss skips the parameter "
            "update IN-PROGRAM (params/opt-state/buffers keep their "
            "pre-step values — a handful of fused selects, no host "
            "sync), the anomalous step is never checkpointed, and the "
            "loop aborts after FLAGS_max_anomalous_steps consecutive "
            "bad steps. The Trainer syncs the loss one step late "
            "(pipelined) to count anomalies; 0 restores the unguarded "
            "log-boundary-only sync behavior.")
define_flag("max_anomalous_steps", 10,
            "Abort training with AnomalousTrainingError after this many "
            "CONSECUTIVE anomalous (NaN/Inf or loss-spike) steps.")
define_flag("loss_spike_factor", 10.0,
            "Loss-spike anomaly threshold: a step whose loss exceeds "
            "this multiple of the rolling mean of recent good losses "
            "counts as anomalous (not checkpointed; counts toward the "
            "abort threshold). 0 disables spike detection; NaN/Inf "
            "detection is always on while FLAGS_anomaly_guard is set.")
define_flag("ckpt_save_retries", 3,
            "VerifiedCheckpointer: retries after a failed checkpoint "
            "save (transient I/O error), with jittered exponential "
            "backoff, before the error propagates.")
define_flag("ckpt_retry_backoff_s", 0.5,
            "Base delay (seconds) for checkpoint save retry backoff; "
            "doubles per attempt (capped at 8s), +/-50% jitter.")
define_flag("serve_prefill_chunk_tokens", 0,
            "ContinuousBatchingPredictor chunked prefill: prompts "
            "longer than this many tokens are ingested as page-aligned "
            "chunks interleaved with decode ticks (one mixed "
            "prefill+decode program per tick) instead of one "
            "monolithic prefill that stalls every in-flight decode. "
            "Rounded DOWN to a power-of-two multiple of page_size (a "
            "latency bound; min one page); the per-tick chunk shrinks "
            "under decode load. 0 disables (constructor "
            "prefill_chunk_tokens overrides).")
define_flag("serve_spec_draft_tokens", 0,
            "Speculative decoding: up to this many prompt-lookup "
            "drafted tokens are verified per compiled decode step "
            "(the verify span is draft_tokens + 1 wide; greedy output "
            "is bitwise-identical to plain greedy decode, sampled "
            "output rejection-sampling-correct). 0 disables "
            "(constructor spec_draft_tokens overrides; "
            "docs/SERVING.md 'Speculative decoding & sampling').")
define_flag("serve_spec_ngram_max", 3,
            "Prompt-lookup drafting: longest suffix n-gram matched "
            "against the request's own prompt+generation history when "
            "proposing draft tokens (host-side, no second model).")
define_flag("serve_sampling", False,
            "Serve-loop on-device sampling: compile the decode step "
            "with per-request temperature/top-k/top-p/seed as batched "
            "operands (requests without SamplingParams stay greedy — "
            "temperature 0 reduces to the argmax bitwise). Off keeps "
            "the plain argmax decode program.")
define_flag("serve_tp_degree", 1,
            "Tensor-parallel serving degree: each "
            "ContinuousBatchingPredictor replica spans this many "
            "devices — weights are NamedSharding'ed over the 'model' "
            "mesh axis and PagedKVPool pages are sharded over KV "
            "heads, so every serve program runs GSPMD-partitioned. "
            "Compiled-in geometry: joins the AOT bundle topology "
            "fingerprint (a mismatch invalidates with reason "
            "'topology'). 1 = single-device replicas (constructor "
            "tp_degree overrides; docs/SERVING.md 'Tensor-parallel "
            "replicas').")
define_flag("serve_role", "unified",
            "Disaggregated serving role of this replica: 'unified' "
            "(prefill+decode on one device group, the historical "
            "default), 'prefill' (fills KV pages and hands off at "
            "first token), or 'decode' (resumes the sync-free loop "
            "from an imported KV page span). Joins the AOT bundle "
            "fingerprint next to topology (mismatch invalidates with "
            "reason 'role'); per-role RuntimeConfig overlays apply via "
            "RuntimeConfig.for_role (docs/SERVING.md 'Disaggregated "
            "prefill/decode').")
define_flag("serve_decode_watchdog_s", 0.0,
            "ContinuousBatchingPredictor decode watchdog: if a decode "
            "step's host sync does not resolve within this many "
            "seconds, pending requests fail with last_status "
            "'watchdog' instead of generate() hanging. 0 disables "
            "(the resolve blocks unconditionally, no polling).")
define_flag("collective_timeout_s", 0.0,
            "Collective deadline: if a collective's host-side sync "
            "(distributed.wait / barrier) does not resolve within this "
            "many seconds, raise CollectiveTimeoutError (with a flight "
            "dump) instead of hanging forever on a peer that never "
            "reached the collective. 0 disables (block "
            "unconditionally).")
define_flag("ckpt_async_save", True,
            "Trainer checkpointing drains in the background: save() "
            "takes only the device->host snapshot at the step boundary "
            "and a drain thread runs the write/digest/manifest/rename "
            "pipeline (all atomicity/verification/retry guarantees "
            "kept; wait() blocks on the drain). Off restores the "
            "fully synchronous save.")
define_flag("ckpt_drain_deadline_s", 30.0,
            "Preemption drain deadline: on SIGTERM/SIGINT the Trainer "
            "blocks at most this many seconds for in-flight background "
            "checkpoint drains before exiting (a drain that misses the "
            "deadline counts robustness.ckpt_drain_timeouts and keeps "
            "draining on its daemon thread). <=0 waits forever.")

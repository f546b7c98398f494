"""Pretraining Trainer: the north-star training loop (SURVEY.md §7 M7).

Reference parity (capability): the PaddleNLP Trainer atop Fleet —
hybrid-parallel train loop with checkpoint/auto-resume, throughput/MFU
logging, and preemption-safe restart. The reference recovers failures by
relaunch-from-checkpoint (fleet elastic, SURVEY.md §5.3); TPU preemption
works the same way, so the loop here is: restore latest → scan steps →
async-checkpoint every save_steps → on SIGTERM checkpoint and exit 0 so
`paddle_tpu.distributed.launch` (or the TPU pod scheduler) restarts us.
"""
from __future__ import annotations

import hashlib
import math
import os
import signal
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..tensor import Tensor
from .. import observability as _obs
from ..framework import faults as _faults
from ..framework.flags import flag_value as _fv

__all__ = ["TrainingArguments", "Trainer", "SpeedMeter",
           "device_peak_flops", "PEAK_BF16_FLOPS", "UnknownDevicePeak",
           "AnomalousTrainingError"]


class AnomalousTrainingError(RuntimeError):
    """Training aborted: FLAGS_max_anomalous_steps consecutive NaN/Inf
    or loss-spike steps (docs/ROBUSTNESS.md). The last verified
    checkpoint is intact — anomalous steps are never checkpointed."""


# Published bf16 peak FLOP/s of one chip, keyed by the `device_kind` JAX
# reports. Source: Google Cloud TPU documentation, the per-generation
# system-architecture pages ("TPU v4", "TPU v5e", "TPU v5p", "TPU v6e");
# the v5e row is the one the on-chip-measurement guide quotes (197
# TFLOP/s bf16, 16 GB HBM at 819 GB/s). A device that is not here has no
# peak: asking for one is an error, never a default.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,      # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,           # how some releases name v5p
    "TPU v6 lite": 918e12,      # v6e
    "TPU v6e": 918e12,
}


class UnknownDevicePeak(LookupError):
    """No published peak for this device: MFU is not defined on it."""


def device_peak_flops(dtype: str = "bfloat16", device=None) -> float:
    """Peak FLOP/s of one local accelerator chip, for MFU accounting,
    from PEAK_BF16_FLOPS by `device_kind` (f32 counted at half the bf16
    rate). Raises UnknownDevicePeak for any other device."""
    dev = device if device is not None else jax.devices()[0]
    kind = getattr(dev, "device_kind", "")
    peak = PEAK_BF16_FLOPS.get(kind)
    if peak is None:
        raise UnknownDevicePeak(
            f"no published peak FLOP/s for device_kind {kind!r} "
            f"(platform {getattr(dev, 'platform', '?')!r}); known: "
            f"{sorted(PEAK_BF16_FLOPS)}. A utilisation comes only from a "
            "chip in this table.")
    return peak if dtype in ("bfloat16", "float16") else peak / 2


@dataclass
class SpeedMeter:
    """Rolling tokens/sec + MFU meter (the reference reports ips/tokens-per
    -sec per rank; MFU = achieved/(peak) with 6*N FLOPs per token)."""
    n_params: int
    n_devices: int = 1
    dtype: str = "bfloat16"
    window: int = 20
    _times: list = field(default_factory=list)
    _tokens: list = field(default_factory=list)

    def update(self, tokens: int):
        now = time.perf_counter()
        self._times.append(now)
        self._tokens.append(tokens)
        if len(self._times) > self.window + 1:
            self._times.pop(0)
            self._tokens.pop(0)

    @property
    def tokens_per_sec(self) -> float:
        if len(self._times) < 2:
            return 0.0
        dt = self._times[-1] - self._times[0]
        return sum(self._tokens[1:]) / dt if dt > 0 else 0.0

    @property
    def mfu(self) -> Optional[float]:
        """None on a device with no published peak (the CPU): the
        number is then not measured, not estimated."""
        try:
            peak = device_peak_flops(self.dtype) * self.n_devices
        except UnknownDevicePeak:
            return None
        return (6.0 * self.n_params * self.tokens_per_sec) / peak


@dataclass
class TrainingArguments:
    """Knob bag (parity-shaped with PaddleNLP TrainingArguments; only the
    fields the loop consumes — unknown knobs belong in DistributedStrategy)."""
    output_dir: str = "output"
    max_steps: int = 1000
    logging_steps: int = 10
    save_steps: int = 100
    seed: int = 42
    bf16: bool = False
    max_checkpoints: int = 3
    # hybrid parallel degrees (compiled to mesh axes by fleet)
    dp_degree: int = 1
    mp_degree: int = 1
    pp_degree: int = 1
    sharding_stage: int = 0  # 0=off, 1/2/3 = ZeRO stage
    sep_degree: int = 1      # context/sequence parallel


class Trainer:
    """Minimal-surface pretrain loop over TrainStep/DistTrainStep.

    train() returns a dict with final step/loss and speed stats. Resume is
    automatic: if output_dir holds a checkpoint, training continues from it
    (parity: Trainer resume_from_checkpoint=True by default under elastic).
    """

    def __init__(self, model, optimizer, loss_fn: Callable,
                 args: TrainingArguments, data_iter_fn: Callable,
                 tokens_per_batch: Optional[int] = None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.args = args
        self.data_iter_fn = data_iter_fn  # (start_step) -> iterator of batches
        self.tokens_per_batch = tokens_per_batch
        self._preempted = False
        self._step_obj = None
        self._ckpt = None

        distributed = (args.dp_degree * args.mp_degree * args.pp_degree *
                       args.sep_degree > 1 or args.sharding_stage >= 2)
        if distributed:
            from ..distributed import fleet
            from ..distributed.fleet import fleet_api
            if fleet_api._fleet_state["hcg"] is None:  # unless user init'd
                strategy = fleet.DistributedStrategy()
                strategy.hybrid_configs = {
                    "dp_degree": args.dp_degree,
                    "mp_degree": args.mp_degree,
                    "pp_degree": args.pp_degree,
                    "sep_degree": args.sep_degree,
                }
                fleet.init(is_collective=True, strategy=strategy)
            from ..distributed.fleet.dist_step import DistTrainStep
            self._step_obj = DistTrainStep(
                model, optimizer, loss_fn,
                sharding_stage=args.sharding_stage)
        else:
            from ..jit.bridge import TrainStep
            self._step_obj = TrainStep(model, optimizer, loss_fn)

    # ------------------------------------------------------- checkpointing --
    def _ckpt_mgr(self):
        if self._ckpt is None:
            from ..distributed.checkpoint import VerifiedCheckpointer
            self._ckpt = VerifiedCheckpointer(
                os.path.join(self.args.output_dir, "checkpoints"),
                max_to_keep=self.args.max_checkpoints,
                async_save=bool(_fv("ckpt_async_save")))
        return self._ckpt

    def _full_state(self, step: int):
        """Model + opt-state + rng as one checkpoint-friendly tree. The
        opt state lives in the compiled step object (donated buffers);
        model params track it after every step, so state_dict() is
        current."""
        state = {"model": dict(self.model.state_dict()),
                 "step": np.asarray(step, dtype=np.int64)}
        opt_leaves = jax.tree_util.tree_leaves(self._step_obj.opt_state)
        state["opt"] = {str(i): leaf for i, leaf in enumerate(opt_leaves)}
        return state

    def _opt_fingerprint(self) -> str:
        """Fingerprint of the optimizer state *structure* (treedef plus
        per-leaf shape/dtype). Persisted in the checkpoint manifest:
        opt leaves are stored by flat index, so restoring into a
        different tree would silently mis-restore — the fingerprint
        turns that into a hard, attributable error."""
        leaves, treedef = jax.tree_util.tree_flatten(
            self._step_obj.opt_state)
        desc = "|".join(
            [str(treedef)]
            + [f"{tuple(np.shape(l))}:{getattr(l, 'dtype', type(l))}"
               for l in leaves])
        return hashlib.sha256(desc.encode()).hexdigest()[:16]

    def _save(self, step: int):
        self._ckpt_mgr().save(step, self._full_state(step),
                              meta={"opt_treedef": self._opt_fingerprint()})

    def _try_resume(self) -> int:
        res = self._ckpt_mgr().restore_latest()
        if res is None:
            return 0
        step, restored, meta = res
        fp, cur = meta.get("opt_treedef"), self._opt_fingerprint()
        if fp is not None and fp != cur:
            raise RuntimeError(
                f"checkpoint step {step} was written with a different "
                f"optimizer state tree (treedef fingerprint {fp} != "
                f"current {cur}): restoring by flat leaf index would "
                "silently mis-restore. Rebuild the Trainer with the "
                "original optimizer configuration, or start fresh with "
                "train(resume=False).")
        # write model params back (jnp.array: force XLA-owned copies —
        # donated buffers must never alias host numpy memory)
        model_sd = self.model.state_dict()
        for k, v in model_sd.items():
            if k in restored["model"]:
                v._value = jnp.array(restored["model"][k])
        # rebuild opt state with the original treedef
        leaves, treedef = jax.tree_util.tree_flatten(self._step_obj.opt_state)
        if len(restored["opt"]) != len(leaves):
            raise RuntimeError(
                f"checkpoint step {step} holds {len(restored['opt'])} "
                f"optimizer leaves but the current optimizer has "
                f"{len(leaves)} — the optimizer changed between runs.")
        new_leaves = [jnp.array(restored["opt"][str(i)])
                      for i in range(len(leaves))]
        self._step_obj._opt_state = jax.tree_util.tree_unflatten(
            treedef, new_leaves)
        return int(np.asarray(restored["step"]))

    # ------------------------------------------------------------ the loop --
    _PREEMPT_SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def _install_preemption_hook(self):
        """SIGTERM/SIGINT -> checkpoint-and-exit at the next step
        boundary. Chains to any pre-existing handler (so an outer
        framework's hook still runs) and records the originals for
        restoration when train() returns — installing a Trainer must
        not permanently clobber the process's signal handling."""
        self._prev_handlers = {}
        self._flight_reason = None

        def handler(signum, frame):
            self._preempted = True  # acted on at the next step boundary
            # crash-time forensics are deferred to that boundary:
            # dumping here would take the flight-recorder/registry
            # locks the interrupted main thread may already hold
            # (non-reentrant -> self-deadlock inside a signal handler)
            self._flight_reason = f"signal_{signum}"
            prev = self._prev_handlers.get(signum)
            if callable(prev) and prev is not signal.default_int_handler:
                prev(signum, frame)  # chain (but not KeyboardInterrupt)

        for s in self._PREEMPT_SIGNALS:
            try:
                self._prev_handlers[s] = signal.signal(s, handler)
            except ValueError:
                pass  # not the main thread (e.g. under a test runner)

    def _restore_preemption_hook(self):
        for s, prev in getattr(self, "_prev_handlers", {}).items():
            if prev is None:
                continue  # non-Python handler: leave as-is
            try:
                signal.signal(s, prev)
            except ValueError:
                pass
        self._prev_handlers = {}

    # -------------------------------------------------------- anomaly guard --
    def _guard_check(self, step: int, loss, parent=None) -> bool:
        """Sync one step's loss and classify it. Returns True when the
        step is anomalous (NaN/Inf, or a spike vs the rolling mean of
        recent good losses). Consecutive anomalies beyond
        FLAGS_max_anomalous_steps abort with AnomalousTrainingError.
        Called at most once per step (the `nan_loss` fault site is
        consumed here, one check per step)."""
        with _obs.span("train.loss_sync", parent=parent, step=step + 1):
            lv = float(loss)
        fa = _faults.check("nan_loss", step=step)
        if fa is not None:
            lv = float("inf") if fa.mode == "inf" else float("nan")
        anomalous, reason = not math.isfinite(lv), "nonfinite"
        spike = float(_fv("loss_spike_factor"))
        window = self._good_losses
        if not anomalous and spike > 0 and len(window) >= 5:
            mean = sum(window) / len(window)
            if abs(lv) > spike * max(abs(mean), 1e-12):
                anomalous, reason = True, "spike"
        if anomalous:
            self._anom_consec += 1
            self._anom_total += 1
            _obs.counter("robustness.anomalies_skipped").inc(reason=reason)
            _obs.start_span("train.anomaly_skip", parent=None,
                            step=step + 1, reason=reason,
                            consecutive=self._anom_consec).end()
            self._log({"anomalous_step": step + 1, "loss": lv,
                       "reason": reason,
                       "consecutive": self._anom_consec})
            limit = int(_fv("max_anomalous_steps"))
            if self._anom_consec >= limit:
                try:  # drain in-flight saves so the cited fallback step
                    # is accurate (bounded, best-effort: this path is
                    # already fatal and a parked drain error of ANY kind
                    # must not replace the AnomalousTrainingError)
                    self._ckpt_mgr().wait(timeout_s=5.0)
                except Exception:
                    pass
                last_ok = self._ckpt_mgr().latest_verified()
                _obs.flight_dump(reason="anomalous_training")
                raise AnomalousTrainingError(
                    f"aborting after {self._anom_consec} consecutive "
                    f"anomalous steps (last loss {lv!r} at step "
                    f"{step + 1}, reason {reason}); the newest verified "
                    f"checkpoint is step {last_ok} — anomalous steps "
                    "were never checkpointed. Lower the learning rate, "
                    "inspect the data at this step range, or raise "
                    "FLAGS_max_anomalous_steps.")
        else:
            self._anom_consec = 0
            window.append(lv)
        return anomalous

    def train(self, resume: bool = True):
        args = self.args
        os.makedirs(args.output_dir, exist_ok=True)
        self._install_preemption_hook()
        # per-rank liveness: under the elastic launcher every worker
        # beats into its own PADDLE_RANK_HEARTBEAT file; the launcher's
        # stale-heartbeat detector reads silence there as a wedged rank
        self._hb = None
        hb_path = os.environ.get("PADDLE_RANK_HEARTBEAT")
        if hb_path:
            from ..observability import RankHeartbeat
            self._hb = RankHeartbeat(hb_path, interval=float(
                os.environ.get("PADDLE_RANK_HEARTBEAT_INTERVAL", "1.0")))
            self._hb_rank = os.environ.get(
                "RANK", os.environ.get("PADDLE_TRAINER_ID", "0"))
            self._hb.beat(phase="init", rank=self._hb_rank)
        try:
            return self._train_loop(resume)
        finally:
            if self._hb is not None:
                self._hb.close()
            self._restore_preemption_hook()

    def _train_loop(self, resume: bool):
        args = self.args
        start_step = self._try_resume() if resume else 0
        if self._hb is not None:
            # the resume marker: tools/trace_report.py --recovery ends
            # the incident timeline at this beat
            self._hb.beat(force=True, phase="resumed", step=start_step,
                          rank=self._hb_rank)
        guard = bool(_fv("anomaly_guard"))
        self._anom_consec = 0
        self._anom_total = 0
        self._good_losses = deque(maxlen=20)

        meter = SpeedMeter(
            n_params=sum(int(np.prod(p.shape))
                         for p in self.model.parameters()),
            n_devices=jax.device_count(),
            dtype="bfloat16" if args.bf16 else "float32")
        logs = []
        step = start_step
        loss = None
        loss_val = float("nan")
        save_owed = False       # a save boundary fell on an anomalous step
        pending = None          # (step, loss) awaiting its guard check
        data = self.data_iter_fn(start_step)
        t_start = time.perf_counter()
        for step in range(start_step, args.max_steps):
            # step phase spans (data/dispatch/loss-sync/anomaly-skip):
            # one trace per step, reconstructable as a waterfall by
            # tools/trace_report.py. All no-ops when telemetry is off.
            st_sp = _obs.start_span("train.step", parent=None,
                                    step=step + 1)
            if self._hb is not None:
                self._hb.beat(phase="step", step=step + 1,
                              rank=self._hb_rank)
            fa = _faults.check("slow_step", step=step)
            if fa is not None:
                time.sleep(float(fa.params.get("sleep", 0.05)))
            fa = _faults.check("slow_rank", step=step)
            if fa is not None:
                # per-step straggler injection on ONE rank: with a
                # rank=K param only that rank pays the sleep (the spec
                # is armed fleet-wide through one shared env). The
                # sleep runs inside its own child span so the fleet
                # aggregator's dominant-span diagnosis names it.
                target = fa.params.get("rank")
                if target is None or int(target) == self._env_rank():
                    with _obs.span("train.straggle", parent=st_sp,
                                   step=step + 1):
                        time.sleep(float(fa.params.get("sleep", 0.25)))
            fa = _faults.check("rank_hang", step=step)
            if fa is not None:
                # deliberately wedge: an alive pid whose heartbeat/log
                # go silent — the launcher's stale-heartbeat detector
                # must notice and SIGKILL this rank into a restart
                time.sleep(float(fa.params.get("sleep", 600.0)))
            # rank_slow: persistent MULTIPLICATIVE inflation on one
            # rank — the checked-on-every-rank / paid-on-one pattern of
            # slow_rank, but scaled to the step's measured work
            # (factor=F pays (F-1)x the data+dispatch wall) so it
            # models a degraded host rather than a fixed stall. The
            # mitigation actuator (distributed.launch.mitigate) exists
            # to evict exactly this.
            fa = _faults.check("rank_slow", step=step)
            rank_slow = fa if fa is not None and (
                fa.params.get("rank") is None
                or int(fa.params["rank"]) == self._env_rank()) else None
            t_work0 = time.perf_counter() if rank_slow is not None \
                else 0.0
            with _obs.span("train.data", parent=st_sp, step=step + 1):
                batch = next(data)
            if not isinstance(batch, (tuple, list)):
                batch = (batch,)
            with _obs.span("train.dispatch", parent=st_sp,
                           step=step + 1):
                loss = self._step_obj(*batch)
            if rank_slow is not None:
                factor = float(rank_slow.params.get("factor", 3.0))
                pad = max(0.0, factor - 1.0) \
                    * (time.perf_counter() - t_work0)
                pad = max(pad, float(rank_slow.params.get("min_s",
                                                          0.0)))
                with _obs.span("train.straggle", parent=st_sp,
                               step=step + 1):
                    time.sleep(pad)
            if _faults.check("sigterm", step=step) is not None:
                os.kill(os.getpid(), signal.SIGTERM)  # -> preemption hook
            if self.tokens_per_batch:
                meter.update(self.tokens_per_batch)
            log_b = (step + 1) % args.logging_steps == 0 or self._preempted
            save_b = (step + 1) % args.save_steps == 0 or self._preempted
            last_b = step == args.max_steps - 1
            step_anom = False
            if guard:
                # pipelined check: the previous step's loss syncs only
                # after this step is dispatched, so the guard does not
                # serialize the dispatch queue; boundaries (log/save/
                # preempt/last) check the current step immediately
                if pending is not None:
                    ps, pl = pending
                    pending = None
                    self._guard_check(ps, pl, parent=st_sp)
                if log_b or save_b or last_b:
                    step_anom = self._guard_check(step, loss,
                                                  parent=st_sp)
                else:
                    pending = (step, loss)
            if log_b:
                if guard:
                    # the boundary guard check above already synced this
                    # step's loss; a second span would double-count the
                    # site for a free host read
                    loss_val = float(loss)
                else:
                    with _obs.span("train.loss_sync", parent=st_sp,
                                   step=step + 1):
                        loss_val = float(loss)  # sync at log boundary only
                mfu = meter.mfu
                rec = {"step": step + 1, "loss": round(loss_val, 6),
                       "tokens_per_sec": round(meter.tokens_per_sec, 2),
                       "mfu": None if mfu is None else round(mfu, 4)}
                logs.append(rec)
                self._log(rec)
                if _obs.enabled():
                    # per-step series come from the step object; the
                    # loop owns loss (synced only at log boundaries)
                    if math.isfinite(loss_val):
                        _obs.gauge("train.loss").set(loss_val)
                    executed = step + 1 - start_step
                    _obs.gauge("robustness.goodput").set(
                        (executed - self._anom_total)
                        / max(executed, 1))
                    if getattr(self._step_obj, "_obs", None) is None:
                        # uninstrumented step (single-device TrainStep):
                        # the loop is the only flusher. Instrumented
                        # steps export per step already — a second flush
                        # here would duplicate snapshots.
                        _obs.maybe_export(step=step + 1)
            if step_anom and save_b:
                # never checkpoint an anomalous step: the save is owed
                # and lands at the next verified-good step
                save_owed = True
                self._log({"checkpoint_skipped_at": step + 1,
                           "reason": "anomalous_step"})
            elif (save_b or (save_owed and guard and not step_anom
                             and pending is None)):
                self._save(step + 1)
                save_owed = False
            st_sp.end(anomalous=step_anom)
            if self._preempted:
                _obs.flight_dump(
                    reason=getattr(self, "_flight_reason", None)
                    or "preempted")
                # just-in-time preemption checkpoint: drain in-flight
                # background saves, but bounded — the scheduler's grace
                # window is finite and a wedged store must not turn a
                # clean preemption into a SIGKILL mid-write
                ddl = float(_fv("ckpt_drain_deadline_s"))
                drained = self._ckpt_mgr().wait(
                    timeout_s=ddl if ddl > 0 else None)
                self._log({"preempted_at": step + 1,
                           "ckpt_drained": drained})
                break
        else:
            step = args.max_steps - 1
            if loss is not None:
                loss_val = float(loss)
        if not self._preempted:   # the preemption path already drained
            self._ckpt_mgr().wait()   # (bounded); don't re-block here
        executed = max(step + 1 - start_step, 1)
        return {"start_step": start_step, "final_step": step + 1,
                "final_loss": loss_val,
                "wall_s": time.perf_counter() - t_start,
                "tokens_per_sec": meter.tokens_per_sec, "mfu": meter.mfu,
                "anomalous_steps": self._anom_total,
                "goodput": (executed - self._anom_total) / executed,
                "preempted": self._preempted, "logs": logs}

    @staticmethod
    def _env_rank() -> int:
        """This worker's global rank under the launcher (0 standalone)."""
        try:
            return int(os.environ.get(
                "RANK", os.environ.get("PADDLE_TRAINER_ID", "0")))
        except ValueError:
            return 0

    def _log(self, rec: dict):
        import logging
        logging.getLogger("paddle_tpu.trainer").info("%s", rec)

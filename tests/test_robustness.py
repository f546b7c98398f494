"""Fault-tolerance suite (PR 4): deterministic fault injection driving
every recovery path — checkpoint retry/backoff and corrupt-checkpoint
fallback, the trainer's NaN-skip + abort threshold and SIGTERM resume,
serving deadlines / load shedding / the decode watchdog, and the
launcher's restart backoff. Oracle style mirrors the ISSUE acceptance
criteria: with a fault armed the system must *recover* (complete, fall
back, or fail the right requests) and the robustness.* counters must
record it."""
import math
import os
import signal

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
import paddle_tpu.observability as obs
from paddle_tpu import nn
from paddle_tpu.framework import faults
from paddle_tpu.trainer import (AnomalousTrainingError, Trainer,
                                TrainingArguments)


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    paddle.set_flags({"fault_injection": ""})


def _counter_total(name):
    m = obs.get_registry().get(name)
    return sum(s.value for s in m.samples()) if m else 0.0


# ---------------------------------------------------------------------------
# fault registry
# ---------------------------------------------------------------------------
class TestFaultRegistry:
    def test_parse_spec(self):
        sp = faults.FaultSpec.parse("ckpt_save:step=3:err")
        assert sp.site == "ckpt_save" and sp.mode == "err"
        assert sp.step_lo == sp.step_hi == 3 and sp.times == 1
        sp = faults.FaultSpec.parse("slow_step:step=2-5:times=0:sleep=0.25")
        assert sp.mode == "sleep" and sp.params["sleep"] == 0.25
        assert (sp.step_lo, sp.step_hi, sp.times) == (2, 5, 0)

    def test_default_modes_and_bad_token(self):
        assert faults.FaultSpec.parse("nan_loss").mode == "nan"
        assert faults.FaultSpec.parse("sigterm").mode == "sigterm"
        with pytest.raises(ValueError, match="unknown token"):
            faults.FaultSpec.parse("ckpt_save:frobnicate")

    def test_step_match_fires_once(self):
        reg = faults.FaultRegistry()
        reg.arm("s:step=3:err")
        assert reg.check("s", step=2) is None
        act = reg.check("s", step=3)
        assert act is not None and act.mode == "err"
        assert reg.check("s", step=3) is None  # times=1 consumed
        assert len(reg.events()) == 1

    def test_hit_every_times(self):
        reg = faults.FaultRegistry()
        reg.arm("a:hit=2,b:every=2:times=2")
        assert reg.check("a") is None and reg.check("a") is not None
        fires = [reg.check("b") is not None for _ in range(6)]
        assert fires == [False, True, False, True, False, False]

    def test_every_defaults_to_recurring(self):
        # every=/prob= describe recurring faults: without an explicit
        # times= they must keep firing, per the documented grammar
        reg = faults.FaultRegistry()
        reg.arm("s:every=2")
        fires = [reg.check("s") is not None for _ in range(6)]
        assert fires == [False, True, False, True, False, True]
        assert faults.FaultSpec.parse("s:step=3").times == 1  # one-shot

    def test_prob_deterministic(self):
        def draw():
            reg = faults.FaultRegistry()
            reg.arm("s:prob=0.5:seed=7:times=0")
            return [reg.check("s") is not None for _ in range(64)]

        a, b = draw(), draw()
        assert a == b and any(a) and not all(a)

    def test_flag_wiring_and_disarm(self):
        paddle.set_flags({"fault_injection": "nan_loss:step=1"})
        assert faults.armed()
        paddle.set_flags({"fault_injection": ""})
        assert not faults.armed()
        assert faults.check("nan_loss", step=1) is None

    def test_unmatched_site_is_none(self):
        reg = faults.FaultRegistry()
        reg.arm("x:err")
        assert reg.check("y") is None


# ---------------------------------------------------------------------------
# verified checkpointing
# ---------------------------------------------------------------------------
def _tree(seed, extra=None):
    rs = np.random.RandomState(seed)
    t = {"model": {"w": rs.randn(4, 3).astype(np.float32),
                   "b": rs.randn(3).astype(np.float32)},
         "opt": {"0": rs.randn(4, 3).astype(np.float32)},
         "step": np.asarray(seed, np.int64)}
    if extra:
        t.update(extra)
    return t


def _damage_latest(ckpt, how="truncate"):
    d = ckpt._step_dir(max(ckpt.steps()))
    files = sorted(f for f in os.listdir(d) if f.endswith(".bin"))
    victim = os.path.join(d, files[0])
    if how == "truncate":
        with open(victim, "r+b") as f:
            f.truncate(max(1, os.path.getsize(victim) // 2))
    elif how == "drop_manifest":
        os.unlink(os.path.join(d, "manifest.json"))


class TestVerifiedCheckpointer:
    def _mk(self, tmp_path, **kw):
        from paddle_tpu.distributed.checkpoint import VerifiedCheckpointer
        kw.setdefault("backoff_s", 0.01)
        return VerifiedCheckpointer(str(tmp_path / "ck"), **kw)

    def test_roundtrip_and_meta(self, tmp_path):
        ckpt = self._mk(tmp_path)
        ckpt.save(2, _tree(2), meta={"opt_treedef": "abcd"})
        step, tree, meta = ckpt.restore_latest()
        assert step == 2 and meta["opt_treedef"] == "abcd"
        np.testing.assert_array_equal(tree["model"]["w"],
                                      _tree(2)["model"]["w"])
        assert int(np.asarray(tree["step"])) == 2
        # atomic: no temp dirs survive a completed save
        assert not [n for n in os.listdir(ckpt._dir)
                    if n.startswith(".tmp-")]

    def test_bfloat16_roundtrip(self, tmp_path):
        import ml_dtypes
        ckpt = self._mk(tmp_path)
        a = np.arange(12, dtype=np.float32).reshape(3, 4) \
            .astype(ml_dtypes.bfloat16)
        ckpt.save(1, {"m": {"w": a}})
        _, tree, _ = ckpt.restore_latest()
        assert tree["m"]["w"].dtype == a.dtype
        np.testing.assert_array_equal(
            np.asarray(tree["m"]["w"], np.float32),
            np.asarray(a, np.float32))

    @pytest.mark.parametrize("how", ["truncate", "drop_manifest"])
    def test_fallback_to_verified(self, tmp_path, how):
        ckpt = self._mk(tmp_path)
        ckpt.save(1, _tree(1))
        ckpt.save(2, _tree(2))
        _damage_latest(ckpt, how)
        before = _counter_total("robustness.ckpt_fallbacks")
        ok, why = ckpt.verify(2)
        assert not ok
        step, tree, _ = ckpt.restore_latest()
        assert step == 1
        assert int(np.asarray(tree["step"])) == 1
        assert _counter_total("robustness.ckpt_fallbacks") >= before + 1

    def test_injected_corruption_modes(self, tmp_path):
        for mode in ("truncate", "corrupt", "drop_manifest"):
            ckpt = self._mk(tmp_path / mode)
            ckpt.save(1, _tree(1))
            paddle.set_flags(
                {"fault_injection": f"ckpt_write:step=2:{mode}"})
            ckpt.save(2, _tree(2))
            assert not ckpt.verify(2)[0], mode
            assert ckpt.latest_verified() == 1, mode
            paddle.set_flags({"fault_injection": ""})

    def test_save_retry_recovers(self, tmp_path):
        ckpt = self._mk(tmp_path)
        paddle.set_flags({"fault_injection": "ckpt_save:hit=1:err"})
        before = _counter_total("robustness.ckpt_retries")
        ckpt.save(1, _tree(1))  # first attempt raises, retry succeeds
        assert ckpt.verify(1)[0]
        assert _counter_total("robustness.ckpt_retries") >= before + 1

    def test_save_retries_exhausted(self, tmp_path):
        ckpt = self._mk(tmp_path, retries=2)
        paddle.set_flags({"fault_injection": "ckpt_save:times=0:err"})
        with pytest.raises(OSError):
            ckpt.save(1, _tree(1))
        assert ckpt.restore_latest() is None

    def test_gc_keeps_newest(self, tmp_path):
        ckpt = self._mk(tmp_path, max_to_keep=2)
        for s in (1, 2, 3, 4):
            ckpt.save(s, _tree(s))
        assert ckpt.steps() == [3, 4]


class TestAsyncVerifiedCheckpointer:
    """The async drain (PR 7): save() pays only the device->host
    snapshot; the atomic/verified/retry pipeline runs in background;
    wait() blocks on the drain (optionally with a deadline); restore
    only ever sees fully-landed checkpoints."""

    def _mk(self, tmp_path, **kw):
        from paddle_tpu.distributed.checkpoint import VerifiedCheckpointer
        kw.setdefault("backoff_s", 0.01)
        kw.setdefault("async_save", True)
        return VerifiedCheckpointer(str(tmp_path / "ck"), **kw)

    def test_save_does_not_block_on_slow_store(self, tmp_path):
        import time
        ckpt = self._mk(tmp_path)
        paddle.set_flags(
            {"fault_injection": "ckpt_slow:times=0:sleep=0.4"})
        t0 = time.perf_counter()
        ckpt.save(1, _tree(1))
        dt = time.perf_counter() - t0
        assert dt < 0.2, f"async save blocked {dt:.3f}s"
        g = obs.get_registry().get("robustness.ckpt_stall_seconds")
        assert g is not None
        assert [s.value for s in g.samples()][-1] < 0.2
        assert ckpt.wait(timeout_s=10)
        assert ckpt.verify(1)[0]
        paddle.set_flags({"fault_injection": ""})
        # contrast: the synchronous store pays the stall in save()
        from paddle_tpu.distributed.checkpoint import VerifiedCheckpointer
        sync = VerifiedCheckpointer(str(tmp_path / "sync"))
        paddle.set_flags(
            {"fault_injection": "ckpt_slow:times=0:sleep=0.4"})
        t0 = time.perf_counter()
        sync.save(1, _tree(1))
        assert time.perf_counter() - t0 >= 0.4

    def test_wait_deadline_expires_then_drains(self, tmp_path):
        ckpt = self._mk(tmp_path)
        paddle.set_flags(
            {"fault_injection": "ckpt_slow:times=0:sleep=0.5"})
        before = _counter_total("robustness.ckpt_drain_timeouts")
        ckpt.save(1, _tree(1))
        assert ckpt.wait(timeout_s=0.05) is False
        assert _counter_total("robustness.ckpt_drain_timeouts") \
            >= before + 1
        assert ckpt.wait(timeout_s=10) is True   # daemon kept draining
        assert ckpt.verify(1)[0]

    def test_async_retry_recovers_in_background(self, tmp_path):
        ckpt = self._mk(tmp_path)
        paddle.set_flags({"fault_injection": "ckpt_save:hit=1:err"})
        before = _counter_total("robustness.ckpt_retries")
        ckpt.save(1, _tree(1))
        assert ckpt.wait(timeout_s=10)
        assert ckpt.verify(1)[0]
        assert _counter_total("robustness.ckpt_retries") >= before + 1

    def test_drain_failure_surfaces_at_wait(self, tmp_path):
        ckpt = self._mk(tmp_path, retries=1)
        paddle.set_flags({"fault_injection": "ckpt_save:times=0:err"})
        ckpt.save(1, _tree(1))   # returns immediately
        with pytest.raises(OSError):
            ckpt.wait(timeout_s=10)
        assert ckpt.restore_latest() is None

    def test_crash_mid_drain_falls_back_to_last_verified(self, tmp_path):
        """The elastic-restart contract: a process killed while a drain
        is mid-write leaves only fully-landed checkpoints — the
        restarted process restores the last VERIFIED step."""
        import threading
        from paddle_tpu.distributed.checkpoint import VerifiedCheckpointer
        ckpt = self._mk(tmp_path)
        ckpt.save(2, _tree(2))
        assert ckpt.wait(timeout_s=10)
        # the step-4 drain wedges inside the store; the "crash" is
        # simply never waiting (a killed process's daemon dies mid-write
        # — atomic rename means nothing partial lands under a step name)
        gate = threading.Event()
        ckpt._save_with_retry = lambda *a, **kw: gate.wait()
        ckpt.save(4, _tree(4))
        fresh = VerifiedCheckpointer(str(tmp_path / "ck"))  # restarted
        step, tree, _ = fresh.restore_latest()
        assert step == 2
        assert int(np.asarray(tree["step"])) == 2
        gate.set()   # unwedge the daemon before teardown

    def test_gc_never_collects_inflight_drain(self, tmp_path):
        """Keep-list race: a step whose drain has not landed must
        survive every other save's gc pass."""
        ckpt = self._mk(tmp_path, max_to_keep=1, async_save=False)
        ckpt.save(3, _tree(3))
        with ckpt._cv:
            ckpt._pending.add(3)   # a re-drain of 3 still in flight
        ckpt.save(4, _tree(4))     # gc would normally collect 3
        assert set(ckpt.steps()) == {3, 4}
        with ckpt._cv:
            ckpt._pending.discard(3)
        ckpt.save(5, _tree(5))     # landed -> collectable again
        assert ckpt.steps() == [5]

    def test_snapshot_is_owned_not_a_view(self, tmp_path):
        """The step-boundary contract: mutating a numpy-backed leaf
        AFTER save() returns must not change what the drain writes
        (np.asarray is a no-copy identity for ndarrays)."""
        import threading
        ckpt = self._mk(tmp_path)
        tree = _tree(1)
        want = tree["model"]["w"].copy()
        gate = threading.Event()
        orig = ckpt._save_with_retry

        def gated(step, flat, meta):
            gate.wait(timeout=10)    # hold the drain past the mutation
            return orig(step, flat, meta)

        ckpt._save_with_retry = gated
        ckpt.save(1, tree)
        tree["model"]["w"][:] = -999.0   # caller reuses its buffer
        gate.set()
        assert ckpt.wait(timeout_s=10)
        _, restored, _ = ckpt.restore_latest()
        np.testing.assert_array_equal(restored["model"]["w"], want)

    def test_fifo_drain_ordering_and_close(self, tmp_path):
        ckpt = self._mk(tmp_path, max_to_keep=2)
        for s in (1, 2, 3, 4):
            ckpt.save(s, _tree(s))
        assert ckpt.wait(timeout_s=10)
        assert ckpt.steps() == [3, 4]
        ckpt.close()


class TestCollectiveTimeout:
    """The collective deadline (PR 7): a peer that never shows up
    raises CollectiveTimeoutError instead of hanging forever."""

    def teardown_method(self, method):
        paddle.set_flags({"collective_timeout_s": 0.0,
                          "fault_injection": ""})

    def test_wait_times_out_on_stall(self):
        import paddle_tpu.distributed as dist
        paddle.set_flags({"collective_timeout_s": 0.2,
                          "fault_injection": "collective_stall:sleep=5"})
        t = paddle.to_tensor(np.zeros(4, np.float32))
        before = _counter_total("robustness.collective_timeouts")
        with pytest.raises(dist.CollectiveTimeoutError, match="0.2s"):
            dist.wait(t)
        assert _counter_total("robustness.collective_timeouts") \
            >= before + 1

    def test_wait_resolves_within_deadline(self):
        import paddle_tpu.distributed as dist
        paddle.set_flags({"collective_timeout_s": 5.0})
        t = paddle.to_tensor(np.ones(4, np.float32)) * 2
        out = dist.wait(t)
        np.testing.assert_allclose(out.numpy(), np.full(4, 2.0))

    def test_barrier_timeout_and_explicit_override(self):
        import paddle_tpu.distributed as dist
        paddle.set_flags({"fault_injection": "collective_stall:sleep=5"})
        with pytest.raises(dist.CollectiveTimeoutError):
            dist.barrier(timeout_s=0.2)     # explicit beats the flag
        paddle.set_flags({"fault_injection": ""})
        dist.barrier(timeout_s=0.5)         # healthy: no trip

    def test_disabled_deadline_blocks_normally(self):
        import paddle_tpu.distributed as dist
        t = paddle.to_tensor(np.zeros(2, np.float32))
        dist.wait(t)          # FLAGS_collective_timeout_s=0: plain sync
        dist.barrier()


# ---------------------------------------------------------------------------
# trainer: anomaly guard, preemption, fingerprint
# ---------------------------------------------------------------------------
def _make(seed=0, sgd=False):
    paddle.seed(seed)
    model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    if sgd:
        opt = paddle.optimizer.SGD(learning_rate=1e-2,
                                   parameters=model.parameters())
    else:
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=model.parameters())
    return model, opt


def _data_iter_fn(start_step):
    def gen():
        step = start_step
        while True:
            rs = np.random.RandomState(step)
            yield (paddle.to_tensor(rs.randn(8, 8).astype(np.float32)),
                   paddle.to_tensor(rs.randn(8, 4).astype(np.float32)))
            step += 1
    return gen()


def _loss_fn(out, y):
    return F.mse_loss(out, y)


def _trainer(tmp_path, max_steps, save_steps=2, logging_steps=1, **mk):
    model, opt = _make(**mk)
    args = TrainingArguments(output_dir=str(tmp_path), max_steps=max_steps,
                             logging_steps=logging_steps,
                             save_steps=save_steps)
    return Trainer(model, opt, _loss_fn, args, _data_iter_fn,
                   tokens_per_batch=8)


class TestTrainerAnomalyGuard:
    def test_nan_step_skipped_never_checkpointed(self, tmp_path):
        # step index 3 is the save boundary for checkpoint "4": the NaN
        # lands exactly there, so "never checkpoint an anomalous step"
        # is what keeps "4" off disk; the owed save lands at step 5
        paddle.set_flags({"fault_injection": "nan_loss:step=3"})
        before = _counter_total("robustness.anomalies_skipped")
        res = _trainer(tmp_path, max_steps=6).train(resume=False)
        assert res["final_step"] == 6
        assert res["anomalous_steps"] == 1
        assert math.isfinite(res["final_loss"])
        assert _counter_total("robustness.anomalies_skipped") >= before + 1
        from paddle_tpu.distributed.checkpoint import VerifiedCheckpointer
        ckpt = VerifiedCheckpointer(str(tmp_path / "checkpoints"))
        steps = ckpt.steps()
        assert 4 not in steps          # anomalous step never checkpointed
        assert 5 in steps and 6 in steps   # owed save + final boundary

    def test_abort_after_consecutive_anomalies(self, tmp_path):
        paddle.set_flags(
            {"fault_injection": "nan_loss:step=1-99:times=0"})
        try:
            paddle.set_flags({"max_anomalous_steps": 3})
            with pytest.raises(AnomalousTrainingError,
                               match="consecutive anomalous"):
                _trainer(tmp_path, max_steps=20).train(resume=False)
        finally:
            paddle.set_flags({"max_anomalous_steps": 10})

    def test_guard_off_restores_old_behavior(self, tmp_path):
        paddle.set_flags({"fault_injection": "nan_loss:step=0-99:times=0",
                          "anomaly_guard": False})
        try:
            res = _trainer(tmp_path, max_steps=3).train(resume=False)
            assert res["final_step"] == 3
            assert res["anomalous_steps"] == 0  # guard never consulted
        finally:
            paddle.set_flags({"anomaly_guard": True})

    def test_inprogram_guard_keeps_params(self, tmp_path):
        """A REAL NaN loss must leave params untouched (the in-program
        select), not just skip bookkeeping."""
        from paddle_tpu.jit.bridge import TrainStep
        model, opt = _make()
        step = TrainStep(model, opt, _loss_fn)
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(8, 8).astype(np.float32))
        y = paddle.to_tensor(np.random.RandomState(1)
                             .randn(8, 4).astype(np.float32))
        step(x, y)  # one good step
        before = [np.asarray(p._value).copy() for p in model.parameters()]
        bad_y = paddle.to_tensor(
            np.full((8, 4), np.nan, np.float32))
        loss = step(x, bad_y)
        assert not math.isfinite(float(loss))
        after = [np.asarray(p._value) for p in model.parameters()]
        for b, a in zip(before, after):
            np.testing.assert_array_equal(b, a)


class TestTrainerPreemption:
    def test_sigterm_fault_resume_bounded_loss(self, tmp_path):
        paddle.set_flags({"fault_injection": "sigterm:step=3"})
        tr = _trainer(tmp_path, max_steps=10, save_steps=2)
        res = tr.train(resume=False)
        assert res["preempted"]
        paddle.set_flags({"fault_injection": ""})
        tr2 = _trainer(tmp_path, max_steps=10, save_steps=2)
        res2 = tr2.train()
        # acceptance: resume loses at most save_steps steps
        assert res2["start_step"] >= res["final_step"] - 2
        assert res2["final_step"] == 10 and not res2["preempted"]

    def test_handler_chained_and_restored(self, tmp_path):
        calls = []

        def outer(signum, frame):
            calls.append(signum)

        prev = signal.signal(signal.SIGTERM, outer)
        try:
            paddle.set_flags({"fault_injection": "sigterm:step=2"})
            tr = _trainer(tmp_path, max_steps=6)
            res = tr.train(resume=False)
            assert res["preempted"]
            # chained: the pre-existing handler observed the signal
            assert calls == [signal.SIGTERM]
            # restored: train() put the outer handler back
            assert signal.getsignal(signal.SIGTERM) is outer
            assert signal.getsignal(signal.SIGINT) \
                is signal.default_int_handler
        finally:
            signal.signal(signal.SIGTERM, prev)

    def test_slow_step_fault_fires(self, tmp_path):
        import time as _t
        paddle.set_flags(
            {"fault_injection": "slow_step:step=1:sleep=0.2"})
        tr = _trainer(tmp_path, max_steps=2, save_steps=100)
        t0 = _t.perf_counter()
        tr.train(resume=False)
        assert _t.perf_counter() - t0 >= 0.2
        assert any(e["site"] == "slow_step" for e in faults.events())

    def test_rank_hang_fault_wedges_the_loop(self, tmp_path):
        import time as _t
        paddle.set_flags(
            {"fault_injection": "rank_hang:step=1:sleep=0.3"})
        tr = _trainer(tmp_path, max_steps=2, save_steps=100)
        t0 = _t.perf_counter()
        tr.train(resume=False)
        assert _t.perf_counter() - t0 >= 0.3
        assert any(e["site"] == "rank_hang" for e in faults.events())

    def test_sigterm_drain_deadline_bounds_exit(self, tmp_path):
        """Just-in-time preemption checkpoint: the SIGTERM path drains
        the async checkpoint queue but gives up at
        FLAGS_ckpt_drain_deadline_s instead of hanging the grace window
        on a wedged store (the save keeps draining on its daemon)."""
        import time as _t
        paddle.set_flags({
            "fault_injection":
                "sigterm:step=2,ckpt_slow:times=0:sleep=3",
            "ckpt_drain_deadline_s": 0.2})
        before = _counter_total("robustness.ckpt_drain_timeouts")
        try:
            tr = _trainer(tmp_path, max_steps=10, save_steps=2)
            t0 = _t.perf_counter()
            res = tr.train(resume=False)
            dt = _t.perf_counter() - t0
            assert res["preempted"]
            # two 3s-stalled saves (step 2 + the preemption save) must
            # NOT be paid synchronously before exit
            assert dt < 3.0, f"drain deadline did not bound exit ({dt:.1f}s)"
            assert _counter_total("robustness.ckpt_drain_timeouts") \
                >= before + 1
            # the drain finishes in background: the preemption ckpt lands
            assert tr._ckpt_mgr().wait(timeout_s=30)
            assert tr._ckpt_mgr().latest_verified() is not None
        finally:
            paddle.set_flags({"ckpt_drain_deadline_s": 30.0})

    def test_trainer_heartbeat_env_wires_rank_file(self, tmp_path,
                                                   monkeypatch):
        hb_path = str(tmp_path / "hb" / "heartbeat_rank0.jsonl")
        monkeypatch.setenv("PADDLE_RANK_HEARTBEAT", hb_path)
        monkeypatch.setenv("PADDLE_RANK_HEARTBEAT_INTERVAL", "0.01")
        res = _trainer(tmp_path, max_steps=3, save_steps=100
                       ).train(resume=False)
        assert res["final_step"] == 3
        import json as _json
        recs = [_json.loads(line) for line in open(hb_path)]
        phases = [r.get("phase") for r in recs]
        assert "init" in phases and "resumed" in phases
        assert res["goodput"] == 1.0


class TestTreedefFingerprint:
    def test_optimizer_change_fails_clearly(self, tmp_path):
        tr = _trainer(tmp_path, max_steps=2, save_steps=2)
        tr.train(resume=False)
        tr2 = _trainer(tmp_path, max_steps=4, save_steps=2, sgd=True)
        with pytest.raises(RuntimeError,
                           match="optimizer state tree|optimizer leaves"):
            tr2.train(resume=True)

    def test_same_optimizer_resumes(self, tmp_path):
        tr = _trainer(tmp_path, max_steps=2, save_steps=2)
        tr.train(resume=False)
        res = _trainer(tmp_path, max_steps=4, save_steps=2).train()
        assert res["start_step"] == 2

    def test_resume_falls_back_past_corrupt_latest(self, tmp_path):
        """Acceptance: latest checkpoint truncated on disk -> resume
        from the previous verified one, no crash."""
        tr = _trainer(tmp_path, max_steps=4, save_steps=2)
        tr.train(resume=False)  # checkpoints at 2 and 4
        from paddle_tpu.distributed.checkpoint import VerifiedCheckpointer
        ckpt = VerifiedCheckpointer(str(tmp_path / "checkpoints"))
        assert sorted(ckpt.steps())[-1] == 4
        _damage_latest(ckpt, "truncate")
        res = _trainer(tmp_path, max_steps=6, save_steps=2).train()
        assert res["start_step"] == 2       # fell back to the verified one
        assert res["final_step"] == 6


# ---------------------------------------------------------------------------
# serving: deadlines, shedding, watchdog
# ---------------------------------------------------------------------------
def _serve_model():
    paddle.seed(0)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny())


def _prompts(n, lens=(5, 9, 12, 7)):
    rng = np.random.RandomState(0)
    return [rng.randint(2, 256, (lens[i % len(lens)],)).tolist()
            for i in range(n)]


class TestServingDeadlines:
    def test_expired_deadline_evicted_without_blocking(self):
        from paddle_tpu.inference import ContinuousBatchingPredictor
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=2,
                                         page_size=8, max_seq_len=64)
        before = _counter_total("robustness.deadline_evictions")
        outs = cb.generate(_prompts(3), max_new_tokens=4,
                           deadline_s=[60.0, 0.0, 60.0])
        assert outs[1] == [] and cb.last_status[1] == "deadline"
        for r in (0, 2):
            assert cb.last_status[r] == "ok" and len(outs[r]) == 4
        assert cb.stats["deadline_evictions"] == 1
        assert _counter_total("robustness.deadline_evictions") >= before + 1

    def test_no_deadline_unchanged(self):
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _serve_model()
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, max_seq_len=64)
        outs = cb.generate(_prompts(2), max_new_tokens=3)
        assert all(s == "ok" for s in cb.last_status)
        assert all(len(o) == 3 for o in outs)


class TestServingLoadShedding:
    def test_shed_under_2x_offered_load(self):
        from paddle_tpu.inference import ContinuousBatchingPredictor
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=2,
                                         page_size=8, max_seq_len=64,
                                         max_queue=4)
        before = _counter_total("robustness.shed_requests")
        outs = cb.generate(_prompts(8), max_new_tokens=2)  # 2x the bound
        assert cb.stats["shed_requests"] == 4
        assert [s for s in cb.last_status] == ["ok"] * 4 + ["shed"] * 4
        assert all(outs[r] == [] for r in range(4, 8))
        assert all(len(outs[r]) == 2 for r in range(4))
        assert _counter_total("robustness.shed_requests") >= before + 4

    def test_shed_oldest_policy(self):
        from paddle_tpu.inference import ContinuousBatchingPredictor
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=2,
                                         page_size=8, max_seq_len=64,
                                         max_queue=2, shed_policy="oldest")
        cb.generate(_prompts(4), max_new_tokens=2)
        assert cb.last_status == ["shed", "shed", "ok", "ok"]

    def test_flood_fault_sheds_everything(self):
        from paddle_tpu.inference import ContinuousBatchingPredictor
        paddle.set_flags({"fault_injection": "serve_flood:n=100"})
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=2,
                                         page_size=8, max_seq_len=64,
                                         max_queue=4)
        outs = cb.generate(_prompts(3), max_new_tokens=2)
        assert outs == [[], [], []]
        assert all(s == "shed" for s in cb.last_status)

    def test_unbounded_queue_never_sheds(self):
        from paddle_tpu.inference import ContinuousBatchingPredictor
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=2,
                                         page_size=8, max_seq_len=64)
        cb.generate(_prompts(6), max_new_tokens=2)
        assert cb.stats["shed_requests"] == 0
        assert all(s == "ok" for s in cb.last_status)


class TestServingWatchdog:
    def test_wedged_decode_fails_pending(self):
        from paddle_tpu.inference import ContinuousBatchingPredictor
        paddle.set_flags({"fault_injection": "decode_wedge:sleep=5"})
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=2,
                                         page_size=8, max_seq_len=64,
                                         decode_watchdog_s=0.25)
        import time as _t
        t0 = _t.perf_counter()
        outs = cb.generate(_prompts(2), max_new_tokens=8)
        assert _t.perf_counter() - t0 < 5  # returned, did not hang
        assert cb.stats["watchdog_trips"] == 1
        assert all(s == "watchdog" for s in cb.last_status)
        assert all(isinstance(o, list) for o in outs)

    def test_watchdog_quiet_on_healthy_decode(self):
        from paddle_tpu.inference import ContinuousBatchingPredictor
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=2,
                                         page_size=8, max_seq_len=64,
                                         decode_watchdog_s=30.0)
        outs = cb.generate(_prompts(2), max_new_tokens=3)
        assert cb.stats["watchdog_trips"] == 0
        assert all(len(o) == 3 for o in outs)


# ---------------------------------------------------------------------------
# launcher backoff
# ---------------------------------------------------------------------------
class TestLaunchBackoff:
    def test_parse_args(self):
        from paddle_tpu.distributed.launch.main import parse_args
        ctx = parse_args(["--restart_backoff", "0.25",
                          "--restart_backoff_max", "5", "x.py"])
        assert ctx.restart_backoff_s == 0.25
        assert ctx.restart_backoff_max_s == 5.0

    def test_delay_growth_jitter_cap(self):
        from paddle_tpu.distributed.launch.main import restart_delay
        assert restart_delay(1, 0.0, 60.0) == 0.0
        for n in range(1, 8):
            d = restart_delay(n, 1.0, 8.0)
            ideal = min(8.0, 2.0 ** (n - 1))
            assert 0.5 * ideal <= d <= 1.5 * ideal

    def test_backoff_logged_between_restarts(self, tmp_path, capfd):
        import textwrap
        from paddle_tpu.distributed.launch.main import parse_args, launch
        script = tmp_path / "bad.py"
        script.write_text(textwrap.dedent("""
            import sys
            sys.exit(5)
        """))
        ctx = parse_args(["--max_restart", "1",
                          "--restart_backoff", "0.01",
                          "--log_dir", str(tmp_path / "log"), str(script)])
        assert launch(ctx) == 5
        err = capfd.readouterr().err
        assert "backing off" in err and "restart epoch 1" in err


# ---------------------------------------------------------------------------
# recovery end to end: several faults in one Trainer run, and (slow)
# through the real launcher with Trainer workers
# ---------------------------------------------------------------------------
class TestTrainerRidesOutFaultsTogether:
    def test_save_error_nan_step_and_slow_store_in_one_run(self,
                                                           tmp_path):
        """A transient checkpoint-save error, one NaN step and a store
        whose every write stalls, armed together: the run completes,
        the save was retried, the NaN step skipped, and the newest
        checkpoint verifies and restores."""
        from paddle_tpu.distributed.checkpoint import VerifiedCheckpointer
        from paddle_tpu.framework.flags import flag_value
        prev = {k: flag_value(k) for k in ("ckpt_retry_backoff_s",
                                           "anomaly_guard")}
        paddle.set_flags({
            "fault_injection": "ckpt_save:step=2:err,nan_loss:step=3,"
                               "ckpt_slow:times=0:sleep=0.25",
            "ckpt_retry_backoff_s": 0.05, "anomaly_guard": True})
        retries = _counter_total("robustness.ckpt_retries")
        skipped = _counter_total("robustness.anomalies_skipped")
        try:
            res = _trainer(tmp_path, max_steps=6).train(resume=False)
        finally:
            paddle.set_flags(prev)
        assert res["final_step"] == 6
        assert math.isfinite(res["final_loss"])
        assert res["anomalous_steps"] == 1
        assert _counter_total("robustness.ckpt_retries") >= retries + 1
        assert _counter_total("robustness.anomalies_skipped") \
            >= skipped + 1
        ckpt = VerifiedCheckpointer(str(tmp_path / "checkpoints"))
        assert ckpt.latest_verified() == 6
        restored = ckpt.restore_latest()
        assert restored is not None
        assert int(np.asarray(restored[1]["step"])) == 6


@pytest.mark.slow
class TestLauncherRecovery:
    def test_hung_rank_killed_and_resumed_from_verified_checkpoint(
            self, tmp_path, launch_trainer_workers):
        """A rank that wedges mid-run (alive pid, silent heartbeat) is
        detected and killed by the launcher; the restarted worker
        resumes from the last verified checkpoint and finishes."""
        from paddle_tpu.distributed.checkpoint import VerifiedCheckpointer
        hangs = _counter_total("robustness.hangs_detected")
        # the timeout must exceed the worker's silent import window
        rc, results = launch_trainer_workers(
            ["--nproc_per_node", "1", "--max_restart", "2",
                       "--hang_timeout", "15"],
            fault="rank_hang:step=5:sleep=600", fault_epochs=(0,),
            total_steps=8, save_steps=2, step_s=0)
        assert rc == 0
        assert _counter_total("robustness.hangs_detected") >= hangs + 1
        assert [r["final_step"] for r in results] == [8]   # epoch 0 hung
        assert results[0]["start_step"] > 0
        ckpt = VerifiedCheckpointer(
            str(tmp_path / "rank0" / "checkpoints"))
        assert ckpt.latest_verified() == 8
        g = obs.get_registry().get("robustness.mttr_seconds")
        assert g is not None and [s.value for s in g.samples()]

    def test_persistent_straggler_excluded_and_its_work_taken_over(
            self, tmp_path, launch_trainer_workers):
        """A rank that is slow in every epoch (a degraded host does not
        heal on restart): the fleet detector's incident drives the
        mitigation controller, the pod restarts without the rank, the
        two survivors finish the job's fixed step budget from their
        own checkpoints, and every decision is in control.jsonl with a
        contiguous sequence."""
        import json
        total = 12
        detected = _counter_total("robustness.stragglers_detected")
        rc, results = launch_trainer_workers(
            ["--nproc_per_node", "3", "--max_restart", "2",
                       "--straggler_factor", "2.0",
                       "--straggler_steps", "2",
                       "--mitigation", "exclude",
                       "--mitigation_cooldown", "5"],
            fault="rank_slow:times=0:rank=2:factor=8.0",
            fault_epochs=None, total_steps=total, save_steps=1,
            step_s=1.0)
        assert rc == 0
        assert _counter_total("robustness.stragglers_detected") \
            >= detected + 1
        # excluded: results written under a world of two, resumed
        assert any(r["world"] == 2 and r["start_step"] > 0
                   for r in results)
        # work conserved: the survivors' furthest steps add up to the
        # budget (the excluded rank's partial steps are discarded)
        furthest = {}
        for r in results:
            if r["world"] == 2:
                furthest[r["rank"]] = max(furthest.get(r["rank"], 0),
                                          r["final_step"])
        assert sum(furthest.values()) == total
        audit = [json.loads(ln) for ln in
                 open(tmp_path / "log" / "control.jsonl") if ln.strip()]
        assert "exclude_restart" in [r["action"] for r in audit]
        assert [r["seq"] for r in audit] \
            == list(range(1, len(audit) + 1))
        assert all(r["kind"] == "control" for r in audit)

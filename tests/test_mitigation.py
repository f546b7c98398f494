"""Mitigation controller tests — the straggler actuator driven as a
pure state machine (fake clock, in-memory audit sink, no subprocesses).
The end-to-end path (fleet detector -> controller -> kill -> elastic
restart) is tests/test_robustness.py's slow TestLauncherRecovery; these pin
the DECISION logic: action selection, cooldown, flap damping, the
rank-0 / sole-stage-host / min-world edges, comm-wait inversion, and
the audit-stream contract (contiguous seq, no silent paths)."""
import os

import pytest

from paddle_tpu.distributed.launch.mitigate import (
    MitigationController, reassign_stage_map, stage_of_rank)
from paddle_tpu.observability.metrics import MetricRegistry


def make(world=4, mode="auto", clock=None, audit=None, **kw):
    clock = clock if clock is not None else {"t": 1000.0}
    audit = audit if audit is not None else []
    mit = MitigationController(
        world_size=world, mode=mode, registry=MetricRegistry(),
        now_fn=lambda: clock["t"], emit=audit.append, **kw)
    return mit, clock, audit


def incident(rank, dur=6.0, med=1.0, step=5, consecutive=3, **kw):
    inc = {"rank": str(rank), "step": step, "dur_s": dur,
           "median_s": med, "ratio": dur / med,
           "consecutive": consecutive,
           "dominant_span": "train.straggle"}
    inc.update(kw)
    return inc


class TestStageMath:
    def test_stage_of_rank_contiguous(self):
        # 8 ranks / 4 stages: stage s owns ranks [2s, 2s+2)
        assert [stage_of_rank(r, 8, 4) for r in range(8)] == \
            [0, 0, 1, 1, 2, 2, 3, 3]

    def test_stage_of_rank_degenerate(self):
        assert stage_of_rank(3, 4, 1) == 0
        assert stage_of_rank(0, 0, 4) == 0
        # more stages than ranks: trailing ranks clamp to the last
        assert stage_of_rank(2, 3, 8) == 2

    def test_reassign_swaps_lightest_onto_slow(self):
        m = reassign_stage_map([3.0, 1.0, 2.0], slow_stage=0)
        # stage 0 (cost 3.0) is hosted by group 1; stage 1 by group 0
        assert m == [1, 0, 2]

    def test_reassign_none_when_already_lightest(self):
        assert reassign_stage_map([1.0, 3.0, 2.0], slow_stage=0) is None

    def test_reassign_rejects_bad_stage(self):
        assert reassign_stage_map([1.0, 2.0], slow_stage=5) is None
        assert reassign_stage_map([], slow_stage=0) is None

    def test_reassign_tie_prefers_lowest_index(self):
        # equal costs: the permutation must be deterministic
        assert reassign_stage_map([2.0, 2.0, 2.0], 1) == [1, 0, 2]


class TestDecisions:
    def test_exclude_persistent_slow_rank(self):
        mit, _, audit = make(world=4, mode="exclude")
        dec = mit.offer(incident(2))
        assert dec["action"] == "exclude_restart"
        assert dec["params"]["rank"] == 2
        assert dec["params"]["world_after"] == 3
        assert mit.excluded == [2]
        # init record + the decision; seq is contiguous from 1
        assert [r["seq"] for r in audit] == [1, 2]

    def test_rank0_protected(self):
        # killing rank 0 kills the coordinator, not the straggler
        mit, _, _ = make(world=4, mode="exclude")
        dec = mit.offer(incident(0))
        assert dec["action"] == "tolerate"
        assert "rank0_protected" in dec["params"]["reasons"]
        assert mit.excluded == []

    def test_min_world_floor(self):
        mit, clock, _ = make(world=2, mode="exclude", min_world=2)
        dec = mit.offer(incident(1))
        assert dec["action"] == "tolerate"
        assert "min_world" in dec["params"]["reasons"]

    def test_auto_falls_back_to_reassign(self):
        # 4 ranks / 2 stages, rank 1 slow; world_after=3 < min_world=4
        # blocks exclusion, so auto reassigns the slow stage away
        mit, _, _ = make(world=4, mode="auto", num_stages=2, min_world=4)
        for step in range(1, 4):
            # stage 0 (ranks 0,1) heavier than stage 1 even with the
            # slow rank's own inflation excluded from the cost model
            mit.note_step(step, {"0": 2.0, "1": 6.0, "2": 1.0,
                                 "3": 1.0})
        dec = mit.offer(incident(1))
        assert dec["action"] == "reassign_stages"
        assert dec["params"]["slow_stage"] == 0
        assert dec["params"]["stage_map"] == [1, 0]
        assert mit.stage_map == [1, 0]
        assert mit.excluded == []

    def test_sole_stage_host_cannot_be_excluded(self):
        # 2 ranks / 2 stages: each rank is its stage's only host; a
        # pipeline missing a stage cannot run at all
        mit, _, _ = make(world=2, mode="exclude", num_stages=2,
                         min_world=1)
        dec = mit.offer(incident(1))
        assert dec["action"] == "tolerate"
        assert "sole_stage_host" in dec["params"]["reasons"]

    def test_reassign_none_when_slow_stage_lightest(self):
        mit, _, _ = make(world=4, mode="reassign", num_stages=2)
        for step in range(1, 4):
            # stage 1 (ranks 2,3) is already the lightest once rank
            # 3's own inflation is excluded -> nothing to gain
            mit.note_step(step, {"0": 2.0, "1": 2.0, "2": 1.0,
                                 "3": 9.0})
        dec = mit.offer(incident(3))
        assert dec["action"] == "tolerate"
        assert "no_lighter_stage" in dec["params"]["reasons"]

    def test_second_exclusion_respects_shrunk_world(self):
        mit, clock, _ = make(world=4, mode="exclude", min_world=2,
                             cooldown_s=1.0, flap_window_s=0.0)
        assert mit.offer(incident(3))["action"] == "exclude_restart"
        clock["t"] += 10.0
        # world is now 3; excluding another leaves 2 >= min_world
        assert mit.offer(incident(2))["action"] == "exclude_restart"
        clock["t"] += 10.0
        dec = mit.offer(incident(1))
        assert dec["action"] == "tolerate"
        assert "min_world" in dec["params"]["reasons"]
        assert mit.excluded == [3, 2]


class TestDamping:
    def test_cooldown_holds(self):
        mit, clock, _ = make(world=4, mode="exclude", cooldown_s=30.0,
                             flap_window_s=0.0)
        assert mit.offer(incident(2))["action"] == "exclude_restart"
        clock["t"] += 5.0
        dec = mit.offer(incident(3))
        assert dec["action"] == "hold_cooldown"
        assert dec["params"]["remaining_s"] == pytest.approx(25.0)
        clock["t"] += 26.0   # past the window: actions resume
        assert mit.offer(incident(3))["action"] == "exclude_restart"

    def test_flap_damping_alternating_ranks(self):
        # skew bouncing between ranks = the median moved, not a
        # degraded host; the actuator must hold instead of thrashing
        mit, clock, _ = make(world=4, mode="exclude", cooldown_s=0.0,
                             flap_window_s=60.0)
        first = mit.offer(incident(2))
        assert first["action"] == "exclude_restart"
        for rank in (3, 1, 3, 1):
            clock["t"] += 5.0
            dec = mit.offer(incident(rank))
            assert dec["action"] == "hold_flap"
        assert mit.excluded == [2]

    def test_same_rank_repeat_is_not_flap(self):
        mit, clock, _ = make(world=4, mode="exclude", cooldown_s=0.0,
                             flap_window_s=60.0)
        mit.offer(incident(2))
        clock["t"] += 5.0
        # same rank again inside the window: persistent, not flapping
        assert mit.offer(incident(2))["action"] != "hold_flap"

    def test_flap_window_expiry(self):
        mit, clock, _ = make(world=4, mode="exclude", cooldown_s=0.0,
                             flap_window_s=10.0)
        mit.offer(incident(2))
        clock["t"] += 11.0   # outside the window: a new episode
        assert mit.offer(incident(3))["action"] == "exclude_restart"


class TestCommWaitInversion:
    def test_synchronous_straggler_synthesized(self):
        # lockstep training: rank 1 is slow but shows NO dur skew —
        # the others absorb it as comm-wait; the inversion detector
        # must synthesize the incident after N consecutive steps
        mit, _, _ = make(world=3, comm_share_steps=3)
        shares = {"0": 0.6, "1": 0.05, "2": 0.55}
        durs = {"0": 1.0, "1": 1.0, "2": 1.0}
        assert mit.note_step(1, durs, shares) is None
        assert mit.note_step(2, durs, shares) is None
        inc = mit.note_step(3, durs, shares)
        assert inc is not None
        assert inc["rank"] == 1
        assert inc["source"] == "comm_wait_inversion"
        assert inc["consecutive"] == 3
        # it classifies as compute_slow (the HOST is slow; its NIC is
        # fine) and is actionable
        dec = mit.offer(inc)
        assert dec["inputs"]["classification"] == "compute_slow"
        assert dec["action"] == "exclude_restart"

    def test_inversion_fires_once_per_episode(self):
        mit, _, _ = make(world=3, comm_share_steps=2)
        shares = {"0": 0.6, "1": 0.05, "2": 0.55}
        durs = {"0": 1.0, "1": 1.0, "2": 1.0}
        mit.note_step(1, durs, shares)
        assert mit.note_step(2, durs, shares) is not None
        assert mit.note_step(3, durs, shares) is None  # already flagged

    def test_inversion_resets_on_recovery(self):
        mit, _, _ = make(world=3, comm_share_steps=2)
        low = {"0": 0.6, "1": 0.05, "2": 0.55}
        even = {"0": 0.1, "1": 0.1, "2": 0.1}
        durs = {"0": 1.0, "1": 1.0, "2": 1.0}
        mit.note_step(1, durs, low)
        mit.note_step(2, durs, even)   # fleet median below floor
        assert mit.note_step(3, durs, low) is None   # streak restarted
        assert mit.note_step(4, durs, low) is not None

    def test_no_inversion_without_fleet_wait(self):
        # one rank idles but the fleet median is under the floor: that
        # is load imbalance, not a straggler holding everyone up
        mit, _, _ = make(world=3, comm_share_steps=1)
        shares = {"0": 0.2, "1": 0.01, "2": 0.1}
        assert mit.note_step(1, {"0": 1.0, "1": 1.0, "2": 1.0},
                             shares) is None


class TestClassification:
    def test_comm_dominant_span(self):
        mit, _, _ = make()
        dec = mit.offer(incident(2, dominant_span="comm.allreduce"))
        assert dec["inputs"]["classification"] == "comm_degraded"

    def test_high_own_share_is_comm_degraded(self):
        mit, _, _ = make()
        dec = mit.offer(incident(2, dominant_span=None,
                                 comm_wait_share=0.7))
        assert dec["inputs"]["classification"] == "comm_degraded"

    def test_low_share_is_compute_slow(self):
        mit, _, _ = make()
        dec = mit.offer(incident(2, dominant_span="train.dispatch",
                                 comm_wait_share=0.05))
        assert dec["inputs"]["classification"] == "compute_slow"


class TestAuditStream:
    def test_every_offer_emits_exactly_one_record(self):
        mit, clock, audit = make(world=4, mode="exclude",
                                 cooldown_s=30.0, flap_window_s=20.0)
        mit.offer(incident(2))                  # exclude
        clock["t"] += 1.0
        mit.offer(incident(3))                  # hold_flap
        clock["t"] += 1.0
        mit.offer(incident(3))                  # hold_cooldown
        clock["t"] += 60.0
        mit.offer(incident(0))                  # tolerate (rank 0)
        assert [r["seq"] for r in audit] == [1, 2, 3, 4, 5]
        assert [r["action"] for r in audit] == [
            "observe", "exclude_restart", "hold_flap",
            "hold_cooldown", "tolerate"]
        for rec in audit:
            assert rec["kind"] == "control"
            assert set(rec) >= {"ts", "seq", "tick", "rule", "action",
                                "params", "inputs", "cooldown_s"}

    def test_inputs_carry_detector_evidence(self):
        mit, _, audit = make()
        mit.note_step(1, {"0": 1.0, "1": 1.0, "2": 6.0, "3": 1.0})
        dec = mit.offer(incident(2, step=7, consecutive=4))
        inp = dec["inputs"]
        assert inp["rank"] == 2 and inp["step"] == 7
        assert inp["consecutive"] == 4
        assert inp["mean_step_s"].get(2) == pytest.approx(6.0)
        assert inp["world_size"] == 4 and inp["excluded"] == []

    def test_emit_sink_failure_never_raises(self):
        def bad_sink(rec):
            raise OSError("disk full")
        mit = MitigationController(
            world_size=4, registry=MetricRegistry(),
            now_fn=lambda: 0.0, emit=bad_sink)
        dec = mit.offer(incident(2))
        assert dec["action"] == "exclude_restart"
        assert len(mit.decisions) == 2   # in-memory mirror intact

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError):
            MitigationController(world_size=4, mode="yolo",
                                 registry=MetricRegistry())

    def test_metrics_land_in_registry(self):
        reg = MetricRegistry()
        mit = MitigationController(world_size=4, mode="exclude",
                                   registry=reg, now_fn=lambda: 0.0)
        mit.offer(incident(2))
        inc_m = reg.get("robustness.mitigation.incidents")
        act_m = reg.get("robustness.mitigation.actions")
        exc_m = reg.get("robustness.mitigation.excluded_ranks")
        assert sum(s.value for s in inc_m.samples()) == 1
        assert sum(s.value for s in act_m.samples()) >= 2
        assert [s.value for s in exc_m.samples()][-1] == 1


class TestStageMapEnv:
    def test_mesh_applies_stage_permutation(self, monkeypatch):
        import numpy as np
        from paddle_tpu.distributed.mesh import _apply_stage_map
        arr = np.arange(4).reshape(1, 4, 1, 1, 1)
        monkeypatch.setenv("PADDLE_TPU_STAGE_MAP", "2,0,1,3")
        out = _apply_stage_map(arr, 4)
        assert out.reshape(-1).tolist() == [2, 0, 1, 3]

    def test_mesh_ignores_non_permutation(self, monkeypatch, capsys):
        import numpy as np
        from paddle_tpu.distributed.mesh import _apply_stage_map
        arr = np.arange(4).reshape(1, 4, 1, 1, 1)
        monkeypatch.setenv("PADDLE_TPU_STAGE_MAP", "0,0,1,3")
        out = _apply_stage_map(arr, 4)
        assert out.reshape(-1).tolist() == [0, 1, 2, 3]
        assert "ignoring" in capsys.readouterr().err

    def test_mesh_noop_without_env(self, monkeypatch):
        import numpy as np
        from paddle_tpu.distributed.mesh import _apply_stage_map
        monkeypatch.delenv("PADDLE_TPU_STAGE_MAP", raising=False)
        arr = np.arange(4).reshape(1, 4, 1, 1, 1)
        assert _apply_stage_map(arr, 4) is arr


class TestLauncherWiring:
    def test_pod_controller_skips_excluded_ranks(self, tmp_path):
        from paddle_tpu.distributed.launch.main import (PodController,
                                                        parse_args)
        import textwrap
        script = tmp_path / "w.py"
        script.write_text(textwrap.dedent("""
            import json, os
            with open(os.path.join(os.environ["OUT"],
                                   "r%s.json" % os.environ["RANK"]),
                      "w") as f:
                json.dump({"rank": os.environ["RANK"],
                           "world": os.environ["WORLD_SIZE"],
                           "excluded":
                           os.environ.get("PADDLE_TPU_EXCLUDED_RANKS"),
                           "stage_map":
                           os.environ.get("PADDLE_TPU_STAGE_MAP")}, f)
        """))
        os.environ["OUT"] = str(tmp_path)
        try:
            ctx = parse_args(["--nproc_per_node", "3", "--log_dir",
                              str(tmp_path / "log"), str(script)])
            pod = PodController(ctx, exclude=[1], stage_map=[1, 0])
            pod.start(restart_epoch=0)
            assert pod.local_ranks == [0, 2]
            while pod.poll() is None:
                pass
            pod.stop()
        finally:
            os.environ.pop("OUT", None)
        import json
        assert not (tmp_path / "r1.json").exists()
        for r in (0, 2):
            rec = json.loads((tmp_path / f"r{r}.json").read_text())
            assert rec["world"] == "2"          # live world, not 3
            assert rec["excluded"] == "1"
            assert rec["stage_map"] == "1,0"
        # kill_rank on an excluded local rank is a safe no-op
        pod.kill_rank(1)
        states = pod.rank_states()
        assert [st["rank"] for st in states] == [0, 2]

    def test_restart_delay_injectable_rng(self):
        from paddle_tpu.distributed.launch.main import restart_delay
        # rng pinned to 0.5 -> exactly base * 2^(n-1), no jitter
        assert restart_delay(1, 2.0, 60.0, rng=lambda: 0.5) == 2.0
        assert restart_delay(3, 2.0, 60.0, rng=lambda: 0.5) == 8.0
        # jitter bounds: +/-50%
        assert restart_delay(1, 2.0, 60.0, rng=lambda: 0.0) == 1.0
        assert restart_delay(1, 2.0, 60.0, rng=lambda: 0.999) \
            == pytest.approx(2.998)
        # cap applies before jitter
        assert restart_delay(10, 2.0, 4.0, rng=lambda: 0.5) == 4.0

    def test_launch_clock_driven_backoff(self, tmp_path):
        # the whole launcher babysit loop runs against an injected
        # clock/sleep: a crash-looping worker burns its restart budget
        # without a single real sleep, and the fake clock advances by
        # exactly the backoff the rng dictates
        from paddle_tpu.distributed.launch.main import (launch,
                                                        parse_args)
        script = tmp_path / "w.py"
        script.write_text("raise SystemExit(1)\n")
        clock = {"t": 0.0}
        slept = []

        def fake_sleep(s):
            slept.append(s)
            clock["t"] += s

        ctx = parse_args(["--max_restart", "2", "--restart_backoff",
                          "4.0", "--heartbeat_interval", "0",
                          "--log_dir", str(tmp_path / "log"),
                          str(script)])
        rc = launch(ctx, now_fn=lambda: clock["t"],
                    sleep_fn=fake_sleep, rng=lambda: 0.5)
        assert rc == 1
        # restarts 1 and 2 backed off 4s and 8s (rng pinned: no
        # jitter); the 0.2s poll ticks ride the same fake clock
        assert [s for s in slept if s >= 1.0] == [4.0, 8.0]
        assert clock["t"] >= 12.0


class TestRecoveryReport:
    def test_render_recovery_mitigation_timeline(self):
        """trace_report --recovery renders the full mitigation chain
        from the audit records alone: skew -> decision -> kill ->
        retire -> goodput delta, with the seq-contiguity footer."""
        import importlib.util
        repo = os.path.join(os.path.dirname(__file__), "..")
        spec = importlib.util.spec_from_file_location(
            "trace_report_mit", os.path.join(repo, "tools",
                                             "trace_report.py"))
        tr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tr)
        controls = [
            {"kind": "control", "ts": 10.0, "seq": 1, "rule":
             "persistent_skew", "action": "observe", "params": {},
             "inputs": {"rank": 2}},
            {"kind": "control", "ts": 12.0, "seq": 2, "rule":
             "persistent_skew", "action": "exclude_restart",
             "params": {"rank": 2, "stage": 0, "world_before": 3,
                        "world_after": 2},
             "inputs": {"classification": "compute_slow",
                        "consecutive": 2, "rank": 2}},
            {"kind": "control", "ts": 14.0, "seq": 3, "rule":
             "persistent_skew", "action": "hold_cooldown",
             "params": {"remaining_s": 4.5}, "inputs": {"rank": 1}},
        ]
        fleet_events = [
            {"event": "straggler", "ts": 11.0, "rank": "2", "step": 2,
             "dur_s": 8.0, "median_s": 1.0, "consecutive": 2,
             "dominant_span": "train.straggle"},
            {"event": "rank_retired", "ts": 12.5, "rank": "2"},
        ]
        out = tr.render_recovery(
            [], [], controls=controls, fleet_events=fleet_events,
            goodput={"mitigation": 0.15, "toleration": 0.10})
        assert "MITIGATION seq=2: exclude rank 2" in out
        assert "world 3 -> 2" in out
        assert "compute_slow, 2 consecutive slow steps" in out
        assert "STRAGGLER rank=2" in out
        assert "rank 2 retired from the fleet join" in out
        assert "hold_cooldown rank 1" in out
        assert "audit stream: 3 control records, seq contiguous" in out
        assert "+50.0% from mitigation" in out
        # a gap in the stream is called out, not glossed over
        out2 = tr.render_recovery(
            [], [], controls=[controls[0], controls[2]])
        assert "GAPS" in out2

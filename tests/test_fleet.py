"""Fleet observability: cross-rank aggregation, straggler detection,
tailer robustness (torn lines + mid-read rotation across MULTIPLE
concurrently-growing rank files — the PR-11 single-file tolerance,
generalized), rank identity on exported lines, and the stdlib-only
tools/fleet_report.py renderer.
"""
import json
import os
import subprocess
import sys

import pytest

import paddle_tpu.observability as obs
from paddle_tpu.observability.fleet import (FleetAggregator,
                                            RankFileTailer,
                                            StragglerDetector)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _append(path, recs, newline=True, raw=None):
    with open(path, "a") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
        if raw is not None:
            f.write(raw)
            if newline:
                f.write("\n")


def _rank_step(rank, step, dur, t0=1000.0, trace=None, comm=()):
    """One rank's records for one step: train.step span (+ optional
    comm child spans sharing the trace)."""
    trace = trace or f"tr{rank}_{step}"
    recs = [{"kind": "span", "name": "train.dispatch", "trace": trace,
             "labels": {"step": step}, "dur": dur * 0.8,
             "start": t0 + step}]
    for cdur in comm:
        recs.append({"kind": "span", "name": "comm.wait",
                     "trace": trace, "labels": {"site": "wait"},
                     "dur": cdur, "start": t0 + step})
    recs.append({"kind": "span", "name": "train.step", "trace": trace,
                 "labels": {"step": step}, "dur": dur,
                 "start": t0 + step})
    return recs


# ===========================================================================
# RankFileTailer: whole-line consumption, torn tails, mid-read rotation
# ===========================================================================
class TestRankFileTailer:
    def test_torn_tail_held_back_then_completed(self, tmp_path):
        p = str(tmp_path / "t.jsonl")
        t = RankFileTailer(p)
        _append(p, [{"a": 1}])
        with open(p, "a") as f:          # a line mid-append: no newline
            f.write('{"a": 2')
        recs = t.poll()
        assert recs == [{"a": 1}]        # torn tail NOT consumed
        with open(p, "a") as f:          # writer finishes the line
            f.write(', "b": 3}\n')
        assert t.poll() == [{"a": 2, "b": 3}]   # re-read complete

    def test_interior_garbage_skipped_counted(self, tmp_path):
        p = str(tmp_path / "t.jsonl")
        with open(p, "w") as f:
            f.write('{"a": 1}\nnot json\n{"a": 2}\n')
        t = RankFileTailer(p)
        assert t.poll() == [{"a": 1}, {"a": 2}]
        assert t.dropped == 1

    def test_mid_read_rotation_loses_nothing(self, tmp_path):
        """JsonlExporter-style rotation (os.replace to .1 + fresh file)
        between polls: the old file's unread remainder is drained from
        the .1 sibling, then the new file is read — no loss, no
        double-count, even when the fresh file grows past the old
        offset before the next poll."""
        p = str(tmp_path / "t.jsonl")
        t = RankFileTailer(p)
        _append(p, [{"i": 1}, {"i": 2}])
        assert [r["i"] for r in t.poll()] == [1, 2]
        _append(p, [{"i": 3}])           # written, not yet polled
        os.replace(p, p + ".1")          # rotation
        # fresh file immediately grows PAST the old offset
        _append(p, [{"i": 4}, {"i": 5}, {"i": 6}, {"i": 7}])
        assert [r["i"] for r in t.poll()] == [3, 4, 5, 6, 7]
        _append(p, [{"i": 8}])
        assert [r["i"] for r in t.poll()] == [8]

    def test_preexisting_rotation_sibling_folded_in(self, tmp_path):
        p = str(tmp_path / "t.jsonl")
        _append(p + ".1", [{"i": 1}])
        _append(p, [{"i": 2}])
        t = RankFileTailer(p)
        assert [r["i"] for r in t.poll()] == [1, 2]

    def test_truncation_restarts(self, tmp_path):
        p = str(tmp_path / "t.jsonl")
        t = RankFileTailer(p)
        _append(p, [{"i": 1}, {"i": 2}])
        t.poll()
        with open(p, "w") as f:          # truncate-and-rewrite
            f.write('{"i": 9}\n')
        assert [r["i"] for r in t.poll()] == [9]


# ===========================================================================
# StragglerDetector: persistent-skew state machine
# ===========================================================================
class TestStragglerDetector:
    def test_fires_once_after_n_consecutive(self):
        det = StragglerDetector(factor=2.0, min_steps=3)
        durs_fast = {"0": 0.05, "1": 0.05, "2": 0.05, "3": 0.05}
        assert det.observe(1, durs_fast) == []
        slow = dict(durs_fast, **{"2": 0.3})
        assert det.observe(2, slow) == []
        assert det.observe(3, slow) == []
        hits = det.observe(4, slow)              # 3rd consecutive
        assert [h["rank"] for h in hits] == ["2"]
        assert hits[0]["ratio"] == pytest.approx(6.0)
        assert det.observe(5, slow) == []        # once per episode

    def test_rearms_after_recovery(self):
        det = StragglerDetector(factor=2.0, min_steps=2)
        fast = {"0": 0.05, "1": 0.05, "2": 0.05}
        slow = dict(fast, **{"1": 0.2})
        det.observe(1, slow)
        assert [h["rank"] for h in det.observe(2, slow)] == ["1"]
        assert det.observe(3, fast) == []        # recovered: re-arm
        det.observe(4, slow)
        assert [h["rank"] for h in det.observe(5, slow)] == ["1"]

    def test_non_consecutive_does_not_fire(self):
        det = StragglerDetector(factor=2.0, min_steps=3)
        fast = {"0": 0.05, "1": 0.05}
        slow = {"0": 0.05, "1": 0.2}
        # the median of 2 ranks is the midpoint, 0.125 -> ratio 1.6x:
        # use 3 ranks so the median is a fast rank
        fast = {"0": 0.05, "1": 0.05, "2": 0.05}
        slow = dict(fast, **{"1": 0.2})
        det.observe(1, slow)
        det.observe(2, slow)
        assert det.observe(3, fast) == []        # streak broken
        det.observe(4, slow)
        det.observe(5, slow)
        assert det.observe(6, slow) != []        # fresh 3-streak

    def test_disabled_and_single_rank(self):
        det = StragglerDetector(factor=0.0, min_steps=1)
        assert det.observe(1, {"0": 1.0, "1": 0.01}) == []
        det2 = StragglerDetector(factor=2.0, min_steps=1)
        assert det2.observe(1, {"0": 1.0}) == []   # needs >= 2 ranks


# ===========================================================================
# FleetAggregator: the cross-rank join
# ===========================================================================
class TestFleetAggregator:
    def _mk(self, tmp_path, **kw):
        reg = obs.MetricRegistry()
        agg = FleetAggregator(str(tmp_path), registry=reg,
                              log=lambda m: None, **kw)
        return agg, reg

    def _write_step(self, tmp_path, rank, step, dur, **kw):
        _append(str(tmp_path / f"telemetry_rank{rank}.jsonl"),
                _rank_step(rank, step, dur, **kw))

    def test_step_join_skew_and_straggler(self, tmp_path):
        agg, reg = self._mk(tmp_path, straggler_factor=2.0,
                            straggler_steps=2)
        for step in range(1, 6):
            for rank in range(4):
                dur = 0.4 if (rank == 1 and step >= 2) else 0.05
                self._write_step(tmp_path, rank, step, dur,
                                 comm=(0.01,))
            agg.poll()
        assert reg.gauge("fleet.step_skew_seconds").value() \
            == pytest.approx(0.35)
        assert [h["rank"] for h in agg.stragglers] == ["1"]
        assert agg.stragglers[0]["dominant_span"] == "train.dispatch"
        assert reg.counter("robustness.stragglers_detected") \
            .value(rank="1") == 1
        # fleet.jsonl: step records carry per-rank comm-wait share
        recs = [json.loads(l) for l in
                open(str(tmp_path / "fleet.jsonl"))]
        steps = [r for r in recs if r.get("event") == "step"]
        assert len(steps) == 5
        assert set(steps[0]["comm_wait_share"]) == {"0", "1", "2", "3"}
        assert steps[0]["comm_wait_share"]["0"] == pytest.approx(
            0.01 / 0.05, rel=1e-3)
        stragglers = [r for r in recs if r.get("event") == "straggler"]
        assert len(stragglers) == 1 and stragglers[0]["rank"] == "1"

    def test_concurrent_growth_with_torn_lines_and_rotation(
            self, tmp_path):
        """Satellite: torn/partially-written lines and mid-read
        rotation across MULTIPLE concurrently-growing rank files must
        not lose or double-count steps."""
        agg, reg = self._mk(tmp_path)
        p0 = str(tmp_path / "telemetry_rank0.jsonl")
        p1 = str(tmp_path / "telemetry_rank1.jsonl")
        # step 1 complete on rank0; rank1's step-1 line torn mid-write
        _append(p0, _rank_step(0, 1, 0.05))
        full = json.dumps(_rank_step(1, 1, 0.05)[-1])
        _append(p1, _rank_step(1, 1, 0.05)[:-1])
        with open(p1, "a") as f:
            f.write(full[:25])           # torn: no newline, half a line
        agg.poll()
        assert agg.stragglers == []
        # nothing joined yet: rank1's step span is incomplete
        assert not os.path.exists(str(tmp_path / "fleet.jsonl"))
        with open(p1, "a") as f:         # writer completes the line
            f.write(full[25:] + "\n")
        agg.poll()
        recs = [json.loads(l) for l in
                open(str(tmp_path / "fleet.jsonl"))]
        assert [r["step"] for r in recs if r["event"] == "step"] == [1]
        # rank0 rotates mid-run with unread records in the old file
        _append(p0, _rank_step(0, 2, 0.05))
        os.replace(p0, p0 + ".1")
        _append(p0, _rank_step(0, 3, 0.05))
        _append(p1, _rank_step(1, 2, 0.05) + _rank_step(1, 3, 0.05))
        agg.poll()
        recs = [json.loads(l) for l in
                open(str(tmp_path / "fleet.jsonl"))]
        assert [r["step"] for r in recs if r["event"] == "step"] \
            == [1, 2, 3]

    def test_comm_balance_and_heartbeat_gaps(self, tmp_path):
        agg, reg = self._mk(tmp_path)
        for rank, mult in ((0, 1), (1, 3)):
            _append(str(tmp_path / f"telemetry_rank{rank}.jsonl"),
                    [{"name": "comm.bytes", "kind": "counter",
                      "labels": {"op": "all_reduce", "axis": "data"},
                      "value": 1000.0 * mult}])
            _append(str(tmp_path / f"heartbeat_rank{rank}.jsonl"),
                    [{"ts": 1000.0 + i, "kind": "heartbeat",
                      "phase": "step"} for i in range(3)]
                    + ([{"ts": 1020.0, "kind": "heartbeat",
                         "phase": "step"}] if rank == 1 else []))
        agg.poll()
        assert reg.gauge("fleet.comm_bytes_imbalance") \
            .value(axis="data") == pytest.approx(3000.0 / 2000.0)
        assert reg.gauge("fleet.heartbeat_gap_seconds") \
            .value(rank="1") == pytest.approx(18.0)
        recs = [json.loads(l) for l in
                open(str(tmp_path / "fleet.jsonl"))]
        gaps = [r for r in recs if r.get("event") == "heartbeat_gap"]
        assert gaps and gaps[0]["rank"] == "1"

    def test_resume_gap_skips_forward(self, tmp_path):
        """A rank that resumed past earlier steps (elastic restart)
        must not deadlock the join: the aggregator skips to the first
        step every rank reports."""
        agg, reg = self._mk(tmp_path)
        for step in (1, 2, 3, 4):
            self._write_step(tmp_path, 0, step, 0.05)
        for step in (3, 4):              # rank1 resumed at step 3
            self._write_step(tmp_path, 1, step, 0.05)
        agg.poll()
        recs = [json.loads(l) for l in
                open(str(tmp_path / "fleet.jsonl"))]
        assert [r["step"] for r in recs if r["event"] == "step"] \
            == [3, 4]

    @staticmethod
    def _control(seq, rule, action, **params):
        return {"kind": "control", "ts": 1000.0 + seq, "seq": seq,
                "tick": seq, "rule": rule, "action": action,
                "params": params, "inputs": {"burn_fast": 1.5},
                "cooldown_s": 0.0}

    def test_control_records_whole_or_nothing_under_truncation(
            self, tmp_path):
        """Satellite (PR 16): the controller's audit stream rides the
        same tailers as the spans — a `{"kind": "control"}` line torn
        mid-write must NOT be consumed (a half decision would poison
        rebuild_timeline's seq/pool replay), then ingest exactly once
        when the writer finishes it."""
        agg, reg = self._mk(tmp_path)
        p = str(tmp_path / "telemetry_rank0.jsonl")
        _append(p, [self._control(1, "init", "observe", pool=1)])
        full = json.dumps(self._control(
            2, "scale_out", "spawn", pool_before=1, pool_after=2))
        with open(p, "a") as f:
            f.write(full[:40])           # torn mid-record, no newline
        agg.poll()
        assert [r["seq"] for r in agg.control_records] == [1]
        with open(p, "a") as f:          # writer completes the line
            f.write(full[40:] + "\n")
        agg.poll()
        assert [r["seq"] for r in agg.control_records] == [1, 2]
        assert all(r["rank"] == "0" for r in agg.control_records)
        # re-emitted into the launcher's single fleet.jsonl view
        recs = [json.loads(l) for l in
                open(str(tmp_path / "fleet.jsonl"))]
        ctl = [r for r in recs if r.get("event") == "control"]
        assert [(r["seq"], r["rule"]) for r in ctl] \
            == [(1, "init"), (2, "scale_out")]

    def test_control_records_survive_rotation(self, tmp_path):
        """Rotation mid-stream (os.replace to .1 + fresh file) must
        keep the decision seq numbers contiguous — the unread tail of
        the old file drains from the sibling before the new file."""
        agg, reg = self._mk(tmp_path)
        p = str(tmp_path / "telemetry_rank0.jsonl")
        _append(p, [self._control(1, "init", "observe", pool=1)])
        agg.poll()
        # seq 2 written but not yet polled when the file rotates
        _append(p, [self._control(2, "shed", "shed_on",
                                  shed_tiers=["batch"])])
        os.replace(p, p + ".1")
        _append(p, [self._control(3, "shed", "shed_off",
                                  shed_tiers_before=["batch"]),
                    self._control(4, "scale_in", "drain",
                                  pool_before=2, pool_after=1)])
        agg.poll()
        assert [r["seq"] for r in agg.control_records] == [1, 2, 3, 4]
        # breach evidence records ride the same path
        _append(p, [{"kind": "slo_breach", "ts": 1010.0, "slo": "ttft",
                     "burn_fast": 2.0, "burn_slow": 1.1}])
        agg.poll()
        assert [b["slo"] for b in agg.slo_breaches] == ["ttft"]
        assert agg.slo_breaches[0]["rank"] == "0"


# ===========================================================================
# rank identity on exported lines
# ===========================================================================
class TestRankIdentity:
    def test_jsonl_lines_carry_identity(self, tmp_path, monkeypatch):
        from paddle_tpu.observability import runtime as rt
        monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "8")
        monkeypatch.setenv("PADDLE_TPU_TOPOLOGY", "data=4,model=2")
        monkeypatch.setattr(rt, "_identity", None)
        reg = obs.MetricRegistry()
        reg.counter("e.calls").inc()
        p = str(tmp_path / "t.jsonl")
        with obs.JsonlExporter(p, registry=reg) as e:
            e.export(step=1)
            e.write_record({"kind": "span", "name": "x"})
            # a record's own fields always win over identity fields
            e.write_record({"kind": "fleet", "rank": "other"})
        recs = [json.loads(l) for l in open(p)]
        assert all(r["rank"] == 3 for r in recs[:-1])
        assert all(r["world_size"] == 8 for r in recs[:-1])
        assert all(r["topology"] == "data=4,model=2"
                   for r in recs[:-1])
        assert recs[-1]["rank"] == "other"

    def test_no_identity_outside_launcher(self, tmp_path, monkeypatch):
        from paddle_tpu.observability import runtime as rt
        for k in ("PADDLE_TRAINER_ID", "RANK"):
            monkeypatch.delenv(k, raising=False)
        monkeypatch.setattr(rt, "_identity", None)
        reg = obs.MetricRegistry()
        reg.counter("e.calls").inc()
        p = str(tmp_path / "t.jsonl")
        with obs.JsonlExporter(p, registry=reg) as e:
            e.export(step=1)
        rec = json.loads(open(p).readline())
        assert "rank" not in rec and "world_size" not in rec

    def test_topology_only_identity_does_not_leak(self, tmp_path,
                                                  monkeypatch):
        """A process-local topology stamp (HybridTrainStep in a
        single-process run calls set_identity(topology=...)) must NOT
        change the single-process line schema or Prometheus labels —
        identity exports are gated on a launcher-provided rank."""
        from paddle_tpu.observability import runtime as rt
        for k in ("PADDLE_TRAINER_ID", "RANK"):
            monkeypatch.delenv(k, raising=False)
        monkeypatch.setattr(rt, "_identity", None)
        obs.set_identity(topology="stage=2")
        reg = obs.MetricRegistry()
        reg.counter("e.calls").inc(op="all_reduce")
        text = obs.PrometheusExporter(registry=reg).render()
        assert 'e_calls{op="all_reduce"} 1.0' in text
        p = str(tmp_path / "t.jsonl")
        with obs.JsonlExporter(p, registry=reg) as e:
            e.export(step=1)
        rec = json.loads(open(p).readline())
        assert "topology" not in rec and "rank" not in rec

    def test_prometheus_rank_label_and_escaping(self, monkeypatch):
        reg = obs.MetricRegistry()
        reg.counter("e.calls").inc()
        text = obs.PrometheusExporter(
            registry=reg,
            const_labels={"rank": 3,
                          "topology": 'da"ta=4,\nmodel=2'}).render()
        line = [l for l in text.splitlines()
                if l.startswith("e_calls{")][0]
        # escaped per the exposition spec: one well-formed line
        assert line == ('e_calls{rank="3",topology='
                        '"da\\"ta=4,\\nmodel=2"} 1.0')

    def test_set_identity_reaches_live_sink(self, tmp_path):
        from paddle_tpu.observability import runtime as rt
        p = str(tmp_path / "t.jsonl")
        was = rt._identity
        try:
            obs.configure(jsonl_path=p)
            obs.set_identity(rank=5, topology="data=2")
            obs.export_record({"kind": "span", "name": "x"})
            obs.configure(None)
            rec = json.loads(open(p).readline())
            assert rec["rank"] == 5 and rec["topology"] == "data=2"
        finally:
            rt._identity = was
            obs.configure(None)


# ===========================================================================
# tools/fleet_report.py — stdlib-only rendering
# ===========================================================================
class TestFleetReport:
    def _populate(self, tmp_path):
        for step in range(1, 6):
            for rank in range(3):
                dur = 0.4 if (rank == 2 and step >= 2) else 0.05
                recs = _rank_step(rank, step, dur, comm=(0.01,))
                for r in recs:
                    r["rank"] = rank
                    r["topology"] = "data=3"
                recs.append({"rank": rank, "name": "comm.bytes",
                             "kind": "counter",
                             "labels": {"op": "all_reduce",
                                        "axis": "data"},
                             "value": 1000.0 * step})
                _append(str(tmp_path / f"telemetry_rank{rank}.jsonl"),
                        recs)
                _append(str(tmp_path / f"heartbeat_rank{rank}.jsonl"),
                        [{"ts": 1000.0 + step, "kind": "heartbeat"}])

    def test_renders_straggler_table_zero_imports(self, tmp_path):
        """`python -I` (isolated mode): importing paddle_tpu/jax is
        impossible, so a nonzero rc would mean the tool grew a runtime
        dependency. The straggler table renders from files alone."""
        self._populate(tmp_path)
        out = subprocess.run(
            [sys.executable, "-I",
             os.path.join(REPO, "tools", "fleet_report.py"),
             str(tmp_path)],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        assert "per-rank step waterfall" in out.stdout
        assert "straggler ranking" in out.stdout
        assert "rank 2 flagged" in out.stdout
        assert "comm-wait share" in out.stdout
        assert "comm balance" in out.stdout
        assert "topology: data=3" in out.stdout

    def test_multi_file_reports_accept_dir(self, tmp_path):
        """Satellite: trace_report/metrics_report read a --dir of
        per-rank files (rotated .1 siblings folded in)."""
        self._populate(tmp_path)
        # rotate one rank: history moves to .1, fresh file continues
        p0 = str(tmp_path / "telemetry_rank0.jsonl")
        os.replace(p0, p0 + ".1")
        _append(p0, [dict(r, rank=0) for r in _rank_step(0, 6, 0.05)])
        for tool, needle in (("trace_report.py", "train step"),
                             ("metrics_report.py", "collectives")):
            out = subprocess.run(
                [sys.executable, "-I",
                 os.path.join(REPO, "tools", tool),
                 "--dir", str(tmp_path)],
                capture_output=True, text=True, timeout=60)
            assert out.returncode == 0, (tool, out.stderr)
            assert needle in out.stdout, (tool, out.stdout)
        # the rotated rank0 history (steps 1..5) must still be seen:
        # 3 ranks x 5 steps + rank0's post-rotation step 6 = 16 spans
        out = subprocess.run(
            [sys.executable, "-I",
             os.path.join(REPO, "tools", "trace_report.py"),
             "--dir", str(tmp_path)],
            capture_output=True, text=True, timeout=60)
        site = [l for l in out.stdout.splitlines()
                if l.strip().startswith("train.step ")]
        assert site and "16" in site[0]

    def test_renders_slo_and_control_sections(self, tmp_path):
        """Satellite (PR 16): the launcher view renders the SLO burn
        timeline, breach evidence and cross-rank control-decision
        audit from the per-rank JSONL alone, stdlib-only."""
        self._populate(tmp_path)
        _append(str(tmp_path / "telemetry_rank0.jsonl"), [
            {"rank": 0, "name": "slo.burn_rate", "kind": "gauge",
             "ts": 1001.0 + i,
             "labels": {"slo": "ttft", "window": "fast"},
             "value": 0.5 * i} for i in range(4)
        ] + [
            {"rank": 0, "kind": "slo_breach", "ts": 1004.0,
             "slo": "ttft", "burn_fast": 1.5, "burn_slow": 1.1,
             "events_fast": [3, 9], "evidence": [{"name": "r"}]},
            {"rank": 0, "kind": "control", "ts": 1000.5, "seq": 1,
             "tick": 0, "rule": "init", "action": "observe",
             "params": {"pool": 1}, "inputs": {}, "cooldown_s": 0.0},
            {"rank": 0, "kind": "control", "ts": 1004.5, "seq": 2,
             "tick": 7, "rule": "shift_quantum",
             "action": "raise_weight", "tier": "interactive",
             "params": {"weight_before": 1.0, "weight_after": 4.0},
             "inputs": {"burn_fast": 1.5}, "cooldown_s": 5.0},
        ])
        out = subprocess.run(
            [sys.executable, "-I",
             os.path.join(REPO, "tools", "fleet_report.py"),
             str(tmp_path)],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        assert "SLO burn rate" in out.stdout
        assert "ttft" in out.stdout
        assert "SLO breaches" in out.stdout
        assert "control decisions" in out.stdout
        assert "shift_quantum" in out.stdout
        assert "raise_weight" in out.stdout


# ===========================================================================
# a straggler through the real launcher (slow: four worker processes)
# ===========================================================================
@pytest.mark.slow
def test_injected_straggler_detected_recorded_and_reported(
        tmp_path, launch_trainer_workers):
    """Four Trainer workers under the real launcher, rank 2 slowed by
    an injected sleep a step (not a hang: its heartbeat keeps beating,
    so only the skew detector can see it): the launcher-side detector
    flags that rank and only it, `fleet.jsonl` holds the incident, the
    step skew and every rank's comm-wait share, each rank's telemetry
    lines carry its identity and its own data-axis bytes, and
    tools/fleet_report.py renders the table with zero imports."""
    nranks, slow, sleep_s, topology = 4, 2, 0.4, "data=4,model=2"
    log_dir = str(tmp_path / "log")
    reg = obs.get_registry()
    reg.reset()
    rc, _ = launch_trainer_workers(
        ["--nproc_per_node", str(nranks), "--max_restart", "0",
         "--straggler_factor", "2.0", "--straggler_steps", "3",
         "--topology", topology],
        fault=f"slow_rank:times=0:sleep={sleep_s}:rank={slow}",
        fault_epochs=None, total_steps=8 * nranks, save_steps=1000,
        dp_degree=2)
    assert rc == 0

    flagged = {s.labels.get("rank") for s in
               reg.get("robustness.stragglers_detected").samples()
               if s.value > 0}
    assert flagged == {str(slow)}
    # the gauge holds the last completed step's skew: the straggler is
    # still slow then, so a fraction of the injected sleep shows
    assert reg.gauge("fleet.step_skew_seconds").value() \
        >= 0.25 * sleep_s

    def lines(name):
        return [json.loads(ln) for ln in
                open(os.path.join(log_dir, name)) if ln.strip()]

    fleet = lines("fleet.jsonl")
    incidents = [r for r in fleet if r.get("event") == "straggler"]
    assert incidents
    assert {str(r["rank"]) for r in incidents} == {str(slow)}
    steps = [r for r in fleet if r.get("event") == "step"]
    assert max(float(r.get("skew_s", 0)) for r in steps) \
        >= 0.5 * sleep_s
    assert any(len(r.get("comm_wait_share") or {}) == nranks
               for r in steps)
    for k in range(nranks):
        recs = lines(f"telemetry_rank{k}.jsonl")
        assert any(r.get("rank") == k and r.get("world_size") == nranks
                   and r.get("topology") == topology for r in recs), k
        assert sum(r.get("value", 0) for r in recs
                   if r.get("name") == "comm.bytes"
                   and (r.get("labels") or {}).get("axis") == "data") \
            > 0, k

    rep = subprocess.run(
        [sys.executable, "-I",
         os.path.join(REPO, "tools", "fleet_report.py"), log_dir],
        capture_output=True, text=True, timeout=120)
    assert rep.returncode == 0, rep.stderr
    assert f"rank {slow} flagged" in rep.stdout

"""tools/trace_replay.py and the control loop it replays (PR 16).

- synthesize(): deterministic production-shaped traces — zipf sessions,
  tenant mix, the spike as EXTRA spike-tier load on top of base traffic
  (the base mix keeps arriving through the spike window).
- write/load round trip, torn-line tolerance, session prompts with
  shared per-session prefixes.
- fit_from_telemetry(): shape-only spec estimation from recorded spans.
- rebuild_timeline(): the control-decision audit replayer, including
  every inconsistency it must refuse.
- CLI under `python -I` (stdlib-only, like every tools/ reader).
- the checked-in fixture trace through a real Router with its SLO
  engine and PoolController on one injected clock: the
  {"kind": "control"} records are contiguous, rebuild to the live end
  state, and trace_report renders them.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TR_PATH = os.path.join(REPO, "tools", "trace_replay.py")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tr():
    return _load("trace_replay_mod", TR_PATH)


SPEC = {
    "requests": 300, "duration_s": 60.0, "sessions": 6,
    "zipf_alpha": 1.1, "seed": 7, "diurnal": 0.0,
    "tiers": {"interactive": 0.8, "batch": 0.2},
    "prompt_len_p50": 32, "prompt_len_max": 128,
    "max_new_p50": 16, "max_new_max": 64,
    "spike": {"start_frac": 0.4, "dur_frac": 0.3, "factor": 5.0,
              "tier": "batch", "prompt_len_factor": 1.0},
}


class TestSynthesize:
    def test_deterministic_for_a_seed(self, tr):
        a = tr.synthesize(SPEC)
        b = tr.synthesize(SPEC)
        assert a == b
        c = tr.synthesize(dict(SPEC, seed=8))
        assert a != c

    def test_shape_and_bounds(self, tr):
        reqs = tr.synthesize(SPEC)
        assert len(reqs) == 300
        assert reqs == sorted(reqs, key=lambda r: r["t"])
        for r in reqs:
            assert r["kind"] == "trace_request"
            assert 0.0 <= r["t"] <= 60.0
            assert 0 <= r["session"] < 6
            assert r["tier"] in ("interactive", "batch")
            assert 4 <= r["prompt_len"] <= 128
            assert 1 <= r["max_new"] <= 64
            assert r["phase"] in ("base", "spike")

    def test_spike_is_extra_load_on_top_of_base_traffic(self, tr):
        """The flood must not REPLACE the base tenants: the 1/factor
        fraction of spike-window arrivals the base rate accounts for
        keeps the base tier mix, so per-tenant SLO claims have spike-
        phase samples to stand on."""
        reqs = tr.synthesize(SPEC)
        base = [r for r in reqs if r["phase"] == "base"]
        spike = [r for r in reqs if r["phase"] == "spike"]
        assert base and spike
        # the window is rate-multiplied: it holds most of the requests
        assert len(spike) > len(base)
        sp_tiers = {t: sum(1 for r in spike if r["tier"] == t)
                    for t in ("interactive", "batch")}
        # the excess is the flood...
        assert sp_tiers["batch"] > 0.6 * len(spike)
        # ...but the interactive tenant keeps arriving through it
        assert sp_tiers["interactive"] > 0.05 * len(spike)
        # base phase keeps roughly the declared mix
        b_int = sum(1 for r in base if r["tier"] == "interactive")
        assert b_int > 0.6 * len(base)

    def test_no_spike_no_spike_phase(self, tr):
        reqs = tr.synthesize(dict(SPEC, spike=None))
        assert all(r["phase"] == "base" for r in reqs)


class TestTraceIO:
    def test_write_load_round_trip(self, tr, tmp_path):
        reqs = tr.synthesize(dict(SPEC, requests=20))
        p = str(tmp_path / "t.jsonl")
        tr.write_trace(p, reqs, SPEC)
        header, loaded = tr.load_trace(p)
        assert header["kind"] == "trace_header"
        assert header["spec"]["seed"] == 7
        assert loaded == reqs

    def test_torn_final_line_tolerated(self, tr, tmp_path):
        reqs = tr.synthesize(dict(SPEC, requests=5))
        p = str(tmp_path / "t.jsonl")
        tr.write_trace(p, reqs, SPEC)
        with open(p, "a") as f:
            f.write('{"kind": "trace_request", "t": 1.0, "trunc')
        _, loaded = tr.load_trace(p)
        assert len(loaded) == 5

    def test_session_prompts_share_prefixes(self, tr):
        long = tr.session_prompt(3, 32, vocab=1000)
        short = tr.session_prompt(3, 16, vocab=1000)
        other = tr.session_prompt(4, 32, vocab=1000)
        assert long[:8] == short[:8]      # shared per-session prefix
        assert long[:8] != other[:8]
        assert len(long) == 32 and len(short) == 16
        assert all(2 <= t < 1000 for t in long)


class TestFitFromTelemetry:
    def test_fit_recovers_the_shape(self, tr, tmp_path):
        p = str(tmp_path / "spans.jsonl")
        with open(p, "w") as f:
            for i in range(40):
                tier = "interactive" if i % 4 else "batch"
                f.write(json.dumps(
                    {"kind": "span", "name": "router.request",
                     "start": 100.0 + i * 0.5,
                     "labels": {"tier": tier, "prompt_len": 16 + i},
                     "events": [{"name": "finish", "tokens": 8}]}) + "\n")
            f.write("not json\n")
        spec = tr.fit_from_telemetry([p])
        assert spec["requests"] == 40
        assert spec["duration_s"] == pytest.approx(19.5)
        assert spec["prompt_len_max"] == 55
        assert spec["max_new_p50"] == 8
        assert spec["tiers"]["interactive"] == pytest.approx(0.75)
        assert spec["tiers"]["batch"] == pytest.approx(0.25)


def _rec(seq, rule, action, params, tick=0, tier=None):
    r = {"kind": "control", "ts": 1.0 + seq, "seq": seq, "tick": tick,
         "rule": rule, "action": action, "params": params,
         "inputs": {}, "cooldown_s": 0.0}
    if tier:
        r["tier"] = tier
    return r


def _init(seq=1, pool=1, weights=None, shed=()):
    return _rec(seq, "init", "observe",
                {"pool": pool, "tier_weights": weights or {},
                 "shed_tiers": sorted(shed)})


class TestRebuildTimeline:
    def test_replays_to_end_state(self, tr):
        recs = [
            _init(1, pool=1, weights={"gold": 1.0, "bulk": 1.0}),
            _rec(2, "shed", "shed_on", {"shed_tiers": ["bulk"]},
                 tier="bulk"),
            _rec(3, "shift_quantum", "raise_weight",
                 {"weight_before": 1.0, "weight_after": 4.0},
                 tier="gold"),
            _rec(4, "scale_out", "spawn",
                 {"pool_before": 1, "pool_after": 2}),
            _rec(5, "shed", "shed_off", {"shed_tiers_before": ["bulk"]}),
            _rec(6, "scale_in", "drain",
                 {"pool_before": 2, "pool_after": 1, "parked": True}),
        ]
        # interleaved non-control records must be ignored
        tl = tr.rebuild_timeline(recs + [{"kind": "autoscale"}])
        assert tl["pool_size"] == 1
        assert tl["tier_weights"] == {"gold": 4.0, "bulk": 1.0}
        assert tl["shed_tiers"] == []
        assert tl["decisions"] == 5
        assert [a["rule"] for a in tl["actions"]] == [
            "shed", "shift_quantum", "scale_out", "shed", "scale_in"]

    def test_rejects_missing_init(self, tr):
        with pytest.raises(ValueError, match="init"):
            tr.rebuild_timeline([_rec(1, "shed", "shed_on",
                                      {"shed_tiers": ["b"]}, tier="b")])

    def test_rejects_empty(self, tr):
        with pytest.raises(ValueError, match="no control records"):
            tr.rebuild_timeline([{"kind": "autoscale"}])

    def test_rejects_seq_gap(self, tr):
        recs = [_init(1), _rec(3, "scale_out", "spawn",
                               {"pool_before": 1, "pool_after": 2})]
        with pytest.raises(ValueError, match="gap"):
            tr.rebuild_timeline(recs)

    def test_rejects_pool_mismatch(self, tr):
        recs = [_init(1, pool=1),
                _rec(2, "scale_out", "spawn",
                     {"pool_before": 3, "pool_after": 4})]
        with pytest.raises(ValueError, match="pool_before"):
            tr.rebuild_timeline(recs)


class TestCLIPythonI:
    """Every tools/ reader must run stdlib-only under `python -I`."""

    def _run(self, args):
        return subprocess.run(
            [sys.executable, "-I", TR_PATH] + args,
            capture_output=True, text=True, timeout=120)

    def test_synth_show_timeline(self, tr, tmp_path):
        out = str(tmp_path / "trace.jsonl")
        r = self._run(["synth", "--out", out, "--requests", "50",
                       "--duration", "10", "--seed", "3",
                       "--tiers", "interactive=0.8,batch=0.2",
                       "--spike", "0.4,0.3,5,batch"])
        assert r.returncode == 0, r.stderr
        assert "trace: 50 requests" in r.stdout
        r = self._run(["show", out])
        assert r.returncode == 0, r.stderr
        assert "tiers=" in r.stdout and "phases=" in r.stdout

        tele = str(tmp_path / "telemetry.jsonl")
        with open(tele, "w") as f:
            for rec in (_init(1, pool=1, weights={"g": 1.0}),
                        _rec(2, "scale_out", "spawn",
                             {"pool_before": 1, "pool_after": 2})):
                f.write(json.dumps(rec) + "\n")
        r = self._run(["timeline", tele])
        assert r.returncode == 0, r.stderr
        tl = json.loads(r.stdout)
        assert tl["pool_size"] == 2

    def test_timeline_rejects_inconsistent_stream(self, tmp_path):
        tele = str(tmp_path / "telemetry.jsonl")
        with open(tele, "w") as f:
            f.write(json.dumps(_rec(2, "scale_out", "spawn",
                                    {"pool_before": 1,
                                     "pool_after": 2})) + "\n")
        r = self._run(["timeline", tele])
        assert r.returncode != 0
        assert "init" in r.stderr


# ---------------------------------------------------------------------------
# the fixture trace through router, SLO engine and controller
# ---------------------------------------------------------------------------
class TestReplayControlLoop:
    def test_spike_drives_an_auditable_decision_stream(self, tr,
                                                       tmp_path):
        """The checked-in spike trace replayed against a real Router
        whose PoolController and SLOEngine share one injected clock.
        The declared target is one no request can meet, so the burn
        (and with it every rule that fires) does not depend on how
        fast this machine is. The `{"kind": "control"}` records in the
        sink are contiguous, rebuild to the live end state, and the
        report renders them stdlib-only."""
        import paddle_tpu as paddle
        import paddle_tpu.observability as obs
        from paddle_tpu.observability import runtime as obs_rt
        from paddle_tpu.observability.slo import SLOEngine, SLOSpec
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference import ContinuousBatchingPredictor
        from paddle_tpu.serving import (Router, PoolController,
                                        ControllerConfig)
        _, reqs = tr.load_trace(os.path.join(
            REPO, "tests", "fixtures", "trace_smoke.jsonl"))
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
        vocab = model.config.vocab_size
        kw = dict(max_batch_size=1, page_size=8, max_seq_len=64)
        clock = {"t": 1000.0}
        now = lambda: clock["t"]
        out = str(tmp_path / "replay.jsonl")
        was = obs.enabled()
        obs.enabled(True)
        obs.get_registry().reset()
        obs_rt.configure(out)
        try:
            spares = [ContinuousBatchingPredictor(model, name="spare0",
                                                  **kw)]
            engine = SLOEngine(
                [SLOSpec("ttft", "serving.router.ttft_seconds",
                         target=1e-9, objective=0.9),
                 SLOSpec("ttft_interactive",
                         "serving.router.ttft_seconds",
                         target=1e-9, objective=0.9,
                         labels={"tier": "interactive"},
                         tier="interactive")],
                fast_window_s=1.0, slow_window_s=10.0, now_fn=now)
            with Router([ContinuousBatchingPredictor(
                    model, name="replica0", **kw)],
                    tier_weights={"interactive": 1, "batch": 1},
                    seed=0) as router:
                ctl = PoolController(
                    router, slo_engine=engine,
                    spawn=lambda: spares.pop() if spares else None,
                    config=ControllerConfig(
                        slo_name="ttft", shed_burn=1.2,
                        scale_out_cooldown_s=0.2,
                        shift_cooldown_s=0.3, max_replicas=2),
                    now_fn=now)
                statuses = {}
                for r in reqs:
                    # the trace's own arrival times, on the injected
                    # clock: one control tick after every request
                    clock["t"] = 1000.0 + float(r["t"])
                    h = router.submit(
                        tr.session_prompt(int(r["session"]),
                                          int(r["prompt_len"]), vocab),
                        max_new_tokens=int(r["max_new"]),
                        tier=r["tier"])
                    h.result(timeout=120)
                    statuses[h.status] = statuses.get(h.status, 0) + 1
                    ctl.tick()
                live = {"pool_size": len(router.healthy()),
                        "tier_weights": {
                            k: float(v)
                            for k, v in router.tier_weights.items()},
                        "shed_tiers": sorted(router.shed_tiers)}
                decisions = list(ctl.decisions)
            obs_rt.maybe_export()
        finally:
            obs_rt.configure(None)
            obs.enabled(was)

        assert statuses.get("ok", 0) >= 1
        assert sum(statuses.values()) == len(reqs)
        rules = {d["rule"] for d in decisions}
        assert {"init", "scale_out", "shed"} <= rules, rules
        assert live["pool_size"] == 2                # the spare joined
        assert statuses.get("shed", 0) >= 1          # batch refused

        recs = [json.loads(ln) for ln in open(out) if ln.strip()]
        ctrl = [r for r in recs if r.get("kind") == "control"]
        assert ctrl == decisions                     # the sink has all
        assert [r["seq"] for r in ctrl] \
            == list(range(1, len(ctrl) + 1))
        assert [r for r in recs if r.get("kind") == "autoscale"]
        rebuilt = tr.rebuild_timeline(recs)
        assert {k: rebuilt[k] for k in live} == live

        rep = subprocess.run(
            [sys.executable, "-I",
             os.path.join(REPO, "tools", "trace_report.py"), out],
            capture_output=True, text=True, timeout=120)
        assert rep.returncode == 0, rep.stderr
        assert "== control decisions ==" in rep.stdout
        assert "scale_out" in rep.stdout

"""Closing the observability loop (PR 11): typed RuntimeConfig,
telemetry replay (tools/autotune.py), versioned auto-tuned deploy
bundles, and the reader hardening that rides along:

- RuntimeConfig schema: defaults == historical behavior, FLAGS bridge,
  round-trip, canonical hash (parity with the standalone tools that
  must not import paddle_tpu), bucket-table lookup;
- golden synthetic-telemetry fixtures: each autotune proposal fires on
  the workload shape built to trigger it, with the telemetry evidence
  (series / n / window / percentile) attached;
- RuntimeConfig -> bundle -> warm_start round trip: the config hash
  joins the bundle identity (mismatch invalidates + self-heals like a
  geometry change) and config-vs-flags drift lands in
  aot.config_drift;
- torn-final-line tolerance + JsonlExporter size rotation across every
  reader (trace_report, metrics_report, autotune);
- the closed loop once, live: a page-pressured run's telemetry ->
  proposal -> tuned config -> the same prompts served with greedy
  parity and fewer page evictions (a count).
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool(name):
    """Import a standalone tools/ module (they are not a package)."""
    spec = importlib.util.spec_from_file_location(
        f"_tool_{name}", os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_jsonl(path, records, torn_tail=None):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
        if torn_tail is not None:
            f.write(torn_tail)   # no newline: a mid-record crash
    return path


def _span(ts, prompt_len, ttft_s, status="ok", tier=None, tokens=4,
          rid="r"):
    labels = {"request_id": rid, "prompt_len": prompt_len}
    if tier is not None:
        labels["tier"] = tier
    return {"kind": "span", "name": "serve.request", "ts": ts,
            "start": ts, "dur": ttft_s + 0.05, "status": status,
            "labels": labels,
            "events": [{"name": "first_token", "ts": ts + ttft_s},
                       {"name": "finish", "ts": ts + ttft_s + 0.05,
                        "tokens": tokens}]}


def _sample(ts, name, kind, value, **labels):
    return {"ts": ts, "name": name, "kind": kind, "labels": labels,
            "value": value}


# ===========================================================================
# RuntimeConfig schema
# ===========================================================================
class TestRuntimeConfig:
    def test_defaults_match_historical_knobs(self):
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        rc = RuntimeConfig()
        assert (rc.max_batch_size, rc.page_size, rc.max_seq_len) == \
            (4, 16, 512)
        assert rc.num_pages is None and rc.max_queue is None
        assert rc.prefill_chunk_tokens == 0
        assert rc.shed_policy == "newest"
        assert rc.wfs_quantum == 64.0
        assert rc.grad_bucket_bytes == 32 * 1024 * 1024
        assert rc.quantized_grad_comm is False

    def test_from_flags_bridges_migrated_knobs(self):
        import paddle_tpu as paddle
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        paddle.set_flags({"grad_bucket_bytes": 1 << 20,
                          "serve_prefill_chunk_tokens": 32})
        try:
            rc = RuntimeConfig.from_flags()
            assert rc.grad_bucket_bytes == 1 << 20
            assert rc.prefill_chunk_tokens == 32
        finally:
            paddle.set_flags({"grad_bucket_bytes": 32 * 1024 * 1024,
                              "serve_prefill_chunk_tokens": 0})
        assert RuntimeConfig.from_flags().grad_bucket_bytes == 32 << 20

    def test_round_trip_and_validation(self):
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        rc = RuntimeConfig(prompt_buckets=(32, 8, 8), max_queue=7)
        assert rc.prompt_buckets == (8, 32)   # sorted, deduped
        rc2 = RuntimeConfig.from_dict(rc.to_dict())
        assert rc2 == rc and rc2.config_hash() == rc.config_hash()
        with pytest.raises(ValueError, match="unknown"):
            RuntimeConfig.from_dict({**rc.to_dict(), "bogus": 1})
        with pytest.raises(ValueError, match="version"):
            RuntimeConfig.from_dict({**rc.to_dict(), "version": 99})
        with pytest.raises(ValueError, match="shed_policy"):
            RuntimeConfig(shed_policy="loudest")

    def test_diff_names_changed_fields(self):
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        a = RuntimeConfig()
        b = a.replace(num_pages=64, quantized_grad_comm=True)
        assert set(a.diff(b)) == {"num_pages", "quantized_grad_comm"}
        assert a.diff(a) == {}

    def test_hash_parity_with_standalone_tools(self):
        """tools/autotune.py and tools/aot_report.py reimplement the
        canonical hash (they must run without paddle_tpu); the three
        implementations must agree byte for byte, and the autotune
        defaults table must mirror the dataclass defaults."""
        from paddle_tpu.framework.runtime_config import (RuntimeConfig,
                                                         config_hash)
        at, ar = _tool("autotune"), _tool("aot_report")
        for rc in (RuntimeConfig(),
                   RuntimeConfig(prompt_buckets=(8, 64), num_pages=40,
                                 quantized_grad_comm=True,
                                 wfs_quantum=24.0)):
            d = rc.to_dict()
            assert rc.config_hash() == config_hash(d) \
                == at.config_hash(d) == ar.config_hash(d)
        assert at.CONFIG_DEFAULTS == RuntimeConfig().to_dict()

    def test_prompt_bucket_lookup(self):
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        rc = RuntimeConfig(prompt_buckets=(6, 12))
        assert rc.prompt_bucket(5) == 6
        assert rc.prompt_bucket(6) == 6
        assert rc.prompt_bucket(7) == 12
        assert rc.prompt_bucket(13) == 16   # pow2 fallback past table
        assert RuntimeConfig().prompt_bucket(24) == 32  # historical


# ===========================================================================
# golden synthetic-telemetry fixtures: each proposal fires on the
# workload shape built to trigger it, with its evidence attached
# ===========================================================================
class TestGoldenProposals:
    def test_skewed_prompt_mix_proposes_buckets_and_chunking(self, tmp_path):
        at = _tool("autotune")
        # 15 short prompts around 20 tokens, one 480-token tail
        recs = [_span(1.0 + i, 20 + (i % 3), 0.01, rid=f"r{i}")
                for i in range(15)]
        recs.append(_span(20.0, 480, 0.2, rid="tail"))
        p = _write_jsonl(tmp_path / "t.jsonl", recs)
        rep = at.analyze([str(p)])
        by_field = {x["field"]: x for x in rep["proposals"]}
        bk = by_field["prompt_buckets"]
        assert bk["evidence"]["series"] == "serve.request.prompt_len"
        assert bk["evidence"]["n"] == 16
        assert 32 in bk["proposed"] and 512 in bk["proposed"]
        ch = by_field["prefill_chunk_tokens"]
        assert ch["proposed"] == 16        # pow2*page cover of the p50
        assert ch["evidence"]["percentile"] == "p99"
        assert ch["evidence"]["value"] >= 4 * ch["evidence"]["p50"]
        # tuned config carries both + the canonical hash
        assert rep["runtime_config"]["prompt_buckets"] == bk["proposed"]
        assert rep["runtime_config_hash"] == at.config_hash(
            rep["runtime_config"])

    def test_uniform_prompts_do_not_propose_chunking(self, tmp_path):
        at = _tool("autotune")
        recs = [_span(1.0 + i, 24, 0.01, rid=f"r{i}")
                for i in range(12)]
        p = _write_jsonl(tmp_path / "t.jsonl", recs)
        rep = at.analyze([str(p)])
        fields = {x["field"] for x in rep["proposals"]}
        assert "prefill_chunk_tokens" not in fields

    def test_page_pressure_spike_proposes_pool_growth(self, tmp_path):
        at = _tool("autotune")
        recs = [_sample(1.0 + i, "serving.page_utilization", "gauge",
                        0.95) for i in range(10)]
        recs.append(_sample(11.0, "serving.page_evictions", "counter",
                            12))
        recs.append(_sample(11.0, "serving.hol_skips", "counter", 3))
        p = _write_jsonl(tmp_path / "t.jsonl", recs)
        base = {"num_pages": 16, "page_size": 8, "max_seq_len": 96,
                "max_batch_size": 2}
        rep = at.analyze([str(p)], base=base)
        pool = next(x for x in rep["proposals"]
                    if x["field"] == "num_pages")
        assert pool["proposed"] > 16
        ev = pool["evidence"]
        assert ev["series"] == "serving.page_utilization"
        assert ev["percentile"] == "p95" and ev["value"] > 0.9
        assert ev["page_evictions"] == 12 and ev["hol_skips"] == 3

    def test_idle_pool_proposes_shrink(self, tmp_path):
        at = _tool("autotune")
        recs = [_sample(1.0 + i, "serving.page_utilization", "gauge",
                        0.10) for i in range(10)]
        p = _write_jsonl(tmp_path / "t.jsonl", recs)
        rep = at.analyze([str(p)], base={"num_pages": 64,
                                         "page_size": 8,
                                         "max_seq_len": 96})
        pool = next(x for x in rep["proposals"]
                    if x["field"] == "num_pages")
        assert pool["proposed"] < 64
        assert pool["proposed"] >= -(-96 // 8) + 1   # one-request floor

    def test_slo_burn_flood_proposes_queue_bound(self, tmp_path):
        at = _tool("autotune")
        # TTFT-SLO flood: every request waits ~2s against a 0.25s SLO
        recs = [_span(1.0 + i, 16, 2.0, rid=f"r{i}")
                for i in range(12)]
        recs.append(_sample(20.0, "serving.slots", "gauge", 4))
        p = _write_jsonl(tmp_path / "t.jsonl", recs)
        rep = at.analyze([str(p)], slo_ttft_s=0.25)
        q = next(x for x in rep["proposals"] if x["field"] == "max_queue")
        assert q["proposed"] >= 1
        ev = q["evidence"]
        assert ev["series"] == "serving.ttft_seconds"
        assert ev["burn"] > 1.0 and ev["slo_ttft_s"] == 0.25
        assert ev["percentile"] == "p99"

    def test_shed_with_headroom_raises_queue_bound(self, tmp_path):
        at = _tool("autotune")
        recs = [_span(1.0 + i, 16, 0.01, rid=f"r{i}")
                for i in range(12)]
        recs.append(_sample(20.0, "robustness.shed_requests",
                            "counter", 5, policy="newest"))
        p = _write_jsonl(tmp_path / "t.jsonl", recs)
        rep = at.analyze([str(p)], base={"max_queue": 8},
                         slo_ttft_s=0.25)
        q = next(x for x in rep["proposals"] if x["field"] == "max_queue")
        assert q["proposed"] == 16
        assert q["evidence"]["series"] == "robustness.shed_requests"

    def test_tier_costs_propose_wfs_quantum(self, tmp_path):
        at = _tool("autotune")
        recs = [_span(1.0 + i, 200, 0.01, tier="batch", tokens=56,
                      rid=f"r{i}") for i in range(10)]
        p = _write_jsonl(tmp_path / "t.jsonl", recs)
        rep = at.analyze([str(p)])
        qn = next(x for x in rep["proposals"]
                  if x["field"] == "wfs_quantum")
        assert qn["proposed"] == 256.0   # p50 cost = 200 + 56
        assert qn["evidence"]["series"] == "serve.request.cost"

    def test_comm_accounting_proposes_buckets_and_quantization(
            self, tmp_path):
        at = _tool("autotune")
        # 20 steps, 512 reduce-scatter calls moving 2GiB/step: tiny
        # buckets (many launches) against heavy wire traffic — the
        # 32MiB default is >4x off the ~8-buckets/step target, and the
        # volume is far past the int8-comm threshold
        recs = [
            _sample(1.0, "train.steps", "counter", 20),
            _sample(1.0, "comm.bytes", "counter", 20 * (2 << 30),
                    op="reduce_scatter", axis="data"),
            _sample(1.0, "comm.calls", "counter", 20 * 512,
                    op="reduce_scatter", axis="data"),
        ]
        p = _write_jsonl(tmp_path / "t.jsonl", recs)
        rep = at.analyze([str(p)])
        by_field = {x["field"]: x for x in rep["proposals"]}
        gb = by_field["grad_bucket_bytes"]
        assert gb["proposed"] != 32 << 20
        assert gb["evidence"]["series"] == "comm.bytes"
        assert gb["evidence"]["steps"] == 20
        q8 = by_field["quantized_grad_comm"]
        assert q8["proposed"] is True
        assert q8["evidence"]["value"] > q8["evidence"]["threshold"]

    def test_quiet_telemetry_proposes_nothing(self, tmp_path):
        at = _tool("autotune")
        p = _write_jsonl(tmp_path / "t.jsonl",
                         [_span(1.0, 16, 0.01, rid="r0")])
        rep = at.analyze([str(p)])
        assert rep["proposals"] == []
        assert rep["runtime_config"] == at.CONFIG_DEFAULTS


# ===========================================================================
# torn final lines + size rotation, across every reader
# ===========================================================================
class TestTornAndRotation:
    def test_autotune_replay_tolerates_torn_final_line(self, tmp_path,
                                                       capsys):
        at = _tool("autotune")
        recs = [_span(1.0 + i, 20, 0.01, rid=f"r{i}")
                for i in range(9)]
        p = _write_jsonl(tmp_path / "t.jsonl", recs,
                         torn_tail='{"kind": "span", "na')
        rep = at.analyze([str(p)])
        assert rep["requests"] == 9
        assert "torn final line" in capsys.readouterr().err

    def test_trace_report_tolerates_torn_final_line(self, tmp_path,
                                                    capsys):
        tr = _tool("trace_report")
        p = _write_jsonl(tmp_path / "t.jsonl",
                         [_span(1.0, 20, 0.01, rid="r0")],
                         torn_tail='{"kind": "sp')
        spans = tr.load_spans(str(p))
        assert len(spans) == 1
        assert "torn final line" in capsys.readouterr().err

    def test_metrics_report_tolerates_torn_final_line(self, tmp_path):
        p = _write_jsonl(tmp_path / "t.jsonl",
                         [_sample(1.0, "serving.admissions", "counter",
                                  3)],
                         torn_tail='{"ts": 2.0, "na')
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "metrics_report.py"), str(p)],
            capture_output=True, text=True)
        assert r.returncode == 0
        assert "torn final line" in r.stderr
        assert "admissions" in r.stdout

    def test_jsonl_exporter_rotation_and_rotated_readers(self, tmp_path):
        from paddle_tpu.observability.exporters import JsonlExporter
        import paddle_tpu.observability as obs
        tr = _tool("trace_report")
        at = _tool("autotune")
        was = obs.enabled()
        obs.enabled(True)
        path = str(tmp_path / "t.jsonl")
        try:
            exp = JsonlExporter(path, max_bytes=512)
            n = 24
            for i in range(n):
                exp.write_record(
                    _span(1.0 + i, 20, 0.01, rid=f"r{i}"))
            exp.close()
        finally:
            obs.enabled(was)
        assert os.path.exists(path + ".1")   # rotated at least once
        # rotation never tears a line: every line in both files parses
        for f in (path, path + ".1"):
            for line in open(f):
                json.loads(line)
        # readers fold the rotated sibling back in (the last rotation
        # may have dropped older generations — .2+ are not kept — so
        # everything in the surviving pair must be visible)
        kept = sum(1 for f in (path, path + ".1")
                   for _ in open(f))
        spans = tr.load_spans(path)
        assert len(spans) == kept > 0
        assert at.analyze([path])["requests"] == kept

    def test_rotation_disabled_by_default(self, tmp_path):
        from paddle_tpu.observability.exporters import JsonlExporter
        path = str(tmp_path / "t.jsonl")
        exp = JsonlExporter(path)
        for i in range(50):
            exp.write_record({"i": i, "pad": "x" * 100})
        exp.close()
        assert not os.path.exists(path + ".1")

    def test_autotune_cli_dry_run_smoke(self, tmp_path):
        """The tier-1 CLI smoke the lint/CI checklist names: --dry-run
        analyzes, prints, and never writes."""
        recs = [_span(1.0 + i, 20, 0.01, rid=f"r{i}")
                for i in range(10)]
        recs.append(_span(30.0, 480, 0.2, rid="tail"))
        p = _write_jsonl(tmp_path / "t.jsonl", recs)
        out = str(tmp_path / "tuned.json")
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "autotune.py"),
             str(p), "--dry-run", "--out", out],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert "prompt_buckets" in r.stdout
        assert "evidence" in r.stdout
        assert not os.path.exists(out)       # dry run never writes
        r2 = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "autotune.py"),
             str(p), "--out", out, "--json"],
            capture_output=True, text=True)
        assert r2.returncode == 0
        rep = json.loads(open(out).read())
        assert rep["runtime_config_hash"] == json.loads(
            r2.stdout)["runtime_config_hash"]
        # a report file round-trips as --base
        r3 = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "autotune.py"),
             str(p), "--base", out, "--dry-run"],
            capture_output=True, text=True)
        assert r3.returncode == 0


# ===========================================================================
# RuntimeConfig -> bundle -> warm_start round trip
# ===========================================================================
def _tiny_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))


class TestConfigBundleRoundTrip:
    def test_manifest_records_config_and_hash(self, tmp_path):
        from paddle_tpu.inference.aot import EngineBuilder
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        model = _tiny_model()
        rc = RuntimeConfig(max_batch_size=2, page_size=8,
                           max_seq_len=64, prompt_buckets=(8,),
                           max_queue=16)
        b = EngineBuilder(model, batch_sizes=[1], capture_forward=False,
                          runtime_config=rc)
        man = b.build(str(tmp_path / "bundle"), wire_cache=False)
        eff = b.effective_runtime_config()
        assert man["runtime_config"] == eff.to_dict()
        assert man["runtime_config_hash"] == eff.config_hash()
        assert man["runtime_config"]["max_queue"] == 16
        assert man["runtime_config"]["prompt_buckets"] == [8]

    def test_config_change_invalidates_and_self_heals(self, tmp_path):
        """A RuntimeConfig disagreeing with the bundle on a COMPILED
        field is rejected (reason runtime_config) and the bundle
        resets to the requested config — the same self-heal contract
        as a geometry change. Runtime-only fields (queue, WFS quantum,
        watchdog, grad comm) differ freely: the explicit config
        serves, the shared bundle survives."""
        import paddle_tpu.observability as obs
        from paddle_tpu.inference.aot import EngineBuilder, warm_start
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        from paddle_tpu.inference.aot.bundle import BundleInvalid
        model = _tiny_model()
        rc = RuntimeConfig(max_batch_size=2, page_size=8,
                           max_seq_len=64, prompt_buckets=(8,))
        path = str(tmp_path / "bundle")
        EngineBuilder(model, batch_sizes=[1], capture_forward=False,
                      runtime_config=rc).build(path, wire_cache=False)
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            # same config: warm, no invalidation
            p1, e1 = warm_start(model, path, wire_cache=False,
                                runtime_config=rc)
            assert e1.warm
            inv = obs.get_registry().get("aot.invalidations")
            assert inv is None or not any(
                s.labels.get("reason") == "runtime_config"
                for s in inv.samples())
            # no explicit config: the bundle's baked config serves
            p2, _ = warm_start(model, path, wire_cache=False)
            assert p2._rc_buckets == (8,)
            assert p2.B == 2 and p2.page == 8
            # runtime-only difference: NO invalidation, bundle stays
            # warm, and the explicit config's knob serves
            rt = rc.replace(wfs_quantum=24.0, max_queue=9)
            p_rt, e_rt = warm_start(model, path, wire_cache=False,
                                    runtime_config=rt)
            assert e_rt.warm
            assert p_rt.max_queue == 9
            inv = obs.get_registry().get("aot.invalidations")
            assert inv is None or not any(
                s.labels.get("reason") == "runtime_config"
                for s in inv.samples())
            # compiled-field difference: strict raises...
            rc2 = rc.replace(prompt_buckets=(8, 16))
            with pytest.raises(BundleInvalid, match="runtime_config"):
                warm_start(model, path, wire_cache=False,
                           runtime_config=rc2, strict=True)
            # ...non-strict invalidates, heals, and re-records
            p3, e3 = warm_start(model, path, wire_cache=False,
                                runtime_config=rc2)
            inv = obs.get_registry().get("aot.invalidations")
            assert any(s.labels.get("reason") == "runtime_config"
                       for s in inv.samples())
            assert not e3.warm
            assert e3.bundle.manifest(refresh=True)[
                "runtime_config_hash"] == rc2.config_hash()
            out = p3.generate([[3, 4, 5]], max_new_tokens=2)
            assert len(out[0]) == 2
        finally:
            obs.enabled(was)

    def test_auto_fields_accept_baked_resolution(self, tmp_path):
        """A requested config leaving num_pages/prompt_buckets on
        their auto sentinels expresses no opinion: the documented
        deploy flow (build with rc, warm_start with the SAME rc) must
        not invalidate the just-built bundle on the builder's resolved
        defaults — and the serving predictor adopts the baked values
        so it matches the compiled artifacts exactly."""
        import paddle_tpu.observability as obs
        from paddle_tpu.inference.aot import EngineBuilder, warm_start
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        model = _tiny_model()
        rc = RuntimeConfig(max_batch_size=2, page_size=8,
                           max_seq_len=64)   # buckets (), num_pages None
        path = str(tmp_path / "bundle")
        EngineBuilder(model, batch_sizes=[1], capture_forward=False,
                      runtime_config=rc).build(path, wire_cache=False)
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            p, e = warm_start(model, path, wire_cache=False,
                              runtime_config=rc)
            assert e.warm   # no invalidation, no reset
            inv = obs.get_registry().get("aot.invalidations")
            assert inv is None or not any(
                s.labels.get("reason") == "runtime_config"
                for s in inv.samples())
            assert p._rc_buckets == (8, 16)   # baked table adopted
        finally:
            obs.enabled(was)

    def test_corrupt_baked_config_self_heals(self, tmp_path):
        """A manifest runtime_config that from_dict rejects (unknown
        key / bad version — hand-edited or newer-schema) invalidates
        and self-heals instead of escaping as a raw ValueError."""
        import paddle_tpu.observability as obs
        from paddle_tpu.inference.aot import EngineBuilder, warm_start
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        from paddle_tpu.inference.aot.bundle import BundleInvalid
        model = _tiny_model()
        rc = RuntimeConfig(max_batch_size=2, page_size=8,
                           max_seq_len=64)
        path = str(tmp_path / "bundle")
        EngineBuilder(model, batch_sizes=[1], capture_forward=False,
                      runtime_config=rc).build(path, wire_cache=False)
        mp = os.path.join(path, "manifest.json")
        man = json.load(open(mp))
        man["runtime_config"]["knob_from_the_future"] = 1
        json.dump(man, open(mp, "w"))
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            with pytest.raises(BundleInvalid, match="unreadable"):
                warm_start(model, path, wire_cache=False,
                           runtime_config=rc, strict=True)
            p, e = warm_start(model, path, wire_cache=False)
            inv = obs.get_registry().get("aot.invalidations")
            assert any(s.labels.get("reason") == "runtime_config"
                       for s in inv.samples())
            out = p.generate([[3, 4, 5]], max_new_tokens=2)
            assert len(out[0]) == 2
        finally:
            obs.enabled(was)

    def test_legacy_bundle_with_explicit_config_invalidates(
            self, tmp_path):
        """A bundle that recorded no runtime_config cannot vouch its
        artifacts match a requested config — serving old geometry
        while telemetry reports tuned knobs would be the silent split
        this field exists to prevent. It invalidates and rebuilds."""
        import paddle_tpu.observability as obs
        from paddle_tpu.inference.aot import warm_start
        from paddle_tpu.inference.aot.bundle import (BundleInvalid,
                                                     EngineBundle)
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        model = _tiny_model()
        path = str(tmp_path / "bundle")
        rc = RuntimeConfig(max_batch_size=2, page_size=8,
                           max_seq_len=64)
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            # legacy bundle: manifest without the field
            from paddle_tpu.inference.aot.bundle import model_fingerprint
            EngineBundle.create(path, model_fingerprint(model),
                                {"max_batch_size": 2, "page_size": 8,
                                 "max_seq_len": 64})
            with pytest.raises(BundleInvalid, match="predates"):
                warm_start(model, path, wire_cache=False,
                           runtime_config=rc, strict=True)
            p, e = warm_start(model, path, wire_cache=False,
                              runtime_config=rc)
            inv = obs.get_registry().get("aot.invalidations")
            assert any(s.labels.get("reason") == "runtime_config"
                       for s in inv.samples())
            assert e.bundle.manifest(refresh=True)[
                "runtime_config_hash"] == rc.config_hash()
            # legacy bundle with NO explicit config: loads unchanged
            EngineBundle.create(path, model_fingerprint(model),
                                {"max_batch_size": 2, "page_size": 8,
                                 "max_seq_len": 64})
            p2, _ = warm_start(model, path, wire_cache=False)
            assert p2.B == 2
        finally:
            obs.enabled(was)

    def test_baked_config_keeps_watchdog_flag_safety_net(self):
        """An explicit/baked config whose decode_watchdog_s is 0
        ("unset") must not disable the host's
        FLAGS_serve_decode_watchdog_s safety net; a nonzero config
        value wins over the flag; the ctor arg forces off."""
        import paddle_tpu as paddle
        from paddle_tpu.inference import ContinuousBatchingPredictor
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        model = _tiny_model()
        rc = RuntimeConfig(max_batch_size=2, page_size=8,
                           max_seq_len=64)
        paddle.set_flags({"serve_decode_watchdog_s": 7.5})
        try:
            cb = ContinuousBatchingPredictor(model, runtime_config=rc)
            cb.generate([[3, 4, 5]], max_new_tokens=1)
            assert cb._wd_cur == 7.5          # flag still arms it
            cb2 = ContinuousBatchingPredictor(
                model, runtime_config=rc.replace(decode_watchdog_s=3.0))
            cb2.generate([[3, 4, 5]], max_new_tokens=1)
            assert cb2._wd_cur == 3.0         # config value wins
            cb3 = ContinuousBatchingPredictor(
                model, runtime_config=rc, decode_watchdog_s=0)
            cb3.generate([[3, 4, 5]], max_new_tokens=1)
            assert cb3._wd_cur is None        # ctor 0 forces off
        finally:
            paddle.set_flags({"serve_decode_watchdog_s": 0.0})

    def test_config_drift_telemetry(self, tmp_path):
        """warm_start compares the serving config against the ambient
        FLAGS-derived config and counts each migrated-knob
        disagreement in aot.config_drift{key}."""
        import paddle_tpu as paddle
        import paddle_tpu.observability as obs
        from paddle_tpu.inference.aot import EngineBuilder, warm_start
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        model = _tiny_model()
        rc = RuntimeConfig(max_batch_size=2, page_size=8,
                           max_seq_len=64, grad_bucket_bytes=1 << 20,
                           quantized_grad_comm=True)
        path = str(tmp_path / "bundle")
        EngineBuilder(model, batch_sizes=[1], capture_forward=False,
                      runtime_config=rc).build(path, wire_cache=False)
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            warm_start(model, path, wire_cache=False)
            drift = obs.get_registry().get("aot.config_drift")
            keys = {s.labels.get("key") for s in drift.samples()}
            # flags hold the defaults; the bundle's config disagrees on
            # exactly these two migrated knobs (geometry fields are not
            # flag-expressible and must not report)
            assert keys == {"grad_bucket_bytes", "quantized_grad_comm"}
        finally:
            obs.enabled(was)

    def test_aot_report_verifies_config_hash(self, tmp_path):
        from paddle_tpu.inference.aot import EngineBuilder
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        model = _tiny_model()
        path = str(tmp_path / "bundle")
        EngineBuilder(model, batch_sizes=[1], capture_forward=False,
                      runtime_config=RuntimeConfig(
                          max_batch_size=2, page_size=8,
                          max_seq_len=64)).build(path, wire_cache=False)
        tool = os.path.join(REPO, "tools", "aot_report.py")
        r = subprocess.run([sys.executable, tool, path, "--verify"],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr + r.stdout
        assert "config" in r.stdout
        # tamper with the recorded config without re-hashing: --verify
        # must catch the manifest lying about its own config
        mp = os.path.join(path, "manifest.json")
        man = json.load(open(mp))
        man["runtime_config"]["max_queue"] = 999
        json.dump(man, open(mp, "w"))
        r2 = subprocess.run([sys.executable, tool, path, "--verify"],
                            capture_output=True, text=True)
        assert r2.returncode == 1
        assert "config hash mismatch" in r2.stderr


# ===========================================================================
# consumer plumbing
# ===========================================================================
class TestConsumerPlumbing:
    def test_predictor_bucket_table(self):
        from paddle_tpu.inference import ContinuousBatchingPredictor
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        model = _tiny_model()
        rc = RuntimeConfig(max_batch_size=2, page_size=8,
                           max_seq_len=64, prompt_buckets=(6, 12))
        cb = ContinuousBatchingPredictor(model, runtime_config=rc)
        assert cb._bucket_len(5) == 6
        assert cb._bucket_len(7) == 12
        assert cb._bucket_len(13) == 16   # pow2 fallback
        # ctor args still override the config
        cb2 = ContinuousBatchingPredictor(model, runtime_config=rc,
                                          max_batch_size=1)
        assert cb2.B == 1 and cb2.page == 8

    def test_grad_bucketer_default_flows_through_config(self):
        import paddle_tpu as paddle
        from paddle_tpu.distributed.collective import GradBucketer
        paddle.set_flags({"grad_bucket_bytes": 4096})
        try:
            b = GradBucketer([(1024,), (1024,)],
                             [np.float32, np.float32])
            assert b.bucket_bytes == 4096
            assert len(b.buckets) == 2   # 4KiB each: one bucket apiece
        finally:
            paddle.set_flags({"grad_bucket_bytes": 32 * 1024 * 1024})
        assert GradBucketer([(8,)], [np.float32]).bucket_bytes \
            == 32 << 20

    def test_dist_step_accepts_runtime_config(self):
        from paddle_tpu.distributed.fleet.dist_step import DistTrainStep
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        import inspect
        sig = inspect.signature(DistTrainStep.__init__)
        assert "runtime_config" in sig.parameters
        rc = RuntimeConfig(grad_bucket_bytes=1 << 20,
                           quantized_grad_comm=True)
        assert rc.grad_bucket_bytes == 1 << 20


# ===========================================================================
# the closed loop, once: pressured run -> replay -> tuned config -> served
# ===========================================================================
class TestClosedLoop:
    def test_pressured_run_to_tuned_config_relieves_evictions(
            self, tmp_path):
        """A pool that holds one request at a time evicts the prefix
        cache's pages on every admission; replaying that run's own
        telemetry proposes a larger pool from the page-pressure
        series, and a predictor built from the tuned config serves the
        same prompts to the same greedy tokens with fewer evictions."""
        import paddle_tpu.observability as obs
        from paddle_tpu.observability import runtime as obs_rt
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        from paddle_tpu.inference import ContinuousBatchingPredictor
        at = _tool("autotune")
        model = _tiny_model()
        rng = np.random.RandomState(0)
        # two sessions behind one shared page, requests alternating:
        # 24 + 16 tokens are the 5 pages the mis-sized pool has
        shared = rng.randint(2, 256, (8,)).tolist()
        sessions = [shared + rng.randint(2, 256, (16,)).tolist()
                    for _ in range(2)]
        prompts = [list(sessions[i % 2]) for i in range(8)]
        rc = RuntimeConfig(max_batch_size=2, page_size=8,
                           max_seq_len=96, num_pages=5)

        def evictions():
            m = obs.get_registry().get("serving.page_evictions")
            return sum(s.value for s in m.samples()) if m else 0

        def serve(config, path=None):
            obs.get_registry().reset()
            obs.configure(path)
            try:
                out = ContinuousBatchingPredictor(
                    model, runtime_config=config).generate(
                        prompts, max_new_tokens=16)
                obs_rt.maybe_export()    # the registry's snapshot
            finally:
                obs.configure(None)
            return out, evictions()

        was = obs.enabled()
        obs.enabled(True)
        try:
            path = str(tmp_path / "pressured.jsonl")
            out_default, ev_default = serve(rc, path)
            assert ev_default > 0
            report = at.analyze([path], base=rc.to_dict(),
                                slo_ttft_s=30.0)
            pool = next(p for p in report["proposals"]
                        if p["field"] == "num_pages")
            assert pool["evidence"]["series"] == "serving.page_utilization"
            tuned = RuntimeConfig.from_dict(report["runtime_config"])
            assert tuned.num_pages > rc.num_pages
            out_tuned, ev_tuned = serve(tuned)
        finally:
            obs.enabled(was)
        assert out_tuned == out_default
        assert ev_tuned < ev_default

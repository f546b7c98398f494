"""End-to-end request tracing (PR 20): TraceContext propagation across
the router → serve-loop → KV-handoff boundaries, critical-path stage
decomposition, tail exemplars on latency histograms + SLO breach
evidence, torn-free concurrent JSONL sink writes, and the
trace_report cross-role waterfall.

The clock-free synthetic paths (handcrafted span dicts, no model) run
beside two live pools: a unified one and a two-role one, whose every
request must be one connected trace.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.observability import critpath
from paddle_tpu.observability import metrics as obsm
from paddle_tpu.observability import runtime as obs_rt
from paddle_tpu.observability import tracing as tr
from paddle_tpu.observability.slo import SLOEngine, SLOSpec
from paddle_tpu.serving import Router


@pytest.fixture(autouse=True)
def _clean():
    obs.configure(None)
    obs.enabled(True)
    tr.flight_recorder().clear()
    yield
    obs.configure(None)
    obs.enabled(True)
    tr.flight_recorder().clear()


def _spans(path):
    out = []
    for line in open(path):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if rec.get("kind") == "span":
            out.append(rec)
    return out


def _tools(name):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import importlib
        return importlib.import_module(name)
    finally:
        sys.path.pop(0)


class Clock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# ------------------------------------------------------- TraceContext --
class TestTraceContext:
    def test_round_trips_the_wire_form(self):
        sp = tr.start_span("router.request", parent=None,
                           request_id="r1")
        ctx = sp.context(request_id="r1", tier="hi")
        assert ctx.trace_id == sp.trace_id
        assert ctx.span_id == sp.span_id
        wire = json.loads(json.dumps(ctx.to_dict()))   # cross-process
        back = obs.TraceContext.from_dict(wire)
        assert back == ctx
        assert back.baggage == {"request_id": "r1", "tier": "hi"}
        sp.end()

    def test_from_dict_none_tolerant(self):
        assert obs.TraceContext.from_dict(None) is None

    def test_child_adopts_carried_context(self):
        root = tr.start_span("router.request", parent=None)
        ctx = obs.TraceContext.from_dict(root.context().to_dict())
        child = tr.start_span("serve.request", parent=ctx)
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
        child.end()
        root.end()

    def test_disabled_mode_mints_none(self):
        with obs.scoped(False):
            sp = tr.start_span("x", parent=None)
            assert sp.context() is None


# ------------------------------------------------ critical-path stages --
def _ev(ts, name, **attrs):
    return dict({"ts": ts, "name": name}, **attrs)


def _mk(name, trace, span, parent, start, dur, events=(), labels=None,
        status="ok"):
    return {"kind": "span", "name": name, "trace": trace, "span": span,
            "parent": parent, "start": start, "dur": dur,
            "status": status, "events": list(events),
            "labels": labels or {}}


def _disagg_trace(t0=100.0):
    """One handcrafted disaggregated request: router root + a
    prefill-role and a decode-role serve.request, milestones at known
    offsets so every stage value is asserted exactly."""
    root = _mk(
        "router.request", "t1", "s0", None, t0, 1.0,
        labels={"request_id": "rr1"},
        events=[_ev(t0 + .01, "routed", replica="p0"),
                _ev(t0 + .40, "first_token"),
                _ev(t0 + .45, "handoff"),
                _ev(t0 + .50, "handoff_import_start"),
                _ev(t0 + .60, "handoff_imported"),
                _ev(t0 + 1.0, "finish")])
    pre = _mk(
        "serve.request", "t1", "s1", "s0", t0 + .02, .43,
        labels={"request_id": "req1", "replica": "p0"},
        events=[_ev(t0 + .03, "queued"), _ev(t0 + .05, "prefill"),
                _ev(t0 + .40, "first_token")])
    dec = _mk(
        "serve.request", "t1", "s2", "s0", t0 + .60, .38,
        labels={"request_id": "req2", "replica": "d0"},
        events=[_ev(t0 + .62, "admitted"), _ev(t0 + .70, "token"),
                _ev(t0 + .95, "finish")])
    return [root, pre, dec]


class TestCritpath:
    def test_disagg_stages_telescope_to_ttft_and_e2e(self):
        d = critpath.stage_decomposition(_disagg_trace(),
                                         trace_id="t1")
        assert [s for s, _ in d["stages"]] == list(critpath.STAGES)
        total = sum(v for _, v in d["stages"])
        assert total == pytest.approx(d["e2e"], abs=1e-9)
        assert d["e2e"] == pytest.approx(1.0, abs=1e-9)
        assert d["ttft"] == pytest.approx(0.40, abs=1e-9)
        prefix = 0.0
        for s, v in d["stages"]:
            prefix += v
            if s == "prefill":
                break
        assert prefix == pytest.approx(d["ttft"], abs=1e-12)
        assert d["aux"]["orphans"] == 0
        assert d["aux"]["status"] == "ok"

    def test_unified_trace_skips_handoff_stages(self):
        spans = [s for s in _disagg_trace() if s["span"] != "s2"]
        spans[0]["events"] = [e for e in spans[0]["events"]
                              if not e["name"].startswith("handoff")]
        d = critpath.stage_decomposition(spans, trace_id="t1")
        names = [s for s, _ in d["stages"]]
        assert "handoff_export" not in names
        assert "decode_queue" not in names
        assert sum(v for _, v in d["stages"]) \
            == pytest.approx(d["e2e"], abs=1e-9)

    def test_orphans_are_counted_not_crashed(self):
        spans = _disagg_trace()
        spans[2]["parent"] = "missing"
        tree = critpath.trace_tree(spans, trace_id="t1")
        assert [s["span"] for s in tree["orphans"]] == ["s2"]
        d = critpath.stage_decomposition(spans, trace_id="t1")
        assert d["aux"]["orphans"] == 1


# ------------------------------------------------------ tail exemplars --
class TestTailExemplars:
    def test_histogram_keeps_topk_descending(self):
        h = obsm.MetricRegistry().histogram("x.seconds")
        for i in range(10):
            h.observe(i / 10.0, exemplar=f"t{i}")
        ex = h.exemplars()
        assert [t for _, t in ex] == ["t9", "t8", "t7", "t6"]
        assert [v for v, _ in ex] == pytest.approx([.9, .8, .7, .6])

    def test_labeled_series_and_jsonl_extra(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        obs.configure(path)
        h = obs.get_registry().histogram("exem.test.seconds")
        h.observe(0.5, exemplar="big", stage="decode")
        h.observe(0.1, exemplar="small", stage="queue")
        assert h.exemplars(stage="decode") == [(0.5, "big")]
        obs_rt.maybe_export()
        obs.configure(None)
        recs = [json.loads(ln) for ln in open(path)]
        hl = [r for r in recs if r.get("kind") == "histogram"
              and r.get("name") == "exem.test.seconds"]
        assert hl, "histogram lines missing from the sink"
        got = {e["trace"]: e["value"] for r in hl
               for e in r.get("exemplars", ())}
        assert got == {"big": 0.5, "small": 0.1}

    def test_slo_breach_attaches_exemplars(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        obs.configure(path)
        reg = obsm.MetricRegistry()
        h = reg.histogram("serving.router.ttft_seconds",
                          buckets=(0.1, 0.25, 1.0))
        clk = Clock(1000.0)
        eng = SLOEngine(
            [SLOSpec("ttft", "serving.router.ttft_seconds",
                     target=0.25, objective=0.9)],
            registry=reg, fast_window_s=60.0, slow_window_s=600.0,
            now_fn=clk)
        eng.evaluate()
        clk.advance(1.0)
        for i in range(8):
            h.observe(0.05, exemplar=f"fast{i}")
        h.observe(0.9, exemplar="slow0")
        h.observe(0.8, exemplar="slow1")
        st = eng.evaluate()["ttft"]
        assert st["new_breach"]
        obs.configure(None)
        recs = [json.loads(ln) for ln in open(path)]
        br = [r for r in recs if r.get("kind") == "slo_breach"]
        assert len(br) == 1
        traces = {e["trace"] for e in br[0]["exemplars"]}
        assert {"slow0", "slow1"} <= traces


# --------------------------------------- concurrent JSONL sink writes --
class TestConcurrentSinkWrites:
    def test_multi_role_threads_never_tear_lines(self, tmp_path):
        """Multiple roles/threads share one process sink: every line
        must parse as exactly one JSON record (a torn or interleaved
        write fails json.loads) and every span line must round-trip
        through the trace_report parser with its events intact."""
        path = str(tmp_path / "t.jsonl")
        obs.configure(path)
        n_threads, n_spans = 6, 40
        errs = []

        def writer(role):
            try:
                for i in range(n_spans):
                    sp = tr.start_span(
                        "serve.request", parent=None,
                        request_id=f"{role}-{i}", replica=role)
                    sp.event("token", i=i, payload="x" * 64)
                    sp.event("finish")
                    sp.end(status="ok")
                    if i % 7 == 0:
                        obs_rt.export_record(
                            {"kind": "marker", "role": role, "i": i})
            except Exception as e:                # pragma: no cover
                errs.append(e)

        ths = [threading.Thread(target=writer, args=(f"r{k}",))
               for k in range(n_threads)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        obs.configure(None)
        assert not errs
        recs = [json.loads(ln)                    # raises on a torn line
                for ln in open(path).read().splitlines()]
        spans = [r for r in recs if r.get("kind") == "span"]
        assert len(spans) == n_threads * n_spans
        loaded = _tools("trace_report").load_spans(path)
        assert len(loaded) == len(spans)
        ids = {s["labels"]["request_id"] for s in loaded}
        assert len(ids) == n_threads * n_spans
        assert all(len(s["events"]) == 2 for s in loaded)


# ------------------------------------------------- waterfall rendering --
class TestWaterfallReport:
    def test_synthetic_disagg_waterfall_renders(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        with open(path, "w") as f:
            for s in _disagg_trace():
                f.write(json.dumps(s) + "\n")
        trace_report = _tools("trace_report")
        loaded = trace_report.load_spans(path)
        out = trace_report.render(loaded, request_id="t1")
        assert "critical path" in out
        for st in ("admission", "handoff_transfer", "decode", "flush"):
            assert st in out
        assert "TTFT" in out and "E2E" in out
        assert "ORPHAN" not in out
        # the router-side request-id label resolves to the same trace
        out2 = trace_report.render(loaded, request_id="rr1")
        assert "critical path" in out2

    def test_waterfall_marks_orphans(self, tmp_path):
        spans = _disagg_trace()
        spans[2]["parent"] = "deadbeef"
        path = str(tmp_path / "t.jsonl")
        with open(path, "w") as f:
            for s in spans:
                f.write(json.dumps(s) + "\n")
        trace_report = _tools("trace_report")
        out = trace_report.render(trace_report.load_spans(path),
                                  request_id="t1")
        assert "ORPHAN" in out


# --------------------------------------------- live router propagation --
def _serve_model():
    paddle.seed(0)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny())


def _prompts(n, lens=(9, 12, 7, 15), seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, 256, (lens[i % len(lens)],)).tolist()
            for i in range(n)]


def _connected(spans, root):
    """All spans of root's trace; asserts every parent resolves."""
    tr_spans = [s for s in spans if s["trace"] == root["trace"]]
    ids = {s["span"] for s in tr_spans}
    orphans = [s["name"] for s in tr_spans
               if s["parent"] and s["parent"] not in ids]
    assert not orphans, f"orphans in {root['trace']}: {orphans}"
    return tr_spans


class TestRouterPropagation:
    def test_unified_pool_single_trace_and_stage_sum(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        obs.get_registry().reset()
        obs.configure(path)
        with Router([_serve_model()], seed=0, max_batch_size=2,
                    page_size=8, max_seq_len=64) as router:
            hs = [router.submit(p, max_new_tokens=4)
                  for p in _prompts(2)]
            for h in hs:
                assert h.result(timeout=120)
        obs.configure(None)
        spans = _spans(path)
        roots = [s for s in spans if s["name"] == "router.request"]
        assert len(roots) == 2
        assert len({r["trace"] for r in roots}) == 2
        for r in roots:
            tr_spans = _connected(spans, r)
            sreqs = [s for s in tr_spans
                     if s["name"] == "serve.request"]
            assert len(sreqs) == 1        # adopted, not re-rooted
            assert sreqs[0]["parent"] == r["span"]
            d = critpath.stage_decomposition(tr_spans,
                                             trace_id=r["trace"])
            assert sum(v for _, v in d["stages"]) \
                == pytest.approx(r["dur"], rel=0.05, abs=1e-3)
            assert d["aux"]["orphans"] == 0
        m = obs.get_registry().get("serve.request.stage.seconds")
        assert m is not None
        exes = {t for _, t in m.exemplars()}
        assert exes and exes <= {r["trace"] for r in roots}

    def test_page_span_round_trips_without_a_warning(self):
        """The handoff endpoints under their one name (the
        `*_request_span` aliases are gone)."""
        import warnings
        from paddle_tpu.inference import ContinuousBatchingPredictor
        cb = ContinuousBatchingPredictor(
            _serve_model(), max_batch_size=2, page_size=8,
            max_seq_len=48)
        prompt = _prompts(1)[0]
        cb.generate([prompt], max_new_tokens=2)
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            span = cb.export_page_span(prompt)
            assert span is not None
            stats = cb.import_page_span(span)
        assert stats is not None
        assert not hasattr(cb, "export_request_span")
        assert not hasattr(cb, "import_request_span")


class TestDisaggWaterfall:
    def test_two_role_pool_one_trace_with_handoff_stages(
            self, tmp_path):
        """A two-role pool end to end: every completed request is ONE
        trace (its `router.request` root the only parent-less span,
        every parent id resolving inside it) carrying both role spans;
        the decomposition includes the handoff stages; the TTFT
        histogram's exemplars resolve to exported traces; and the
        waterfall renders stdlib-only, naming both replicas."""
        path = str(tmp_path / "t.jsonl")
        obs.get_registry().reset()
        obs.configure(path)
        model = _serve_model()
        with Router([model, model], roles=["prefill", "decode"],
                    seed=0, max_batch_size=2, page_size=8,
                    max_seq_len=64) as router:
            hs = [router.submit(p, max_new_tokens=4)
                  for p in _prompts(3)]
            for h in hs:
                h.result(timeout=120)
            assert all(h.status == "ok" for h in hs)
        obs_rt.maybe_export()
        obs.configure(None)
        spans = _spans(path)
        roots = [s for s in spans if s["name"] == "router.request"]
        assert len(roots) == 3
        trace_report = _tools("trace_report")
        loaded = trace_report.load_spans(path)
        for r in roots:
            tr_spans = _connected(spans, r)
            # a boundary that re-minted instead of adopting would
            # leave a second parent-less span in the trace
            assert [s["name"] for s in tr_spans
                    if not s["parent"]] == ["router.request"]
            sreqs = [s for s in tr_spans
                     if s["name"] == "serve.request"]
            assert len(sreqs) == 2        # prefill-role + decode-role
            reps = {s["labels"].get("replica") for s in sreqs}
            assert len(reps) == 2
            d = critpath.stage_decomposition(tr_spans,
                                             trace_id=r["trace"])
            names = {s for s, _ in d["stages"]}
            assert {"handoff_export", "handoff_transfer",
                    "handoff_import"} <= names
            assert sum(v for _, v in d["stages"]) \
                == pytest.approx(r["dur"], rel=0.05, abs=1e-3)
            out = trace_report.render(loaded, request_id=r["trace"])
            assert "critical path" in out
            for rep in reps:
                assert rep in out
        # tail exemplars on the sink's histogram lines name real traces
        traces = {r["trace"] for r in roots}
        exemplars = {}
        for line in open(path):
            rec = json.loads(line)
            if rec.get("kind") == "histogram" and rec.get("exemplars"):
                exemplars.setdefault(rec["name"], set()).update(
                    e["trace"] for e in rec["exemplars"])
        assert exemplars["serving.router.ttft_seconds"] <= traces
        assert all(v <= traces for v in exemplars.values()), exemplars
        # the operator's entry point: the CLI, isolated mode
        cli = subprocess.run(
            [sys.executable, "-I",
             os.path.join(os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__))), "tools",
                 "trace_report.py"),
             path, "--request", roots[0]["trace"]],
            capture_output=True, text=True, timeout=120)
        assert cli.returncode == 0, cli.stderr[-2000:]
        assert roots[0]["trace"] in cli.stdout
        assert "critical path" in cli.stdout

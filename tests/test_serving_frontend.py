"""Multi-tenant serving front end (PR 6): weighted-fair scheduler,
prefix-affinity router, token streaming, autoscale signals.

Invariant coverage (ISSUE 6 satellites):
- DRR share accounting under a sustained low-tier flood — the high
  tier's admission share and head-of-queue wait stay bounded;
- priority-aware shedding never sheds a tier within its weight share,
  and deadline-EXPIRED queued entries are evicted before any shed
  decision (expired low-tier backlog must not cause high-tier sheds);
- affinity routing lands a session on the replica already holding its
  cached pages (asserted via serving.prefix_cache_hits per replica);
- a failed replica's requests are re-admitted elsewhere EXACTLY once,
  and consecutive failures eject the replica;
- generate_stream yields the first token before the full sequence's
  decode completes (span timestamps) and cancellation mid-stream
  returns the request's KV pages to the pool.
"""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.serving import (
    FifoQueue, Router, ServeRequest, WeightedFairScheduler,
)


@pytest.fixture(autouse=True)
def _clean():
    obs.configure(None)
    obs.enabled(True)
    yield
    obs.configure(None)
    obs.enabled(True)
    paddle.set_flags({"fault_injection": ""})


def _serve_model():
    paddle.seed(0)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny())


def _prompts(n, lens=(5, 9, 12, 7), seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, 256, (lens[i % len(lens)],)).tolist()
            for i in range(n)]


def _counter_total(name, **labels):
    """Sum of every series whose labels CONTAIN `labels` (a counter
    like serving.prefix_cache_hits fans out over kind+replica)."""
    m = obs.get_registry().get(name)
    if m is None:
        return 0.0
    return sum(s.value for s in m.samples()
               if all(s.labels.get(k) == v for k, v in labels.items()))


# ---------------------------------------------------------------------------
# weighted-fair scheduler (pure queue discipline, no model)
# ---------------------------------------------------------------------------
class TestWeightedFairScheduler:
    def test_fifo_discipline_is_fifo(self):
        q = FifoQueue()
        for r in range(5):
            q.push(r)
        assert len(q) == 5
        assert q.pop() == 0
        q.push_front(0)
        assert [q.pop() for _ in range(5)] == [0, 1, 2, 3, 4]
        assert q.pop() is None

    def test_drr_share_under_sustained_low_tier_flood(self):
        """The fairness invariant: with weights 8:1 and equal request
        cost, a huge backlog of low-tier work must not push the high
        tier below ~8/9 of admissions in any window, and the FIRST
        high-tier admission happens within one quantum round of its
        arrival (bounded admission wait, not starvation)."""
        q = WeightedFairScheduler({"hi": 8, "lo": 1}, quantum=16.0)
        for i in range(500):
            q.push(("lo", i), tier="lo", cost=8.0)
        for i in range(40):
            q.push(("hi", i), tier="hi", cost=8.0)
        order = []
        while len(q):
            rid = q.pop()
            q.consume(rid)
            order.append(rid[0])
        first_hi = order.index("hi")
        # one lo visit admits at most quantum/cost = 2 before the
        # pointer reaches hi's tier
        assert first_hi <= 2
        # within the window where both tiers are backlogged, hi's
        # admission share tracks 8/9 (hi drains after ~45 pops)
        both = order[:45]
        hi_share = both.count("hi") / len(both)
        assert hi_share >= 0.80
        # nothing lost: all 540 admitted
        assert len(order) == 540

    def test_drr_work_share_with_uneven_costs(self):
        """Fairness is in WORK (cost), not request count: cheap lo
        requests cannot out-admit hi by being numerous."""
        q = WeightedFairScheduler({"hi": 4, "lo": 1}, quantum=8.0)
        for i in range(400):
            q.push(("lo", i), tier="lo", cost=1.0)
        for i in range(50):
            q.push(("hi", i), tier="hi", cost=8.0)
        cost_admitted = {"hi": 0.0, "lo": 0.0}
        seen_hi = 0
        while seen_hi < 50:
            rid = q.pop()
            q.consume(rid)
            cost_admitted[rid[0]] += 8.0 if rid[0] == "hi" else 1.0
            seen_hi += rid[0] == "hi"
        # while hi was backlogged, lo's work share is ~1/5
        total = cost_admitted["hi"] + cost_admitted["lo"]
        assert cost_admitted["lo"] / total <= 0.30

    def test_push_front_refunds_deficit(self):
        """A popped-but-unadmissible request (no pages yet) requeued at
        its tier's head must not burn the tier's share: the next pop
        returns it again without extra rounds."""
        q = WeightedFairScheduler({"a": 1}, quantum=4.0)
        q.push("x", tier="a", cost=4.0)
        q.push("y", tier="a", cost=4.0)
        assert q.pop() == "x"
        q.push_front("x")
        assert q.pop() == "x"
        q.consume("x")
        assert q.pop() == "y"

    def test_remove_and_ids(self):
        q = WeightedFairScheduler({"a": 1, "b": 2})
        q.push(1, tier="a")
        q.push(2, tier="b")
        q.push(3, tier="a")
        assert set(q.ids()) == {1, 2, 3}
        assert q.remove(2)
        assert not q.remove(2)
        assert q.tier_of(1) == "a"
        assert len(q) == 2
        assert q.depths() == {"a": 2}

    def test_shed_picks_lowest_tier_over_its_share(self):
        """Priority-aware shedding: with max_queue=8 and weights 3:1,
        hi's share is 6 and lo's is 2. lo at depth 6 is over its share
        → lo sheds; hi at depth 4 (within 6) is NEVER the victim."""
        q = WeightedFairScheduler({"hi": 3, "lo": 1})
        for i in range(4):
            q.push(("hi", i), tier="hi")
        for i in range(6):
            q.push(("lo", i), tier="lo")
        shed = [q.pick_shed("newest", max_queue=8) for _ in range(2)]
        assert all(rid[0] == "lo" for rid in shed)
        # newest within the tier: lo 5 then lo 4
        assert [rid[1] for rid in shed] == [5, 4]

    def test_shed_within_share_tier_survives_flood(self):
        """Even when EVERY shed comes from a single flooding tier, the
        within-share tier is untouched down to the bound."""
        q = WeightedFairScheduler({"hi": 8, "lo": 1})
        for i in range(3):
            q.push(("hi", i), tier="hi")
        for i in range(50):
            q.push(("lo", i), tier="lo")
        while len(q) > 10:
            victim = q.pick_shed("newest", max_queue=10)
            assert victim[0] == "lo"
        assert q.depths()["hi"] == 3

    def test_shed_declines_when_no_tier_over_share(self):
        """Apparent overflow with every tier inside its share (the
        serve_flood fault site inflates depth) must not shed anyone:
        pick_shed declines with None instead of breaking the
        never-shed-within-share invariant."""
        q = WeightedFairScheduler({"hi": 3, "lo": 1})
        q.push(("hi", 0), tier="hi")
        q.push(("lo", 0), tier="lo")
        assert q.pick_shed("newest", max_queue=8) is None
        assert len(q) == 2


# ---------------------------------------------------------------------------
# predictor-level: tiers, expired-before-shed, streaming, cancellation
# ---------------------------------------------------------------------------
class TestPredictorTiers:
    def test_wfq_generate_with_tier_metrics(self):
        from paddle_tpu.inference import ContinuousBatchingPredictor
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=2,
                                         page_size=8, max_seq_len=64)
        n = 6
        tiers = ["interactive" if i % 2 == 0 else "batch"
                 for i in range(n)]
        before = _counter_total("serving.tier.admissions")
        outs = cb.generate(_prompts(n), max_new_tokens=3, tiers=tiers,
                           tier_weights={"interactive": 8, "batch": 1})
        assert all(s == "ok" for s in cb.last_status)
        assert all(len(o) == 3 for o in outs)
        assert _counter_total("serving.tier.admissions") == before + n
        assert _counter_total("serving.tier.admissions",
                              tier="interactive") >= 3

    def test_expired_queued_evicted_before_any_shed(self):
        """REGRESSION (ISSUE 6 satellite): a backlog of deadline-dead
        low-tier entries must be evicted BEFORE the shed decision —
        live high-tier requests must never shed on their account."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=1,
                                         page_size=8, max_seq_len=64,
                                         max_queue=3)
        # 4 lo entries already expired on arrival + 3 live hi = 7
        # requests into a queue bounded at 3. Expiry eviction first
        # leaves exactly the 3 live hi → ZERO sheds.
        prompts = _prompts(7)
        tiers = ["batch"] * 4 + ["interactive"] * 3
        deadlines = [0.0] * 4 + [None] * 3
        outs = cb.generate(prompts, max_new_tokens=2, tiers=tiers,
                           deadline_s=deadlines,
                           tier_weights={"interactive": 8, "batch": 1})
        assert cb.last_status[:4] == ["deadline"] * 4
        assert cb.last_status[4:] == ["ok"] * 3
        assert cb.stats["shed_requests"] == 0
        assert all(outs[r] == [] for r in range(4))
        assert all(len(outs[r]) == 2 for r in range(4, 7))

    def test_priority_aware_shed_protects_high_tier(self):
        """Over capacity with live entries, the lowest tier sheds
        first; interactive requests within their weight share all
        run (the PR-4 global newest|oldest pick would have shed
        the late-arriving interactive ones)."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=1,
                                         page_size=8, max_seq_len=64,
                                         max_queue=4)
        # 8 batch then 3 interactive (newest): global-newest would
        # shed every interactive request
        prompts = _prompts(11)
        tiers = ["batch"] * 8 + ["interactive"] * 3
        cb.generate(prompts, max_new_tokens=2, tiers=tiers,
                    tier_weights={"interactive": 8, "batch": 1})
        assert cb.last_status[8:] == ["ok"] * 3
        assert cb.last_status[:8].count("shed") == 7
        assert _counter_total("serving.tier.shed_requests",
                              tier="batch") >= 7


class TestTokenStreaming:
    def test_stream_yields_tokens_incrementally(self):
        """generate_stream yields each request's tokens as decode ticks
        complete — kind "token" events with growing index, then one
        "end" carrying the final status; results/last_status fill in
        place and match the blocking API."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _serve_model()
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, max_seq_len=64)
        prompts = _prompts(3)
        ref = ContinuousBatchingPredictor(
            model, max_batch_size=2, page_size=8,
            max_seq_len=64).generate(prompts, max_new_tokens=4)
        st = cb.generate_stream(prompts, max_new_tokens=4)
        seen = {r: [] for r in range(3)}
        ends = {}
        for ev in st:
            if ev.kind == "token":
                seen[ev.request].append(ev.token)
                assert ev.index == len(seen[ev.request])
            else:
                ends[ev.request] = ev.status
        assert st.results == ref
        assert [seen[r] for r in range(3)] == ref
        assert ends == {0: "ok", 1: "ok", 2: "ok"}
        assert st.status == ["ok"] * 3

    def test_first_token_before_full_decode_span_ts(self):
        """ACCEPTANCE: the stream yields a request's first token
        STRICTLY before decode of its full sequence completes —
        asserted via the request span's event timestamps (first_token
        ts < last token-tick ts) AND via the consumer's own clock
        (the first token was in hand before the end event's span
        timestamp)."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        from paddle_tpu.observability import tracing as tr
        tr.flight_recorder().clear()
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=1,
                                         page_size=8, max_seq_len=64)
        recv_ts = {}
        st = cb.generate_stream(_prompts(1), max_new_tokens=8)
        for ev in st:
            if ev.kind == "token" and ev.index == 1:
                recv_ts["first"] = time.time()
            if ev.kind == "end":
                recv_ts["end"] = time.time()
        (res,) = st.results
        assert len(res) == 8
        spans = {s["name"]: s for s in tr.flight_recorder().spans()}
        req = spans["serve.request"]
        evs = {e["name"]: e["ts"] for e in req["events"]}
        toks = [e["ts"] for e in req["events"] if e["name"] == "token"]
        span_end = req["start"] + req["dur"]
        assert evs["first_token"] < toks[-1]      # span-ts ordering
        assert recv_ts["first"] < span_end        # consumer had it live
        # the stream's per-event ts IS the span event timestamp
        assert recv_ts["first"] < recv_ts["end"]
        tr.flight_recorder().clear()

    def test_cancel_mid_stream_frees_pages(self):
        """ACCEPTANCE: cancelling a request mid-stream evicts it at the
        next loop tick — partial tokens kept, last_status "cancelled",
        and its KV pages return to the pool (refcounts to baseline)."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=2,
                                         page_size=8, max_seq_len=64,
                                         enable_prefix_cache=False)
        assert cb.pool.free_count == cb.capacity
        before = _counter_total("serving.cancelled_requests")
        st = cb.generate_stream(_prompts(2), max_new_tokens=12)
        for ev in st:
            if ev.kind == "token" and ev.request == 0 and ev.index == 2:
                st.cancel(0)
        assert st.status[0] == "cancelled"
        assert st.status[1] == "ok"
        assert 2 <= len(st.results[0]) < 12    # partial, stopped early
        assert len(st.results[1]) == 12
        assert cb.stats["cancelled_requests"] == 1
        assert _counter_total("serving.cancelled_requests") == before + 1
        # no prefix cache → every page must be back
        assert cb.pool.free_count == cb.capacity

    def test_abandoning_stream_cancels_everything(self):
        """A consumer that stops iterating cannot leak pages or slots:
        closing the stream (context-manager exit) cancels every pending
        request synchronously and the pool returns to baseline."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=2,
                                         page_size=8, max_seq_len=64,
                                         enable_prefix_cache=False)
        with cb.generate_stream(_prompts(3), max_new_tokens=16) as st:
            for ev in st:
                if ev.kind == "token" and ev.index == 1:
                    break           # walk away mid-decode
        assert cb.pool.free_count == cb.capacity
        assert all(s in ("cancelled",) for s in st.status)
        assert cb.stats["cancelled_requests"] >= 1

    def test_queued_cancellation_without_slot(self):
        """Cancelling a request that never reached a slot removes it
        from the queue (status "cancelled", no tokens)."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        cb = ContinuousBatchingPredictor(_serve_model(), max_batch_size=1,
                                         page_size=8, max_seq_len=64)
        st = cb.generate_stream(_prompts(3), max_new_tokens=6)
        st.cancel(2)                 # B=1: request 2 is still queued
        st.drain()
        assert st.status[2] == "cancelled"
        assert st.results[2] == []
        assert len(st.results[0]) == 6


# ---------------------------------------------------------------------------
# router: affinity, failover, ejection, streaming, autoscale
# ---------------------------------------------------------------------------
class TestRouter:
    def test_affinity_routes_session_to_cached_replica(self):
        """ISSUE 6 satellite: requests sharing a page-aligned prefix
        all land on the SAME replica, and that replica's
        serving.prefix_cache_hits counter (replica label) carries every
        hit while the other replica has none."""
        model = _serve_model()
        rng = np.random.RandomState(3)
        sess = rng.randint(2, 256, (16,)).tolist()     # 2 full pages
        reqs = [sess + rng.randint(2, 256, (3,)).tolist()
                for _ in range(4)]
        other = rng.randint(2, 256, (16,)).tolist()
        with Router([model, model], policy="affinity", seed=0,
                    max_batch_size=2, page_size=8,
                    max_seq_len=64) as router:
            h0 = router.submit(reqs[0], max_new_tokens=2)
            h0.result(timeout=120)
            # force the pool out of the all-idle tie so the session
            # replica is a real affinity choice, not a least-loaded tie
            router.submit(other, max_new_tokens=2).result(timeout=120)
            hs = [router.submit(p, max_new_tokens=2) for p in reqs[1:]]
            for h in hs:
                h.result(timeout=120)
            home = h0.replica
            assert all(h.replica == home for h in hs)
            assert all(h.status == "ok" for h in hs)
            stats = router.stats()
            hits_home = stats[home]["prefix_hits"] \
                + stats[home]["prefix_partial_hits"]
            assert hits_home >= 3
            away = next(n for n in stats if n != home)
            assert stats[away]["prefix_hits"] == 0
        assert _counter_total("serving.prefix_cache_hits",
                              replica=home) >= 1

    def test_random_policy_spreads_sessions(self):
        """Control arm: the same session trace under policy="random"
        does NOT stick to one replica (seeded to a spread outcome)."""
        model = _serve_model()
        rng = np.random.RandomState(3)
        sess = rng.randint(2, 256, (16,)).tolist()
        with Router([model, model], policy="random", seed=1,
                    max_batch_size=2, page_size=8,
                    max_seq_len=64) as router:
            hs = []
            for _ in range(6):
                h = router.submit(
                    sess + rng.randint(2, 256, (3,)).tolist(),
                    max_new_tokens=2)
                h.result(timeout=120)
                hs.append(h)
            assert len({h.replica for h in hs}) == 2

    def test_replica_failure_readmits_exactly_once(self):
        """A replica whose serve loop dies re-admits its in-flight
        requests to another replica EXACTLY once each; they complete
        there, the failure is counted, and the sick replica ejects
        after `eject_after` consecutive failures."""
        model = _serve_model()
        before_re = _counter_total("serving.router.readmissions")
        before_ej = _counter_total("serving.router.ejections")
        with Router([model, model], policy="least_loaded", seed=0,
                    eject_after=1, max_batch_size=2, page_size=8,
                    max_seq_len=64) as router:
            sick = router.replicas[0]

            def exploding_prefill(bucket, group):
                raise RuntimeError("boom")

            # the serve loop is ALREADY running and polling intake —
            # break it from inside (first admission with a cache miss
            # dies), not by swapping serve_stream after the fact
            sick.predictor._batch_prefill = exploding_prefill
            hs = [router.submit(p, max_new_tokens=2)
                  for p in _prompts(4, seed=5)]
            outs = [h.result(timeout=120) for h in hs]
            assert all(h.status == "ok" for h in hs)
            assert all(len(o) == 2 for o in outs)
            # every request that hit the sick replica bounced once
            bounced = [h for h in hs if h.attempts == 1]
            assert bounced, "expected at least one readmission"
            assert all(h.attempts <= 1 for h in hs)
            assert all(h.replica == router.replicas[1].name
                       for h in bounced)
            assert sick.ejected
            assert router.healthy() == [router.replicas[1]]
            # the crashed loop's terminal statuses on the sick replica
            # say "error" — a crash must not masquerade as consumer
            # cancellation in telemetry
            assert "error" in sick.predictor.last_status
            assert "cancelled" not in sick.predictor.last_status
            assert sick.predictor.stats["cancelled_requests"] == 0
        assert _counter_total("serving.router.readmissions") \
            >= before_re + len(bounced)
        assert _counter_total("serving.router.ejections") == before_ej + 1

    def test_revive_after_eject(self):
        """An ejected replica rejoins the pool with a fresh predictor
        and serves again."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _serve_model()
        with Router([model, model], policy="least_loaded", seed=0,
                    eject_after=1, max_batch_size=2, page_size=8,
                    max_seq_len=64) as router:
            sick = router.replicas[0]
            sick.predictor._batch_prefill = \
                lambda bucket, group: (_ for _ in ()).throw(
                    RuntimeError("boom"))
            router.submit(_prompts(1)[0], max_new_tokens=2).result(
                timeout=120)
            # wait for the failure/ejection to land (worker thread)
            for _ in range(200):
                if sick.ejected:
                    break
                time.sleep(0.01)
            assert sick.ejected
            sick.revive(ContinuousBatchingPredictor(
                model, name=sick.name, max_batch_size=2, page_size=8,
                max_seq_len=64))
            assert len(router.healthy()) == 2
            h = router.submit(_prompts(1)[0], max_new_tokens=2)
            assert h.result(timeout=120) and h.status == "ok"

    def test_router_stream_and_tiers(self):
        """Router-level streaming: handle.stream() yields token events
        then "end"; per-tier router TTFT histograms gain the tier
        label."""
        model = _serve_model()
        with Router([model], tier_weights={"hi": 4, "lo": 1}, seed=0,
                    max_batch_size=2, page_size=8,
                    max_seq_len=64) as router:
            toks = []
            for ev in router.generate_stream(_prompts(1)[0],
                                             max_new_tokens=4,
                                             tier="hi"):
                if ev.kind == "token":
                    toks.append(ev.token)
                else:
                    assert ev.status == "ok"
            assert len(toks) == 4
        m = obs.get_registry().get("serving.router.ttft_seconds")
        assert m is not None and m.quantile(0.5, tier="hi") > 0

    def test_router_cancel_propagates(self):
        """handle.cancel() reaches the replica's serve loop: the
        request ends "cancelled" and the router counts it done."""
        model = _serve_model()
        with Router([model], seed=0, max_batch_size=1, page_size=8,
                    max_seq_len=96) as router:
            h = router.submit(_prompts(1)[0], max_new_tokens=40)
            got_first = False
            for ev in h.stream(timeout=120):
                if ev.kind == "token" and not got_first:
                    got_first = True
                    h.cancel()
                if ev.kind == "end":
                    assert ev.status == "cancelled"
            assert got_first
            assert h.status == "cancelled"
            assert 1 <= len(h.tokens) < 40

    def test_stream_timeout_raises_timeouterror(self):
        """stream(timeout=) raises TimeoutError on an expired wait,
        like result(timeout=) — not the raw queue.Empty."""
        model = _serve_model()
        with Router([model], seed=0, max_batch_size=2, page_size=8,
                    max_seq_len=64) as router:
            h = router.submit(_prompts(1, seed=3)[0], max_new_tokens=4)
            with pytest.raises(TimeoutError):
                for _ in h.stream(timeout=1e-4):
                    pass
            assert h.result(timeout=120) is not None

    def test_autoscale_signals_shape_and_gauges(self):
        """The serving.autoscale view: required signal keys present,
        sane desired-replica suggestion, and the gauges land in the
        registry for the exporters to pick up."""
        model = _serve_model()
        with Router([model, model], seed=0,
                    tier_weights={"interactive": 8, "batch": 1},
                    max_batch_size=2, page_size=8,
                    max_seq_len=64) as router:
            router.generate(_prompts(4), max_new_tokens=2,
                            tiers=["interactive", "batch"] * 2)
            sig = router.autoscale(slo_ttft_s=10.0)
        for key in ("queue_depth", "ttft_p90_s", "ttft_burn",
                    "page_pressure", "replica_utilization",
                    "healthy_replicas", "desired_replicas"):
            assert key in sig
        assert sig["healthy_replicas"] == 2
        assert 1 <= sig["desired_replicas"] <= 8
        assert sig["ttft_burn"] < 1.0            # SLO of 10s: headroom
        assert len(sig["page_pressure"]) == 2
        reg = obs.get_registry()
        assert reg.get("serving.autoscale.desired_replicas") is not None
        assert reg.get("serving.autoscale.ttft_burn") is not None


class TestReportsOnARoutedRun:
    def test_router_tier_replica_and_autoscale_sections_render(
            self, tmp_path):
        """The operator's two readers on the JSONL sink of a routed,
        tiered run: metrics_report has the router, per-tier and
        autoscale views, trace_report the per-tier SLO split and the
        per-replica table."""
        import importlib.util
        from paddle_tpu.observability import runtime as obs_rt

        def tool(name):
            spec = importlib.util.spec_from_file_location(
                f"_frontend_{name}", os.path.join(
                    os.path.dirname(__file__), "..", "tools",
                    f"{name}.py"))
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod

        path = str(tmp_path / "routed.jsonl")
        obs.get_registry().reset()
        obs.configure(path)
        model = _serve_model()
        with Router([model, model],
                    tier_weights={"interactive": 8, "batch": 1}, seed=0,
                    max_batch_size=2, page_size=8,
                    max_seq_len=64) as router:
            hs = [router.submit(p, max_new_tokens=3,
                                tier=("interactive", "batch")[i % 2])
                  for i, p in enumerate(_prompts(4))]
            assert all(h.result(timeout=120) for h in hs)
            sig = router.autoscale()
            assert sig["desired_replicas"] >= 1
        obs_rt.maybe_export()
        obs.configure(None)

        trr = tool("trace_report")
        text = trr.render(trr.load_spans(path))
        assert "per-tier SLO" in text and "interactive TTFT" in text
        assert "per-replica" in text and "replica0" in text
        mrr = tool("metrics_report")
        with open(path) as f:
            text = mrr.render(mrr.parse(f, spans={}), None)
        assert "serving front end (router)" in text
        assert "interactive" in text and "autoscale signals" in text

"""Disaggregated prefill/decode serving (PR 18) — KV page-span handoff.

Invariant coverage (ISSUE 18 satellites):
- KVPageSpan export → import round-trips the pages BITWISE (trailing
  partial page zero-padded past its valid tokens), dedups against
  prefix pages already resident on the import side, and rejects a
  corrupted span (checksum) without leaking pool pages;
- TP=2 head-sharded pools export the unsharded view and reshard on
  import (recorded as the kv_span_import/reshard fallback), bitwise in
  both directions;
- the two-stage router: a prefill+decode pool produces token-for-token
  the unified pool's greedy output, handoff telemetry
  (serving.handoff.*) carries the spans, and an un-exportable span
  (prefix cache off) falls back end-to-end with reason export_miss;
- a decode replica dying AFTER handoff re-dispatches to the DECODE
  role (never back to prefill), replaying the kept span — the
  Router._readmit regression;
- per-role RuntimeConfig overlays (for_role) and stage_cost shapes;
- per-role AOT bundles: warm start on a role+topology match, reason
  `role` on mismatch (strict raises, non-strict self-heals), prefill
  builds clamp the capture budget to 1 token.
(That every request of a two-role pool is one connected trace:
tests/test_request_tracing.py.)
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.serving import Router


@pytest.fixture(autouse=True)
def _clean():
    obs.configure(None)
    obs.enabled(True)
    yield
    obs.configure(None)
    obs.enabled(True)


def _serve_model():
    paddle.seed(0)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny())


def _cb(model, **kw):
    from paddle_tpu.inference import ContinuousBatchingPredictor
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq_len", 64)
    return ContinuousBatchingPredictor(model, **kw)


def _prompts(n, lens=(9, 12, 17, 7), seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, 256, (lens[i % len(lens)],)).tolist()
            for i in range(n)]


def _counter_total(name, **labels):
    m = obs.get_registry().get(name)
    if m is None:
        return 0.0
    return sum(s.value for s in m.samples()
               if all(s.labels.get(k) == v for k, v in labels.items()))


def _tp_mesh(tp=2):
    import jax
    from paddle_tpu.distributed.fleet.hybrid.plan import HybridParallelPlan
    plan = HybridParallelPlan.from_spec(f"model={tp}", zero_stage=0)
    return plan.build_mesh(devices=jax.devices()[:tp])


def _pool(mesh=None, num_pages=8):
    from paddle_tpu.generation.kv_cache import PagedKVPool
    return PagedKVPool(n_layers=2, num_pages=num_pages, page_size=4,
                       n_kv_heads=2, head_dim=2, mesh=mesh)


def _fill_pages(pool, ids, seed=0):
    """Write distinct deterministic values into `ids` (all layers)."""
    rng = np.random.RandomState(seed)
    for layer in range(len(pool.k)):
        for pid in ids:
            shape = pool.k[layer].shape[1:]
            pool.k[layer] = pool.k[layer].at[pid].set(
                rng.randn(*shape).astype(np.float32))
            pool.v[layer] = pool.v[layer].at[pid].set(
                rng.randn(*shape).astype(np.float32))


# ---------------------------------------------------------------------------
# KVPageSpan: export/import round-trip, dedup, rejection
# ---------------------------------------------------------------------------
class TestKVPageSpan:
    def test_export_import_bitwise_roundtrip(self):
        """A 7-token prompt (1 full page + 3-token partial, page=4)
        exports, transfers, and imports BITWISE — with the stale tail
        of the partial page zeroed so the payload (and checksum) is a
        function of the prompt's K/V only."""
        src = _pool()
        ids = src.alloc(2)
        _fill_pages(src, ids, seed=1)
        prompt = list(range(10, 17))                 # 7 tokens
        span = src.export_span(prompt, ids, next_token=42)
        assert span.verify()
        assert span.n_pages == 2 and span.nbytes > 0
        assert span.next_token == 42
        assert span.prompt == tuple(prompt)
        # the partial page's tail past token 3 is zeroed
        for a in span.k_pages + span.v_pages:
            assert np.all(a[-1, 3:] == 0)
        # ...but the valid prefix matches the source pages bitwise
        for layer in range(2):
            np.testing.assert_array_equal(
                span.k_pages[layer][0], np.array(src.k[layer][ids[0]]))
            np.testing.assert_array_equal(
                span.k_pages[layer][1][:3],
                np.array(src.k[layer][ids[1]])[:3])
        dst = _pool()
        stats = dst.import_span(span)
        assert stats["imported"] == 2 and stats["reused"] == 0
        assert stats["bytes"] == span.nbytes
        assert not stats["resharded"]
        got = stats["page_ids"]
        assert len(got) == 2
        for layer in range(2):
            np.testing.assert_array_equal(
                np.array(dst.k[layer][np.array(got)]),
                span.k_pages[layer])
            np.testing.assert_array_equal(
                np.array(dst.v[layer][np.array(got)]),
                span.v_pages[layer])
        # without a prefix cache the caller owns the refs
        assert dst.free_count == 6

    def test_prefix_dedup_on_import(self):
        """Importing into a pool whose trie already holds the span's
        prefix transfers only the missing pages; a replayed import of
        a fully-resident span moves zero bytes."""
        from paddle_tpu.generation.kv_cache import PrefixCache
        src = _pool()
        ids = src.alloc(3)
        _fill_pages(src, ids, seed=2)
        prompt = list(range(20, 28))                 # 2 full pages
        span = src.export_span(prompt, ids[:2], next_token=7)
        dst = _pool()
        cache = PrefixCache(page_size=4)
        s1 = dst.import_span(span, cache)
        assert s1["imported"] == 2 and s1["reused"] == 0
        free_after = dst.free_count
        # replay (the readmit path re-imports the kept span): fully
        # resident, nothing to transfer, no pages consumed
        s2 = dst.import_span(span, cache)
        assert s2["imported"] == 0 and s2["reused"] == 2
        assert s2["bytes"] == 0
        assert dst.free_count == free_after
        # a second span sharing the first page transfers only page 2
        prompt2 = prompt[:4] + list(range(40, 44))
        span2 = src.export_span(prompt2, [ids[0], ids[2]], next_token=9)
        s3 = dst.import_span(span2, cache)
        assert s3["reused"] == 1 and s3["imported"] == 1
        assert s3["bytes"] == span2.nbytes // 2

    def test_corrupted_span_rejected(self):
        """A flipped payload byte fails the checksum: the import
        raises before touching the pool (no page leak, nothing
        half-materialized)."""
        src = _pool()
        ids = src.alloc(1)
        _fill_pages(src, ids, seed=3)
        span = src.export_span(list(range(4)), ids, next_token=1)
        span.k_pages[0][0, 0, 0, 0] += 1.0
        assert not span.verify()
        dst = _pool()
        before = dst.free_count
        with pytest.raises(ValueError, match="checksum"):
            dst.import_span(span)
        assert dst.free_count == before

    def test_geometry_mismatch_rejected(self):
        from paddle_tpu.generation.kv_cache import PagedKVPool
        src = _pool()
        ids = src.alloc(1)
        span = src.export_span(list(range(4)), ids)
        other = PagedKVPool(n_layers=2, num_pages=4, page_size=8,
                            n_kv_heads=2, head_dim=2)
        with pytest.raises(ValueError, match="geometry"):
            other.import_span(span)


# ---------------------------------------------------------------------------
# TP=2 head-sharded export/import parity
# ---------------------------------------------------------------------------
class TestSpanTP:
    def test_sharded_export_unsharded_import_bitwise(self):
        """A head-sharded pool exports the assembled UNSHARDED view;
        importing it into a single-device pool is bitwise and records
        the cross-layout reshard fallback."""
        sharded = _pool(mesh=_tp_mesh(2))
        assert sharded.kv_sharding is not None
        ids = sharded.alloc(2)
        _fill_pages(sharded, ids, seed=4)
        prompt = list(range(30, 38))
        reg = obs.get_registry()
        before = _counter_total("kernels.pallas_fallbacks",
                                kernel="kv_span_import", reason="reshard")
        span = sharded.export_span(prompt, ids, next_token=5)
        assert span.verify()
        assert span.topology != "single"
        dst = _pool()
        stats = dst.import_span(span)
        assert stats["resharded"]
        assert _counter_total("kernels.pallas_fallbacks",
                              kernel="kv_span_import",
                              reason="reshard") == before + 1
        got = np.array(stats["page_ids"])
        for layer in range(2):
            np.testing.assert_array_equal(
                np.array(dst.k[layer][got]), span.k_pages[layer])
            np.testing.assert_array_equal(
                np.array(dst.v[layer][got]), span.v_pages[layer])

    def test_unsharded_export_sharded_import_bitwise(self):
        """The reverse direction: importing a replicated span into a
        TP=2 pool lays it out on the head-sharded mesh (the decode
        fleet may run a different topology than prefill) and keeps the
        sharded layout on the hot arrays."""
        src = _pool()
        ids = src.alloc(2)
        _fill_pages(src, ids, seed=5)
        prompt = list(range(50, 58))
        span = src.export_span(prompt, ids, next_token=3)
        dst = _pool(mesh=_tp_mesh(2))
        stats = dst.import_span(span)
        assert stats["resharded"]
        assert dst.k[0].sharding.spec[2] == "model"
        got = np.array(stats["page_ids"])
        for layer in range(2):
            np.testing.assert_array_equal(
                np.array(dst.k[layer][got]), span.k_pages[layer])


# ---------------------------------------------------------------------------
# two-stage router: parity, telemetry, fallbacks, readmission
# ---------------------------------------------------------------------------
class TestDisaggRouter:
    def test_disagg_greedy_parity_and_handoff_telemetry(self):
        """A 1-prefill + 1-decode pool serves token-for-token the
        unified predictor's greedy output; every request hands off
        exactly once (serving.handoff.requests / .seconds / .bytes),
        no fallbacks, and finishes on the decode replica in stage
        "decode"."""
        model = _serve_model()
        prompts = _prompts(4)
        ref = _cb(model).generate(prompts, max_new_tokens=6)
        before_req = _counter_total("serving.handoff.requests")
        before_fb = _counter_total("serving.handoff.fallbacks")
        before_bytes = _counter_total("serving.handoff.bytes")
        with Router([model, model], roles=["prefill", "decode"], seed=0,
                    max_batch_size=2, page_size=8,
                    max_seq_len=64) as router:
            assert router.disaggregated
            hs = [router.submit(p, max_new_tokens=6) for p in prompts]
            outs = [h.result(timeout=120) for h in hs]
            assert outs == ref
            assert all(h.status == "ok" for h in hs)
            assert all(h.stage == "decode" for h in hs)
            decode_name = router.replicas[1].name
            assert all(h.replica == decode_name for h in hs)
            # TTFT was measured (first token streamed from the
            # prefill side before the handoff)
            assert all(h.first_token_ts is not None for h in hs)
        assert _counter_total("serving.handoff.requests") \
            == before_req + len(prompts)
        assert _counter_total("serving.handoff.fallbacks") == before_fb
        assert _counter_total("serving.handoff.bytes") > before_bytes
        hist = obs.get_registry().get("serving.handoff.seconds")
        assert hist is not None
        assert sum(s.count for s in hist.series()) >= len(prompts)
        assert _counter_total("serving.handoff.pages",
                              kind="imported") > 0

    def test_export_miss_falls_back_end_to_end(self):
        """A prefill replica that cannot export a span (prefix cache
        off) still hands the request to the decode fleet — without a
        span, counted under fallbacks{reason=export_miss} — and the
        decode side prefills from scratch, greedy output unchanged."""
        model = _serve_model()
        prompt = _prompts(1)[0]
        ref = _cb(model).generate([prompt], max_new_tokens=6)
        pred_p = _cb(model, name="p0", role="prefill",
                     enable_prefix_cache=False)
        pred_d = _cb(model, name="d0", role="decode")
        before = _counter_total("serving.handoff.fallbacks",
                                reason="export_miss")
        with Router([pred_p, pred_d],
                    roles=["prefill", "decode"], seed=0) as router:
            h = router.submit(prompt, max_new_tokens=6)
            assert h.result(timeout=120) == ref[0]
            assert h.status == "ok"
            assert h.replica == "d0"
            assert h.handoff_span is None
        assert _counter_total("serving.handoff.fallbacks",
                              reason="export_miss") == before + 1

    def test_handoff_corrupt_fault_reprefills_end_to_end(self):
        """Chaos arm for the handoff wire: the handoff_corrupt fault
        site flips one payload byte in the KV span BEFORE the decode
        side imports it. The span's checksum fence must reject the
        import (fallbacks{reason=corrupt}), the request must re-prefill
        from scratch on the decode replica — never decode from corrupt
        pages — and the greedy output must stay bitwise identical to
        the unified predictor's."""
        model = _serve_model()
        prompt = _prompts(1)[0]
        ref = _cb(model).generate([prompt], max_new_tokens=6)
        before = _counter_total("serving.handoff.fallbacks",
                                reason="corrupt")
        injected = _counter_total("robustness.faults_injected",
                                  site="handoff_corrupt")
        paddle.set_flags(
            {"fault_injection": "handoff_corrupt:times=1"})
        try:
            with Router([model, model], roles=["prefill", "decode"],
                        seed=0, max_batch_size=2, page_size=8,
                        max_seq_len=64) as router:
                h = router.submit(prompt, max_new_tokens=6)
                assert h.result(timeout=120) == ref[0]
                assert h.status == "ok"
                assert h.stage == "decode"
        finally:
            paddle.set_flags({"fault_injection": ""})
        assert _counter_total("serving.handoff.fallbacks",
                              reason="corrupt") == before + 1
        assert _counter_total("robustness.faults_injected",
                              site="handoff_corrupt") == injected + 1

    def test_snapshot_refresh_waits_for_concurrent_trace(self):
        """The shared-model snapshot race a disaggregated pool makes
        likely: while one replica's FIRST trace holds the per-model
        trace lock with the shared parameter Tensors rebound to
        tracers (bound_state), another replica's _ensure_ready must
        BLOCK on that lock — an unlocked snapshot would commit the
        tracers as a "weight update" (leaked-tracer dispatch + a
        spurious prefix-cache flush). Simulated deterministically with
        a sentinel standing in for the tracer."""
        import threading
        model = _serve_model()
        pred_a = _cb(model, name="a")
        pred_a.generate([_prompts(1)[0]], max_new_tokens=2)
        pred_b = _cb(model, name="b")
        lock = model.__dict__["_cb_trace_lock"]
        params = [p for _, p in model.named_parameters()]
        olds = [p._value for p in params]
        sentinel = object()
        entered, release, done = (threading.Event(), threading.Event(),
                                  threading.Event())
        snap = {}

        def fake_trace():    # what _jit_call's locked bound_state does
            with lock:
                for p in params:
                    p._value = sentinel
                entered.set()
                release.wait(timeout=30)
                for p, v in zip(params, olds):
                    p._value = v

        def refresh():
            pred_b._ensure_ready()
            snap["vals"] = list(pred_b._p_src)
            done.set()

        t1 = threading.Thread(target=fake_trace)
        t1.start()
        assert entered.wait(timeout=10)
        t2 = threading.Thread(target=refresh)
        t2.start()
        # must park on the trace lock, not read the sentinel-bound
        # tensors
        assert not done.wait(timeout=0.3)
        release.set()
        assert done.wait(timeout=30)
        t1.join(timeout=10)
        t2.join(timeout=10)
        assert all(v is not sentinel for v in snap["vals"])

    def test_readmit_after_handoff_goes_to_decode(self):
        """The Router._readmit regression: a decode replica dying
        AFTER handoff re-dispatches the request to the DECODE role —
        never back to prefill — replaying the kept span on the
        surviving decode replica, with already-streamed tokens deduped
        by the handle's ordinal guard."""
        model = _serve_model()
        prompt = _prompts(1)[0]
        ref = _cb(model).generate([prompt], max_new_tokens=6)
        before_re = _counter_total("serving.router.readmissions")
        with Router([model, model, model],
                    roles=["prefill", "decode", "decode"], seed=0,
                    max_batch_size=2, page_size=8,
                    max_seq_len=64) as router:
            armed = {"on": True}
            # arm a one-shot bomb on BOTH decode replicas: whichever
            # receives the handed-off request dies on its first decode
            # step; the replay on the survivor passes through
            for rep in router.replicas[1:]:
                orig = rep.predictor._resolve_step

                def bomb(*a, _orig=orig, **kw):
                    if armed["on"]:
                        armed["on"] = False
                        raise RuntimeError("boom")
                    return _orig(*a, **kw)

                rep.predictor._resolve_step = bomb
            h = router.submit(prompt, max_new_tokens=6)
            out = h.result(timeout=120)
            assert not armed["on"], "the bomb never fired"
            assert out == ref[0]
            assert h.status == "ok"
            assert h.attempts == 1
            assert h.stage == "decode"
            assert h.handoff_span is not None   # span kept for replay
            final = next(r for r in router.replicas
                         if r.name == h.replica)
            assert final.role == "decode"
        assert _counter_total("serving.router.readmissions") \
            >= before_re + 1


# ---------------------------------------------------------------------------
# per-role RuntimeConfig overlays + stage cost
# ---------------------------------------------------------------------------
class TestRoleConfig:
    def test_for_role_overlays(self):
        from paddle_tpu.framework.runtime_config import (
            RuntimeConfig, config_hash)
        rc = RuntimeConfig(spec_draft_tokens=3, sampling_enabled=True,
                           prefill_chunk_tokens=64)
        rp = rc.for_role("prefill")
        assert rp.serve_role == "prefill"
        assert rp.spec_draft_tokens == 0 and not rp.sampling_enabled
        assert rp.prefill_chunk_tokens == 64      # chunking kept
        rd = rc.for_role("decode")
        assert rd.serve_role == "decode"
        assert rd.prefill_chunk_tokens == 0       # no chunk ingest
        assert rd.spec_draft_tokens == 3          # spec kept
        ru = rc.for_role("unified")
        assert ru == rc.replace(serve_role="unified")
        # distinct roles hash distinctly (per-fleet bundle payloads)
        assert len({config_hash(x.to_dict())
                    for x in (rc, rp, rd)}) == 3
        with pytest.raises(ValueError, match="serve_role"):
            rc.for_role("bogus")

    def test_stage_cost_shapes(self):
        from paddle_tpu.serving.scheduler import stage_cost
        assert stage_cost(100, 32, None) == 132.0
        assert stage_cost(100, 32, "prefill") == 101.0
        assert stage_cost(100, 32, "decode") == 32.0 + 100 / 8.0
        # the two stages together never weigh less than the unified
        # dispatch underestimates would hide
        assert stage_cost(100, 32, "prefill") \
            + stage_cost(100, 32, "decode") > stage_cost(100, 32, None) / 2


# ---------------------------------------------------------------------------
# per-role AOT bundles
# ---------------------------------------------------------------------------
class TestRoleBundle:
    def test_role_mismatch_invalidation(self, tmp_path):
        """A bundle built for role=decode warm-starts clean for decode,
        refuses a prefill warm start with reason `role` (strict), and
        non-strict self-heals to the requested role + re-fingerprints
        (aot.invalidations{reason="role"})."""
        import json
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        from paddle_tpu.inference.aot import EngineBuilder, warm_start
        from paddle_tpu.inference.aot.bundle import BundleInvalid
        model = _serve_model()
        rc = RuntimeConfig(max_batch_size=2, page_size=8, max_seq_len=64,
                           prompt_buckets=(8,)).for_role("decode")
        path = str(tmp_path / "bundle")
        EngineBuilder(model, batch_sizes=[1], capture_forward=False,
                      runtime_config=rc).build(path, wire_cache=False)
        man = json.load(open(path + "/manifest.json"))
        assert man["geometry"]["role"] == "decode"
        reg = obs.get_registry()
        reg.reset()
        # matching role: warm, zero invalidations
        p, e = warm_start(model, path, wire_cache=False,
                          runtime_config=rc)
        assert e.warm and p.role == "decode"
        inv = reg.get("aot.invalidations")
        assert inv is None or not any(s.value for s in inv.samples())
        # mismatching role: strict raises with the reason...
        with pytest.raises(BundleInvalid) as ei:
            warm_start(model, path, wire_cache=False, strict=True,
                       role="prefill")
        assert ei.value.reason == "role"
        # ...non-strict invalidates, heals, re-fingerprints
        p2, e2 = warm_start(model, path, wire_cache=False,
                            role="prefill")
        assert not e2.warm and p2.role == "prefill"
        inv = reg.get("aot.invalidations")
        assert any(s.labels.get("reason") == "role"
                   for s in inv.samples())
        g = e2.bundle.manifest(refresh=True)["geometry"]
        assert g["role"] == "prefill"

    def test_prefill_build_clamps_capture_budget(self):
        """A prefill-role build captures ingest + ONE token — the rest
        of the budget runs on the decode fleet, so compiling decode
        depth into the prefill bundle would be pure cold-start waste."""
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        from paddle_tpu.inference.aot import EngineBuilder
        model = _serve_model()
        rc = RuntimeConfig(max_batch_size=2, page_size=8, max_seq_len=64,
                           prompt_buckets=(8,))
        b = EngineBuilder(model, batch_sizes=[1], max_new_tokens=16,
                          capture_forward=False,
                          runtime_config=rc.for_role("prefill"))
        assert b.max_new_tokens == 1
        b2 = EngineBuilder(model, batch_sizes=[1], max_new_tokens=16,
                           capture_forward=False,
                           runtime_config=rc.for_role("decode"))
        assert b2.max_new_tokens == 16

"""Tensor-parallel serving (PR 17) — GSPMD-sharded serve loop.

Covers, on the 8-device XLA CPU host mesh (conftest):
- TP=2 vs TP=1 BITWISE greedy parity through the serve path — plain,
  open-ended serve_stream, chunked-prefill, and spec-verify variants
  (the sharded matmul + all-reduce must reassemble the exact logits,
  not merely close ones);
- head-sharded PagedKVPool: refcount / copy-on-write invariants are
  sharding-independent, indivisible head counts are rejected at the
  pool and downgraded (with the tp_head_shard fallback reason) at the
  predictor;
- the _paged_gate per-shard tiling judgment (reason tp_head_shard);
- per-topology AOT bundles: a warm start at a different tp_degree
  invalidates with reason `topology` (strict raises, non-strict
  self-heals to the requested degree), and the matching degree
  warm-starts without compiling, bitwise the one-device replica.
"""
import numpy as np
import pytest

import paddle_tpu as paddle


def _model(**kw):
    paddle.seed(0)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny(**kw))


def _cb(model, tp=1, **kw):
    from paddle_tpu.inference import ContinuousBatchingPredictor
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq_len", 64)
    return ContinuousBatchingPredictor(model, tp_degree=tp, **kw)


def _tp_mesh(tp=2):
    import jax
    from paddle_tpu.distributed.fleet.hybrid.plan import HybridParallelPlan
    plan = HybridParallelPlan.from_spec(f"model={tp}", zero_stage=0)
    return plan.build_mesh(devices=jax.devices()[:tp])


# ---------------------------------------------------------------------------
# bitwise greedy parity, TP=2 vs TP=1
# ---------------------------------------------------------------------------
class TestTPGreedyParity:
    def test_plain_decode_parity(self):
        """One replica spanning 2 devices produces token-for-token the
        single-device stream — and both match the static reference."""
        from paddle_tpu.inference import LLMPredictor
        model = _model()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(2, 256, (n,)).tolist() for n in (9, 4, 13)]
        ref = LLMPredictor(model, max_batch_size=1).generate(
            prompts, max_new_tokens=10)
        out1 = _cb(model, tp=1).generate(prompts, max_new_tokens=10)
        cb2 = _cb(model, tp=2)
        out2 = cb2.generate(prompts, max_new_tokens=10)
        assert out2 == out1 == ref
        assert cb2.tp == 2 and cb2.tp_topology == "model=2"
        assert len(cb2.tp_devices) == 2
        # KV pages actually sharded over heads (4 kv heads / 2 shards)
        assert cb2.pool.kv_sharding is not None

    def test_serve_stream_parity(self):
        """The open-ended replica loop (serve_stream intake) under
        TP=2 matches the TP=1 batch path."""
        from paddle_tpu.serving.streaming import ServeRequest
        model = _model()
        rng = np.random.RandomState(1)
        prompts = [rng.randint(2, 256, (n,)).tolist() for n in (7, 12)]
        ref = _cb(model, tp=1).generate(prompts, max_new_tokens=8)
        cb = _cb(model, tp=2)
        state = {"sent": False}

        def intake():
            if state["sent"]:
                return None
            state["sent"] = True
            return [ServeRequest(p, 8) for p in prompts]

        stream = cb.serve_stream(intake)
        for _ in stream:
            pass
        assert list(stream.results) == ref

    def test_chunked_prefill_parity(self):
        """Chunked prompt ingestion (mixed prefill+decode program)
        stays bitwise under GSPMD sharding."""
        model = _model()
        rng = np.random.RandomState(2)
        prompts = [rng.randint(2, 256, (n,)).tolist() for n in (37, 23)]
        kw = dict(max_seq_len=128, prefill_chunk_tokens=16)
        ref = _cb(model, tp=1, **kw).generate(prompts, max_new_tokens=8)
        cb = _cb(model, tp=2, **kw)
        assert cb.generate(prompts, max_new_tokens=8) == ref
        assert cb.stats["chunked_requests"] >= 1

    def test_spec_verify_parity(self):
        """Speculative multi-token verify steps under TP=2: greedy
        output stays bitwise plain-greedy, and drafts are accepted
        (the verify program really ran sharded)."""
        model = _model()
        # repetitive prompts so prompt-lookup drafting fires
        prompts = [[1, 2, 3, 4] * 2 + [1, 2], [5, 6, 7] * 3]
        ref = _cb(model, tp=1).generate(prompts, max_new_tokens=10)
        cb = _cb(model, tp=2, spec_draft_tokens=3)
        assert cb.generate(prompts, max_new_tokens=10) == ref
        assert cb.stats["spec_accepted"] > 0

    def test_tp_telemetry_and_comm_accounting(self):
        """TP gauges export under the replica's device-group label and
        every dispatched tick books model-axis all-reduce bytes (the
        analytic GSPMD accounting propose_tp consumes)."""
        import paddle_tpu.observability as obs
        model = _model()
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            cb = _cb(model, tp=2, name="r0")
            cb.generate([[2, 3, 4, 5]], max_new_tokens=6)
            reg = obs.get_registry()
            deg = reg.get("serving.tp.degree")
            s = [x for x in deg.samples() if x.labels.get("replica") == "r0"]
            assert s and s[0].value == 2.0
            assert s[0].labels.get("devices")   # e.g. "0-1"
            assert next(iter(reg.get(
                "serving.tp.kv_shards").samples())).value == 2.0
            calls = reg.get("comm.calls").value(op="all_reduce",
                                                axis="model")
            bts = reg.get("comm.bytes").value(op="all_reduce", axis="model")
            assert calls > 0 and bts > 0
            # 2 row-parallel all-reduces per layer per token
            cfg = model.config
            per_tok = 2 * cfg.num_hidden_layers * cfg.hidden_size * 4
            assert bts % per_tok == 0
        finally:
            obs.enabled(was)


# ---------------------------------------------------------------------------
# head-sharded PagedKVPool
# ---------------------------------------------------------------------------
class TestHeadShardedPool:
    def test_sharded_pool_refcount_and_cow(self):
        """Refcount / copy-on-write semantics are identical with pages
        sharded over heads — same invariants as the unsharded pool test
        (test_serving_fastpath), plus the sharding actually applied."""
        import jax.numpy as jnp
        from paddle_tpu.generation.kv_cache import PagedKVPool
        pool = PagedKVPool(n_layers=2, num_pages=4, page_size=4,
                           n_kv_heads=2, head_dim=2, mesh=_tp_mesh(2))
        assert pool.kv_sharding is not None
        assert pool.k[0].sharding.spec[2] == "model"
        a, b = pool.alloc(2)
        assert pool.free_count == 2
        pool.retain([a])
        pool.release([a])
        assert pool.free_count == 2          # still held once
        pool.k[0] = pool.k[0].at[a].set(7.0)
        pool.copy_into(a, b)
        assert float(jnp.max(jnp.abs(pool.k[0][b] - 7.0))) == 0.0
        # the CoW copy kept the head-sharded layout (no silent gather
        # to one device on the decode hot path)
        assert pool.k[0].sharding.spec[2] == "model"
        pool.release([a])
        pool.release([b])
        assert pool.free_count == 4
        assert pool.ref_count(a) == 0

    def test_indivisible_heads_rejected_at_pool(self):
        from paddle_tpu.generation.kv_cache import PagedKVPool
        with pytest.raises(ValueError, match="divide"):
            PagedKVPool(n_layers=1, num_pages=2, page_size=4,
                        n_kv_heads=3, head_dim=2, mesh=_tp_mesh(2))

    def test_predictor_downgrades_indivisible_heads(self):
        """A model whose KV heads don't divide tp_degree keeps
        replicated pages (served, fast path lost) and records the
        downgrade as a pallas fallback with reason tp_head_shard."""
        import paddle_tpu.observability as obs
        model = _model(num_attention_heads=4, num_key_value_heads=1)
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            ref = _cb(model, tp=1).generate([[3, 4, 5, 6]],
                                            max_new_tokens=6)
            cb = _cb(model, tp=2)
            assert cb.pool.kv_sharding is None
            fb = obs.get_registry().get("kernels.pallas_fallbacks")
            assert fb.value(kernel="paged_kv_pool",
                            reason="tp_head_shard") == 1
            assert next(iter(obs.get_registry().get(
                "serving.tp.kv_shards").samples())).value == 1.0
            assert cb.generate([[3, 4, 5, 6]], max_new_tokens=6) == ref
        finally:
            obs.enabled(was)

    def test_paged_gate_tp_head_shard_reason(self):
        """_paged_gate judges the PER-SHARD head count: a global head
        count that tiles (16 % 8 == 0) but whose shard doesn't
        (16/4 = 4 heads) loses the Pallas path with reason
        tp_head_shard."""
        import jax.numpy as jnp
        import paddle_tpu.observability as obs
        from paddle_tpu.kernels.paged_attention import _paged_gate
        q = jnp.zeros((1, 16, 128))
        pages = jnp.zeros((2, 4, 16, 128))
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            assert _paged_gate("paged_attention", q, pages, pages,
                               True, tp_degree=2)      # 8 heads/shard
            assert not _paged_gate("paged_attention", q, pages, pages,
                                   True, tp_degree=4)  # 4 heads/shard
            fb = obs.get_registry().get("kernels.pallas_fallbacks")
            assert fb.value(kernel="paged_attention",
                            reason="tp_head_shard") == 1
        finally:
            obs.enabled(was)


# ---------------------------------------------------------------------------
# per-topology AOT bundles
# ---------------------------------------------------------------------------
class TestTopologyBundle:
    def test_topology_mismatch_invalidation(self, tmp_path):
        """A bundle compiled for model=2 refuses a tp_degree=1 warm
        start with reason `topology` (checked FIRST, before the generic
        geometry diff); non-strict self-heals to the requested degree
        and re-fingerprints; the matching degree warm-starts clean."""
        import paddle_tpu.observability as obs
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        from paddle_tpu.inference.aot import EngineBuilder, warm_start
        from paddle_tpu.inference.aot.bundle import BundleInvalid
        model = _model()
        rc = RuntimeConfig(max_batch_size=2, page_size=8, max_seq_len=64,
                           prompt_buckets=(8,), tp_degree=2)
        path = str(tmp_path / "bundle")
        EngineBuilder(model, batch_sizes=[1], capture_forward=False,
                      runtime_config=rc).build(path, wire_cache=False)
        man = __import__("json").load(
            open(path + "/manifest.json"))
        assert man["geometry"]["tp_degree"] == 2
        assert man["geometry"]["mesh_topology"] == "model=2"
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            # matching degree: warm, no invalidation
            p2, e2 = warm_start(model, path, wire_cache=False,
                                runtime_config=rc)
            assert e2.warm and p2.tp == 2
            inv = obs.get_registry().get("aot.invalidations")
            assert inv is None or not any(s.value for s in inv.samples())
            # mismatching degree: strict raises with the reason...
            with pytest.raises(BundleInvalid) as ei:
                warm_start(model, path, wire_cache=False, strict=True,
                           tp_degree=1)
            assert ei.value.reason == "topology"
            # ...non-strict invalidates, heals, re-fingerprints
            p1, e1 = warm_start(model, path, wire_cache=False,
                                tp_degree=1)
            assert not e1.warm and p1.tp == 1
            inv = obs.get_registry().get("aot.invalidations")
            assert any(s.labels.get("reason") == "topology"
                       for s in inv.samples())
            g = e1.bundle.manifest(refresh=True)["geometry"]
            assert g["tp_degree"] == 1
            assert g["mesh_topology"] == "replicated"
        finally:
            obs.enabled(was)

    def test_tp2_bundle_warm_starts_zero_compile_with_tp1_parity(
            self, tmp_path):
        """The model=2 programs come back from their per-topology
        bundle: a warm start serves from it alone, sharded over two
        devices, and its greedy tokens are the one-device replica's."""
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        from paddle_tpu.inference.aot import EngineBuilder, warm_start
        model = _model()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(2, 256, (n,)).tolist() for n in (12, 11)]
        ref = _cb(model, tp=1).generate(prompts, max_new_tokens=8)
        rc = RuntimeConfig(max_batch_size=2, page_size=8, max_seq_len=64,
                           prompt_buckets=(16,), tp_degree=2)
        path = str(tmp_path / "bundle")
        EngineBuilder(model, batch_sizes=[1, 2], capture_forward=False,
                      runtime_config=rc, eos_token_id=None).build(
            path, wire_cache=False)
        pred, eng = warm_start(model, path, wire_cache=False)
        assert eng.warm and pred.tp == 2
        assert pred.pool.kv_sharding is not None
        assert pred.generate(prompts, max_new_tokens=8) == ref
        assert eng.stats["hits"] > 0 and eng.stats["misses"] == 0

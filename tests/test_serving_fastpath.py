"""Serving fast path (device-resident prefill, prefix caching,
sync-free decode) — the PR-2 acceptance suite.

Covers, against the continuous-batching predictor:
- zero per-layer host round-trips at admission (no Tensor.numpy on
  prefill K/V; every host download in the serve loop is a small int
  vector), asserted by patching the transfer points;
- prefix-cache hit / refcount / copy-on-write semantics, including a
  full hit running ZERO prefill forward passes;
- batched same-bucket prefill parity with the static generate path;
- rejection + head-of-line-skip behavior under page pressure;
- token-for-token decode parity with model.generate;
- the incremental ragged-meta builder vs the from-scratch flatten;
- the serving series (prefill, first token, prefix hits and misses)
  reaching the JSONL sink;
- the windowed-segment-mean 'area' pooling precision fix.
"""
import numpy as np
import pytest

import paddle_tpu as paddle


def _model(**kw):
    paddle.seed(0)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny(**kw))


def _ref(model, prompts, max_new=8):
    from paddle_tpu.inference import LLMPredictor
    return LLMPredictor(model, max_batch_size=1).generate(
        prompts, max_new_tokens=max_new)


class TestDeviceResidentAdmission:
    def test_no_host_roundtrip_for_prefill_kv(self, monkeypatch):
        """Admission must not fetch K/V to host: Tensor.numpy (the old
        per-layer round-trip) is never called inside generate, and every
        np.asarray download the serve loop performs is a small int
        vector (tokens/flags), never a [L, S, H, D] cache block."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        import paddle_tpu.inference as inf
        from paddle_tpu.tensor import Tensor

        model = _model()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(2, 256, (n,)).tolist() for n in (5, 11, 3)]
        ref = _ref(model, prompts)

        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, max_seq_len=64)
        numpy_calls = []
        orig_numpy = Tensor.numpy
        monkeypatch.setattr(
            Tensor, "numpy",
            lambda self: numpy_calls.append(1) or orig_numpy(self))
        fetched_sizes = []
        orig_asarray = inf.np.asarray

        def counting_asarray(a, *args, **kw):
            if not isinstance(a, (np.ndarray, list, tuple, int, float)):
                fetched_sizes.append(int(np.size(orig_asarray(a))))
            return orig_asarray(a, *args, **kw)

        monkeypatch.setattr(inf.np, "asarray", counting_asarray)
        out = cb.generate(prompts, max_new_tokens=8)
        monkeypatch.undo()

        assert out == ref
        assert numpy_calls == []            # zero Tensor.numpy anywhere
        assert fetched_sizes, "expected token downloads"
        # largest legal download: the [N, bucket] next-token matrix
        assert max(fetched_sizes) <= 4 * 64

    def test_batched_bucket_prefill_parity(self):
        """Several same-bucket prompts admitted in ONE prefill batch
        must produce the same tokens as the sequential static path."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model()
        rng = np.random.RandomState(1)
        # 4 prompts in the 8-bucket, batch of 4 slots: one admission
        # round prefills them together
        prompts = [rng.randint(2, 256, (n,)).tolist() for n in (5, 7, 6, 8)]
        cb = ContinuousBatchingPredictor(model, max_batch_size=4,
                                         page_size=8, max_seq_len=64,
                                         enable_prefix_cache=False)
        out = cb.generate(prompts, max_new_tokens=6)
        assert out == _ref(model, prompts, 6)
        assert cb.stats["prefill_batches"] == 1
        assert cb.stats["prefills"] == 4

    def test_decode_parity_with_model_generate(self):
        """Token-for-token parity with model.generate (greedy), prefix
        cache on and off."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model()
        rng = np.random.RandomState(2)
        prompts = [rng.randint(2, 256, (n,)).tolist() for n in (9, 4, 13)]
        ref = _ref(model, prompts, 10)
        for pfx in (True, False):
            cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                             page_size=8, max_seq_len=64,
                                             enable_prefix_cache=pfx)
            assert cb.generate(prompts, max_new_tokens=10) == ref

    def test_gqa_decode_parity(self):
        """Grouped-query models ride the XLA paged-attention path."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model(num_attention_heads=4, num_key_value_heads=2)
        rng = np.random.RandomState(3)
        prompts = [rng.randint(2, 256, (n,)).tolist() for n in (6, 10)]
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, max_seq_len=64)
        assert cb.generate(prompts, max_new_tokens=6) == _ref(
            model, prompts, 6)


class TestPrefixCache:
    def test_pool_refcount_and_cow(self):
        """PagedKVPool unit semantics: alloc→1 ref, retain/release
        counting, free only at zero, device copy-on-write."""
        import jax.numpy as jnp
        from paddle_tpu.generation.kv_cache import PagedKVPool
        pool = PagedKVPool(n_layers=2, num_pages=4, page_size=4,
                           n_kv_heads=1, head_dim=2)
        a, b = pool.alloc(2)
        assert pool.free_count == 2
        pool.retain([a])
        pool.release([a])
        assert pool.free_count == 2          # still held once
        pool.k[0] = pool.k[0].at[a].set(7.0)
        pool.copy_into(a, b)
        assert float(jnp.max(jnp.abs(pool.k[0][b] - 7.0))) == 0.0
        pool.release([a])
        pool.release([b])
        assert pool.free_count == 4
        assert pool.ref_count(a) == 0

    def test_full_hit_zero_forward_passes(self):
        """A repeated prompt skips prefill entirely: the cached pages
        and the cached continuation token admit the request with no
        forward pass, and outputs stay token-identical."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model()
        rng = np.random.RandomState(4)
        prompt = rng.randint(2, 256, (11,)).tolist()   # non page-aligned
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, max_seq_len=64)
        first = cb.generate([prompt], max_new_tokens=6)
        n_prefills = cb.stats["prefills"]
        again = cb.generate([prompt], max_new_tokens=6)
        assert again == first
        assert cb.stats["prefills"] == n_prefills       # ZERO new forwards
        assert cb.stats["prefix_hits"] == 1
        assert cb.stats["pages_reused"] >= 2            # 1 full + partial

    def test_partial_hit_suffix_prefill_and_cow(self):
        """A prompt extending a cached one prefills only the suffix
        (copy-on-write at the shared partial page), with exact parity;
        re-serving the original prompt afterwards still full-hits with
        the original tokens — the CoW protected the cached page."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model()
        rng = np.random.RandomState(5)
        base = rng.randint(2, 256, (10,)).tolist()
        longer = base + rng.randint(2, 256, (5,)).tolist()
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, max_seq_len=64)
        out_base = cb.generate([base], max_new_tokens=6)
        out_long = cb.generate([longer], max_new_tokens=6)
        assert cb.stats["prefix_partial_hits"] == 1
        assert out_long == _ref(model, [longer], 6)
        out_base2 = cb.generate([base], max_new_tokens=6)
        assert out_base2 == out_base
        assert cb.stats["prefix_hits"] >= 1

    def test_shared_prefix_within_one_stream(self):
        """Requests inside one generate() call share prefixes too."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model()
        rng = np.random.RandomState(6)
        sys_prompt = rng.randint(2, 256, (16,)).tolist()  # 2 full pages
        prompts = [sys_prompt + rng.randint(2, 256, (k,)).tolist()
                   for k in (3, 4, 5, 6)]
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, max_seq_len=64)
        out = cb.generate(prompts, max_new_tokens=6)
        assert out == _ref(model, prompts, 6)
        assert cb.stats["pages_reused"] >= 2   # later requests reused
        assert cb.stats["prefix_partial_hits"] + cb.stats["prefix_hits"] >= 1

    def test_reclaim_under_pressure_and_no_leak(self):
        """Cached pages are dropped LRU-first when allocation runs
        short, free_count reports them as available, and nothing leaks
        across generate calls."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model()
        rng = np.random.RandomState(7)
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, num_pages=4,
                                         max_seq_len=32)
        free0 = cb.pool.free_count
        for _ in range(3):   # distinct prompts force cache turnover
            prompts = [rng.randint(2, 256, (n,)).tolist() for n in (9, 5)]
            out = cb.generate(prompts, max_new_tokens=4)
            assert all(len(o) == 4 for o in out)
            assert cb.pool.free_count == free0


class TestWeightRefresh:
    def test_weight_update_between_generates_honored(self):
        """generate() re-snapshots the model arrays each call: a weight
        update between calls changes the output AND flushes the prefix
        cache (its K/V was computed with the old weights)."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model()
        rng = np.random.RandomState(9)
        prompt = rng.randint(2, 256, (9,)).tolist()
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, max_seq_len=64)
        cb.generate([prompt], max_new_tokens=6)
        for p in model.parameters():
            if p.ndim == 2:
                p.set_value(p * 0.5)
        ref = _ref(model, [prompt], 6)
        out = cb.generate([prompt], max_new_tokens=6)
        assert out == ref                       # new weights served
        assert cb.stats["prefix_hits"] == 0     # stale cache flushed


class TestQueuePolicy:
    def test_hol_skip_under_page_pressure(self):
        """A large request waiting for pages must not starve later
        small ones: the admission scan passes over it (counted in
        serving.hol_skips) and serves everyone eventually."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model()
        rng = np.random.RandomState(8)
        small1 = rng.randint(2, 256, (4,)).tolist()    # 2 pages w/ +8
        big = rng.randint(2, 256, (20,)).tolist()      # 4 pages w/ +8
        small2 = rng.randint(2, 256, (5,)).tolist()
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, num_pages=4,
                                         max_seq_len=32,
                                         enable_prefix_cache=False)
        prompts = [small1, big, small2]
        out = cb.generate(prompts, max_new_tokens=8)
        assert out == _ref(model, prompts, 8)
        assert cb.stats["hol_skips"] >= 1
        assert cb.last_status == ["ok", "ok", "ok"]

    def test_rejection_reasons_and_page_accounting(self):
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model()
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, num_pages=2,
                                         max_seq_len=64)
        free0 = cb.pool.free_count
        ok, too_big = [3, 4, 5], list(range(2, 30))
        with pytest.raises(ValueError, match="pool"):
            cb.generate([ok, too_big], max_new_tokens=8)
        assert cb.pool.free_count == free0
        out = cb.generate([ok, too_big, ok], max_new_tokens=8,
                          strict=False)
        assert out[1] == []
        assert cb.last_status[1] == "rejected_over_pool_capacity"
        assert len(out[0]) == 8 and len(out[2]) == 8
        assert cb.pool.free_count == free0


class TestRaggedMetaBuilder:
    def test_matches_from_scratch_flatten_through_kernel(self):
        """The incrementally maintained segment layout must drive the
        ragged varq kernel (one query a slot) to the same output as
        build_ragged_meta's compact layout, across admissions,
        page-boundary advances, and evictions."""
        import jax.numpy as jnp
        from paddle_tpu.framework.flags import set_flags, get_flags
        old = get_flags(["use_pallas_kernels", "pallas_interpret"])
        set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
        try:
            from paddle_tpu.kernels.paged_attention import (
                RaggedMetaBuilder, build_ragged_meta,
                paged_attention_ragged_varq)
            rs = np.random.RandomState(2)
            B, H, D, page, pps = 3, 8, 128, 8, 4
            P = B * pps + 1
            trash = P - 1
            kp = jnp.asarray(rs.randn(P, page, H, D).astype("f") * 0.3)
            vp = jnp.asarray(rs.randn(P, page, H, D).astype("f") * 0.3)
            builder = RaggedMetaBuilder(B, pps, page, trash)
            tables = np.full((B, pps), trash, np.int32)
            lens = np.ones((B,), np.int32)
            for b in range(B):
                builder.clear_slot(b)

            def check():
                q = jnp.asarray(rs.randn(B, 1, H, D).astype("f") * 0.3)
                ones = jnp.ones(B, jnp.int32)
                m1 = builder.meta()
                m2 = build_ragged_meta(tables, lens, page,
                                       bucket_to=B * pps)
                o1 = paged_attention_ragged_varq(
                    q, kp, vp, jnp.asarray(lens), ones,
                    {k: v.copy() for k, v in m1.items()})
                o2 = paged_attention_ragged_varq(
                    q, kp, vp, jnp.asarray(lens), ones, m2)
                np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                           atol=1e-5)

            # admission of slots 0 and 2
            tables[0, :3] = [1, 2, 3]
            lens[0] = 18
            builder.set_slot(0, tables[0], 18)
            tables[2, :2] = [4, 5]
            lens[2] = 9
            builder.set_slot(2, tables[2], 9)
            check()
            # decode advances crossing a page boundary on slot 2
            for post in (10, 16, 17):
                lens[2] = post
                builder.advance_slot(2, post)
                check()
            # eviction of slot 0 back to the dummy row
            tables[0, :] = trash
            lens[0] = 1
            builder.clear_slot(0)
            check()
        finally:
            set_flags({k.removeprefix("FLAGS_"): v for k, v in old.items()})


class TestServingSeriesReachTheSink:
    def test_prefill_and_prefix_series_in_jsonl(self, tmp_path):
        """A serve run with a shared prefix leaves the series the
        report tools and autotune read in the JSONL sink: prefill and
        first-token histograms, prefix-cache hits and misses."""
        import json
        import paddle_tpu.observability as obs
        from paddle_tpu.inference import ContinuousBatchingPredictor
        from paddle_tpu.observability import runtime as obs_rt
        model = _model()
        rng = np.random.RandomState(0)
        shared = rng.randint(2, 256, (8,)).tolist()
        prompts = [shared + rng.randint(2, 256, (n,)).tolist()
                   for n in (5, 9)] + [rng.randint(2, 256, (7,)).tolist()]
        path = str(tmp_path / "serve.jsonl")
        was = obs.enabled()
        obs.enabled(True)
        obs.get_registry().reset()
        obs.configure(path)
        try:
            cb = ContinuousBatchingPredictor(model, max_batch_size=1,
                                             page_size=8, max_seq_len=64)
            outs = cb.generate(prompts, max_new_tokens=3)
            obs_rt.maybe_export()
        finally:
            obs.configure(None)
            obs.enabled(was)
        assert [len(o) for o in outs] == [3, 3, 3]
        last = {}
        for line in open(path):
            rec = json.loads(line)
            if rec.get("name"):
                last[rec["name"]] = rec
        assert last["serving.prefill_seconds"]["count"] >= 2
        assert last["serving.ttft_seconds"]["count"] == 3
        assert last["serving.prefix_cache_hits"]["value"] \
            == cb.stats["prefix_hits"] + cb.stats["prefix_partial_hits"]
        assert last["serving.prefix_cache_hits"]["value"] >= 1
        assert last["serving.prefix_cache_misses"]["value"] >= 1


class TestAreaPoolingPrecision:
    def test_long_axis_offset_signal(self):
        """ADVICE r5 #3: adaptive 'area' pooling must keep per-cell
        precision independent of axis length — a 64k axis riding a big
        DC offset stays at fp32 accuracy (the old full-axis cumsum
        difference lost ~3 decimal digits here)."""
        import paddle_tpu.nn.functional as F
        s, out_len = 1 << 16, 7
        x = (np.random.RandomState(0).randn(1, 1, s).astype(np.float32)
             + 1000.0)
        out = F.interpolate(paddle.to_tensor(x), size=[out_len],
                            mode="area", data_format="NCW").numpy()
        xf = x.astype(np.float64)[0, 0]
        ref = [xf[(o * s) // out_len: -((-(o + 1) * s) // out_len)].mean()
               for o in range(out_len)]
        np.testing.assert_allclose(out[0, 0], np.asarray(ref), atol=2e-4)

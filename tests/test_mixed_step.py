"""Unified mixed prefill+decode step — the PR-11 acceptance suite.

Covers:
- the variable-query ragged paged-attention kernel: interpret-mode
  Pallas parity vs the XLA reference over mixed chunk/decode spans,
  and single-token spans bitwise-identical to the existing decode
  kernel (the mixed program must not perturb pure decode);
- RaggedMetaBuilder edge cases: advance_slot crossing a page boundary
  at exactly pages_per_seq, clear_slot-then-reuse, and
  build_ragged_meta bucket rounding;
- chunked prefill through ContinuousBatchingPredictor: greedy output
  token-identical to the unchunked path (XLA and interpret-mode ragged
  routes), chunk telemetry (span events + stats), TTFT measured at the
  first token (not admission), and page accounting on mid-ingest
  eviction;
- the Pallas-fallback observability counter
  (kernels.pallas_fallbacks{kernel,reason});
- a bundle built with chunked prefill serves a chunked prompt at warm
  start without compiling, whichever way its buckets were captured.
"""
import numpy as np
import pytest

import paddle_tpu as paddle


def _model(**kw):
    paddle.seed(0)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny(**kw))


def _interpret_flags():
    from paddle_tpu.framework.flags import set_flags, get_flags
    old = get_flags(["use_pallas_kernels", "pallas_interpret"])
    set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
    return old


def _restore_flags(old):
    from paddle_tpu.framework.flags import set_flags
    set_flags({k.removeprefix("FLAGS_"): v for k, v in old.items()})


class TestVarqKernel:
    def _setup(self, rs, B=3, H=8, D=128, page=8, pps=6):
        import jax.numpy as jnp
        P = B * pps + 1
        trash = P - 1
        kp = jnp.asarray(rs.randn(P, page, H, D).astype("f") * 0.3)
        vp = jnp.asarray(rs.randn(P, page, H, D).astype("f") * 0.3)
        tables = np.full((B, pps), trash, np.int32)
        tables[0, :4] = [0, 1, 2, 3]
        tables[1, :2] = [4, 5]
        tables[2, :3] = [6, 7, 8]
        return kp, vp, tables, trash

    def test_interpret_parity_vs_xla_reference(self):
        """Mixed spans (a 2-page chunk, a decode token, a mid-page
        chunk) through the interpret-mode Pallas kernel must match the
        XLA reference, including padding-query and tail-page masking."""
        import jax.numpy as jnp
        old = _interpret_flags()
        try:
            from paddle_tpu.kernels.paged_attention import (
                paged_attention_varq, paged_attention_ragged_varq,
                build_ragged_meta)
            rs = np.random.RandomState(0)
            kp, vp, tables, _ = self._setup(rs)
            B, Qb = 3, 16
            q = jnp.asarray(rs.randn(B, Qb, 8, 128).astype("f") * 0.3)
            kv_lens = np.asarray([30, 9, 17], np.int32)
            q_lens = np.asarray([16, 1, 5], np.int32)
            meta = build_ragged_meta(tables, kv_lens, 8, bucket_to=24)
            o_ref = paged_attention_varq(q, kp, vp, jnp.asarray(tables),
                                         kv_lens, q_lens)
            o_krn = paged_attention_ragged_varq(q, kp, vp, kv_lens,
                                                q_lens, meta)
            np.testing.assert_allclose(np.asarray(o_krn),
                                       np.asarray(o_ref), atol=2e-6)
            # padding query rows are zeroed (slot 1: rows 1.., slot 2:
            # rows 5..)
            assert float(np.abs(np.asarray(o_krn)[1, 1:]).max()) == 0.0
            assert float(np.abs(np.asarray(o_krn)[2, 5:]).max()) == 0.0
        finally:
            _restore_flags(old)

    def test_single_token_spans_match_decode_kernel(self):
        """q_lens == 1 everywhere degenerates to single-token decode
        attention. The block-table decode kernel contracts a block of
        pages on the MXU, so the two agree to float32 rounding."""
        import jax.numpy as jnp
        old = _interpret_flags()
        try:
            from paddle_tpu.kernels.paged_attention import (
                paged_attention, paged_attention_ragged_varq,
                RaggedMetaBuilder)
            rs = np.random.RandomState(1)
            kp, vp, tables, trash = self._setup(rs)
            B = 3
            q = jnp.asarray(rs.randn(B, 1, 8, 128).astype("f") * 0.3)
            kv_lens = np.asarray([30, 9, 17], np.int32)
            ones = np.ones((B,), np.int32)
            builder = RaggedMetaBuilder(B, 6, 8, trash)
            for b in range(B):
                builder.set_slot(b, tables[b], int(kv_lens[b]))
            o_v = paged_attention_ragged_varq(q, kp, vp, kv_lens, ones,
                                              builder.meta())
            o_bt = paged_attention(q[:, 0], kp, vp, jnp.asarray(tables),
                                   kv_lens)
            np.testing.assert_allclose(np.asarray(o_bt),
                                       np.asarray(o_v)[:, 0],
                                       rtol=0, atol=1e-6)
        finally:
            _restore_flags(old)

    def test_xla_gqa_and_fallback_counter(self):
        """GQA rides the XLA varq path; a wanted-but-lost Pallas fast
        path is counted in kernels.pallas_fallbacks{kernel,reason}."""
        import jax.numpy as jnp
        import paddle_tpu.observability as obs
        from paddle_tpu.observability import metrics as obsm
        old = _interpret_flags()
        was = obs.enabled()
        obs.enabled(True)
        reg = obs.get_registry()
        reg.reset()
        try:
            from paddle_tpu.kernels.paged_attention import (
                paged_attention_varq, paged_attention_ragged_varq,
                build_ragged_meta)
            rs = np.random.RandomState(2)
            B, H, Hkv, D, page, pps = 2, 4, 2, 16, 4, 3
            P = B * pps + 1
            kp = jnp.asarray(rs.randn(P, page, Hkv, D).astype("f"))
            vp = jnp.asarray(rs.randn(P, page, Hkv, D).astype("f"))
            tables = np.full((B, pps), P - 1, np.int32)
            tables[0, :2] = [0, 1]
            tables[1, :1] = [2]
            kv_lens = np.asarray([6, 3], np.int32)
            q_lens = np.asarray([2, 1], np.int32)
            q = jnp.asarray(rs.randn(B, 4, H, D).astype("f"))
            out = paged_attention_varq(q, kp, vp, jnp.asarray(tables),
                                       kv_lens, q_lens)
            assert out.shape == (B, 4, H, D)
            # ragged entry falls back (gqa + tiling) onto the XLA path
            meta = build_ragged_meta(tables, kv_lens, page,
                                     bucket_to=B * pps)
            out2 = paged_attention_ragged_varq(
                q, kp, vp, kv_lens, q_lens, meta,
                block_tables=jnp.asarray(tables))
            np.testing.assert_allclose(np.asarray(out2),
                                       np.asarray(out), atol=1e-6)
            m = reg.get("kernels.pallas_fallbacks")
            assert m is not None
            labels = {(s.labels.get("kernel"), s.labels.get("reason"))
                      for s in m.samples()}
            assert ("paged_attention_ragged_varq", "gqa_ratio") in labels
            # without block tables the lost fast path is a hard error,
            # not silently-wrong output
            with pytest.raises(ValueError, match="block_tables"):
                paged_attention_ragged_varq(q, kp, vp, kv_lens, q_lens,
                                            meta)
        finally:
            _restore_flags(old)
            obs.enabled(was)
            obsm.get_registry().reset()


class TestRaggedMetaBuilderEdges:
    def _check_equal(self, builder, tables, lens, page, pps):
        from paddle_tpu.kernels.paged_attention import build_ragged_meta
        m1 = builder.meta()
        m2 = build_ragged_meta(tables, lens, page,
                               bucket_to=tables.shape[0] * pps)
        # the two layouts differ (fixed segments vs compact), but per
        # slot the VALID (page, ordinal, first, last) sets must agree
        def rows(m):
            out = {}
            for i in range(len(m["seq"])):
                if m["valid"][i]:
                    out.setdefault(int(m["seq"][i]), []).append(
                        (int(m["page"][i]), int(m["ordinal"][i]),
                         int(m["first"][i]), int(m["last"][i])))
            return out
        assert rows(m1) == rows(m2)

    def test_advance_to_exactly_full_table(self):
        """advance_slot crossing its LAST page boundary (post_len lands
        on pages_per_seq * page exactly): the final entry flips to
        last=1 and the padding-alias rewrite degenerates to an empty
        slice instead of walking off the segment."""
        from paddle_tpu.kernels.paged_attention import RaggedMetaBuilder
        page, pps = 4, 3
        builder = RaggedMetaBuilder(2, pps, page, trash_page=9)
        tables = np.full((2, pps), 9, np.int32)
        tables[0] = [1, 2, 3]
        lens = np.ones((2,), np.int32)
        builder.clear_slot(0)
        builder.clear_slot(1)
        builder.set_slot(0, tables[0], 5)          # 2 pages
        for post in (8, 9, 12):                    # 2 → 3 pages → full
            lens[0] = post
            builder.advance_slot(0, post)
            self._check_equal(builder, tables, lens, page, pps)
        m = builder.meta()
        seg = slice(0, pps)
        assert list(m["valid"][seg]) == [1, 1, 1]
        assert list(m["last"][seg]) == [0, 0, 1]
        assert list(m["page"][seg]) == [1, 2, 3]

    def test_clear_slot_then_reuse(self):
        """clear_slot parks the segment on the trash page (one valid
        entry); a later set_slot rebuilds it for a new request with no
        residue from the old one."""
        from paddle_tpu.kernels.paged_attention import RaggedMetaBuilder
        page, pps = 4, 3
        builder = RaggedMetaBuilder(1, pps, page, trash_page=7)
        t1 = np.asarray([4, 5, 6], np.int32)
        builder.set_slot(0, t1, 11)
        builder.clear_slot(0)
        m = builder.meta()
        assert list(m["valid"]) == [1, 0, 0]
        assert set(m["page"].tolist()) == {7}       # all trash-aliased
        assert list(m["first"])[0] == 1 and list(m["last"])[0] == 1
        t2 = np.asarray([2, 1, 7], np.int32)
        builder.set_slot(0, t2, 6)                  # 2 pages
        m = builder.meta()
        assert list(m["valid"]) == [1, 1, 0]
        assert list(m["page"]) == [2, 1, 1]         # pad aliases last
        assert list(m["last"]) == [0, 1, 0]

    def test_build_ragged_meta_bucket_rounding(self):
        """Default bucketing rounds the flat entry count up to a power
        of two (>= 8) so serving steps reuse one compiled kernel;
        overflowing an explicit bucket raises."""
        from paddle_tpu.kernels.paged_attention import build_ragged_meta
        tables = np.asarray([[0, 1, 2], [3, 9, 9]], np.int32)
        lens = np.asarray([12, 4], np.int32)        # 3 + 1 pages
        m = build_ragged_meta(tables, lens, 4)
        assert len(m["seq"]) == 8                   # 4 entries → 8
        assert m["valid"].sum() == 4
        big = build_ragged_meta(tables, np.asarray([12, 12]), 4)
        assert len(big["seq"]) == 8                 # 6 entries → 8
        m16 = build_ragged_meta(tables, lens, 4, bucket_to=16)
        assert len(m16["seq"]) == 16
        # padding aliases the LAST real entry, never a live page of
        # another slot's row 0
        assert m16["page"][m16["valid"].sum():].tolist() == [3] * 12
        with pytest.raises(ValueError, match="exceed"):
            build_ragged_meta(tables, np.asarray([12, 12]), 4,
                              bucket_to=4)


class TestChunkedPrefill:
    def test_parity_with_unchunked_and_telemetry(self):
        """Chunked-prefill generation is token-identical to unchunked
        greedy decode; chunk stats/span events record the ingest."""
        import paddle_tpu.observability as obs
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model()
        rng = np.random.RandomState(3)
        prompts = [rng.randint(2, 256, (n,)).tolist()
                   for n in (40, 5, 23, 9)]
        cb0 = ContinuousBatchingPredictor(model, max_batch_size=3,
                                          page_size=8, max_seq_len=128,
                                          enable_prefix_cache=False)
        ref = cb0.generate(prompts, max_new_tokens=8)
        was = obs.enabled()
        obs.enabled(True)
        try:
            from paddle_tpu.observability import tracing as obstr
            rec = obstr.flight_recorder()
            rec.clear()
            cb1 = ContinuousBatchingPredictor(
                model, max_batch_size=3, page_size=8, max_seq_len=128,
                enable_prefix_cache=False, prefill_chunk_tokens=16)
            out = cb1.generate(prompts, max_new_tokens=8)
        finally:
            obs.enabled(was)
        assert out == ref
        assert cb1.stats["chunked_requests"] == 2     # 40 and 23 tokens
        assert cb1.stats["prefill_chunks"] >= 3
        assert cb1.stats["mixed_steps"] >= 2
        assert cb0.stats["mixed_steps"] == 0
        # span events: chunked requests carry prefill_chunk events whose
        # covered counts end at the prompt length, and first_token comes
        # AFTER the last chunk (TTFT decomposition, trace_report view)
        spans = [s for s in rec.spans() if s["name"] == "serve.request"]
        chunked = {}
        for s in spans:
            evs = s.get("events") or []
            chunks = [e for e in evs if e["name"] == "prefill_chunk"]
            if chunks:
                chunked[s["labels"]["prompt_len"]] = (s, chunks)
        assert set(chunked) == {40, 23}
        for plen, (s, chunks) in chunked.items():
            assert chunks[-1]["covered"] == plen
            assert sum(c["tokens"] for c in chunks) == plen
            ft = [e for e in s["events"] if e["name"] == "first_token"]
            assert ft and ft[0]["ts"] >= chunks[-1]["ts"]
            adm = [e for e in s["events"] if e["name"] == "admitted"]
            assert adm and adm[0].get("chunked") is True

    def test_parity_on_interpret_ragged_route(self):
        """The full mixed program through the interpret-mode Pallas
        varq kernel stays token-identical: an MHA predictor with
        chunked prefill keeps the metadata for its span programs, one
        without has none anywhere."""
        old = _interpret_flags()
        try:
            from paddle_tpu.inference import ContinuousBatchingPredictor
            model = _model(hidden_size=1024, num_attention_heads=8,
                           num_key_value_heads=8, intermediate_size=256,
                           num_hidden_layers=2)
            rng = np.random.RandomState(4)
            prompts = [rng.randint(2, 256, (n,)).tolist()
                       for n in (20, 4)]
            cb0 = ContinuousBatchingPredictor(
                model, max_batch_size=2, page_size=8, max_seq_len=64,
                enable_prefix_cache=False)
            assert not cb0.span_ragged
            ref = cb0.generate(prompts, max_new_tokens=4)
            cb1 = ContinuousBatchingPredictor(
                model, max_batch_size=2, page_size=8, max_seq_len=64,
                enable_prefix_cache=False, prefill_chunk_tokens=8)
            assert cb1.span_ragged
            out = cb1.generate(prompts, max_new_tokens=4)
            assert out == ref
            assert cb1.stats["chunked_requests"] == 1
        finally:
            _restore_flags(old)

    def test_padding_overflow_never_clobbers_full_table_writes(self):
        """A slot with a FULLY-allocated block table (no trash rows)
        whose padding span positions run past the table's end must not
        corrupt its pages: out-of-range padding writes are dropped,
        not clipped into the last real page where they would race the
        span's real K/V write (duplicate scatter indices have an
        unspecified winner)."""
        import jax.numpy as jnp
        from paddle_tpu.generation.kv_cache import (
            PagedCacheEntry, paged_cache_mixed_update_attend)
        B, page, pps, H, D = 1, 8, 4, 4, 16
        kp = jnp.zeros((pps, page, H, D), "float32")
        vp = jnp.zeros((pps, page, H, D), "float32")
        bt = jnp.asarray(np.arange(pps, dtype=np.int32)[None, :])
        cl = jnp.asarray(np.asarray([30], np.int32))
        ql = jnp.asarray(np.asarray([1], np.int32))
        qb = 16          # padding positions 31..45 overflow the table
        rs = np.random.RandomState(8)
        q = jnp.asarray(rs.randn(B, qb, H, D).astype("f"))
        k = jnp.asarray(rs.randn(B, qb, H, D).astype("f"))
        v = jnp.asarray(rs.randn(B, qb, H, D).astype("f"))
        entry = PagedCacheEntry(kp, vp, bt, cl, None, ql)
        out, new = paged_cache_mixed_update_attend(entry, q, k, v)
        # the single real write landed at position 30 = (page 3, off 6)
        np.testing.assert_array_equal(np.asarray(new.k_pages)[3, 6],
                                      np.asarray(k)[0, 0])
        np.testing.assert_array_equal(np.asarray(new.v_pages)[3, 6],
                                      np.asarray(v)[0, 0])
        # and nothing else in the pool was touched
        mask = np.ones((pps, page), bool)
        mask[3, 6] = False
        assert float(np.abs(np.asarray(new.k_pages)[mask]).max()) == 0.0
        assert float(np.abs(np.asarray(new.v_pages)[mask]).max()) == 0.0

    def test_mid_ingest_deadline_frees_pages(self):
        """A deadline firing while a prompt is mid-ingest evicts the
        slot and returns every reserved page to the pool."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model()
        rng = np.random.RandomState(5)
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, max_seq_len=128,
                                         enable_prefix_cache=False,
                                         prefill_chunk_tokens=16)
        free0 = cb.pool.free_count
        long_p = rng.randint(2, 256, (80,)).tolist()
        out = cb.generate([long_p], max_new_tokens=8,
                          deadline_s=[1e-4])
        assert out == [[]]
        assert cb.last_status == ["deadline"]
        assert cb.pool.free_count == free0
        # the predictor still serves normally afterwards
        ok = cb.generate([long_p[:5]], max_new_tokens=3)
        assert len(ok[0]) == 3
        assert cb.pool.free_count == free0

    def test_threshold_rounds_down_never_disables(self):
        """A mid-range threshold normalizes DOWN (it is a latency
        bound): prefill_chunk_tokens=40 on page 8 gives chunk_max 32,
        and chunking still triggers for prompts over it — the old
        round-UP could push the threshold past every servable prompt
        and silently disable the feature."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model()
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, max_seq_len=64,
                                         prefill_chunk_tokens=40)
        assert cb._chunk_max == 32
        rng = np.random.RandomState(6)
        prompt = rng.randint(2, 256, (40,)).tolist()
        ref = ContinuousBatchingPredictor(
            model, max_batch_size=2, page_size=8,
            max_seq_len=64).generate([prompt], max_new_tokens=3)
        assert cb.generate([prompt], max_new_tokens=3) == ref
        assert cb.stats["chunked_requests"] == 1

    def test_chunk_bucket_adaptivity(self):
        """The per-tick chunk bucket shrinks under decode load and
        collapses to the smallest covering bucket for final chunks."""
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = _model()
        cb = ContinuousBatchingPredictor(model, max_batch_size=4,
                                         page_size=8, max_seq_len=128,
                                         prefill_chunk_tokens=32)
        assert cb._chunk_max == 32
        assert cb._chunk_bucket(100, 0) == 32     # idle: full chunk
        assert cb._chunk_bucket(100, 1) == 16     # halved under load
        assert cb._chunk_bucket(100, 3) == 8      # floor: one page
        assert cb._chunk_bucket(9, 0) == 16       # smallest covering
        assert cb._chunk_bucket(1, 0) == 8        # page floor


class TestMixedBucketCapture:
    @pytest.mark.parametrize("max_seq_len,prompt_len", [
        # chunk_max 16, max_seq 18: the bucket-16 steering prompt
        # needs 17 + max_new > 18, so both buckets are compiled
        # directly with dispatch-shaped operands; a 17-token prompt is
        # still chunkable at serve time
        (18, 17),
        # room for the steering prompts: calibration traffic captures
        # the buckets, and a prompt of two chunks and a tail ingests
        (64, 33),
    ], ids=["tight-direct", "roomy-steered"])
    def test_chunked_bundle_serves_zero_compile(
            self, tmp_path, max_seq_len, prompt_len):
        """A bundle built with chunked prefill in its geometry holds
        every mixed bucket, whichever way the builder captured it: a
        warm-started predictor ingesting a chunked prompt hits the
        bundle with zero misses."""
        from paddle_tpu.inference import aot, ContinuousBatchingPredictor
        model = _model()
        geo = dict(max_batch_size=2, page_size=8, max_seq_len=max_seq_len,
                   prefill_chunk_tokens=16, enable_prefix_cache=False)
        d = str(tmp_path / "engine")
        manifest = aot.build_engine(model, d, prompt_buckets=(8,),
                                    batch_sizes=(1,), max_new_tokens=1,
                                    wire_cache=False, **geo)
        kinds = [rec.get("kind")
                 for rec in manifest["artifacts"].values()]
        assert kinds.count("mixed") == 2            # buckets 8 and 16
        pred, eng = aot.warm_start(model, d, wire_cache=False)
        rng = np.random.RandomState(7)
        prompt = rng.randint(2, 256, (prompt_len,)).tolist()
        out = pred.generate([prompt], max_new_tokens=1)
        ref = ContinuousBatchingPredictor(model, **geo).generate(
            [prompt], max_new_tokens=1)
        assert out == ref
        assert pred.stats["chunked_requests"] == 1
        assert eng.stats["misses"] == 0, eng.stats

"""Speculative decoding + on-device sampling — the acceptance suite.

Covers:
- the on-device sampling kernels (generation/sampling.py): batched
  temperature/top-k/top-p operands, counter-based seeded streams,
  temperature<=0 reducing to the raw argmax bitwise;
- `verify_spans`: greedy longest-accepted-prefix correctness (perfect/
  partial/zero drafts, q_lens==1 degenerating to plain decode) and the
  rejection-sampling acceptance rule preserving the target
  distribution for a deterministic drafter (statistical check);
- prompt-lookup drafting (`propose_ngram_drafts`);
- the serve loop: greedy speculative output BITWISE-identical to plain
  greedy decode (lossless acceptance, including forced full-reject
  ticks and eos-mid-span), multi-token StreamEvent spans, KV/pool/
  ragged-meta accounting back to baseline after rejected drafts and
  after mid-verify cancel/deadline eviction, in-graph K/V rollback of
  rejected positions (page contents restored byte-for-byte);
- on-device sampling through the serve loop: temperature=0
  token-identical to greedy, per-seed determinism, mixed greedy+
  sampled batches, and the cross-path regression — eager generate,
  static-cache generate, and the serve loop emit the SAME sampled
  stream for a fixed seed (the kernels are shared);
- router exactly-once delivery of multi-token span events across
  re-admissions (`RequestHandle._push_token`);
- `RaggedMetaBuilder.rollback_slot` (spec rewind == fresh set_slot);
- `tools/autotune.py propose_spec` fixtures (raise on high measured
  acceptance, disable on low, silent without data) and the
  RuntimeConfig spec/sampling fields (round trip, COMPILED_FIELDS);
- a bundle built with the spec + sampling program variants warm-starts
  without compiling, and `serve.spec.accept_rate` is accepted over
  proposed under the replica's label.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle


def _model():
    paddle.seed(0)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))


def _cyclic_prompts(vocab, n=3, length=20):
    """Tiled-motif prompts whose greedy continuation under
    paddle.seed(0) is (near-)cyclic — the repetitive workload where
    prompt lookup pays (the motif indices were found by a probe)."""
    rng = np.random.RandomState(0)
    motifs = [rng.randint(2, vocab, (3 + s % 4,)).tolist()
              for s in range(24)]
    return [(motifs[s] * (length // 3 + 1))[:length]
            for s in (2, 9, 16)][:n]


def _cb(model, **kw):
    from paddle_tpu.inference import ContinuousBatchingPredictor
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("enable_prefix_cache", False)
    return ContinuousBatchingPredictor(model, **kw)


def _pool_baseline(cb):
    """Free pages with nothing admitted: everything but the trash
    page."""
    if cb.prefix_cache is not None:
        cb.prefix_cache.clear(cb.pool)
    return len(cb.pool._free) == cb.pool.num_pages - 1


# ---------------------------------------------------------------------------
# sampling kernels
# ---------------------------------------------------------------------------
class TestSamplingKernels:
    def test_temp0_is_bitwise_argmax(self):
        import jax.numpy as jnp
        from paddle_tpu.generation import sampling as S
        rng = np.random.RandomState(0)
        logits = jnp.asarray(rng.randn(5, 64).astype(np.float32))
        tok, _ = S.sample_tokens(
            logits, np.zeros(5, np.float32), np.zeros(5, np.int32),
            np.ones(5, np.float32), np.arange(5, dtype=np.int32),
            np.zeros(5, np.int32))
        assert (np.asarray(tok)
                == np.asarray(jnp.argmax(logits, -1))).all()

    def test_counter_and_seed_drive_stream(self):
        from paddle_tpu.generation import sampling as S
        import jax.numpy as jnp
        B, V = 64, 500
        logits = jnp.zeros((B, V), jnp.float32)
        ones = np.ones(B, np.float32)
        zk = np.zeros(B, np.int32)
        a, _ = S.sample_tokens(logits, ones, zk, ones,
                               np.zeros(B, np.int32),
                               np.zeros(B, np.int32))
        a2, _ = S.sample_tokens(logits, ones, zk, ones,
                                np.zeros(B, np.int32),
                                np.zeros(B, np.int32))
        b, _ = S.sample_tokens(logits, ones, zk, ones,
                               np.zeros(B, np.int32),
                               np.ones(B, np.int32))
        c, _ = S.sample_tokens(logits, ones, zk, ones,
                               np.arange(B, dtype=np.int32),
                               np.zeros(B, np.int32))
        assert (np.asarray(a) == np.asarray(a2)).all()       # same key
        assert (np.asarray(a) != np.asarray(b)).any()        # counter
        assert len(set(np.asarray(c).tolist())) > B // 2     # seed

    def test_dynamic_topk_topp_match_static_filters(self):
        import jax.numpy as jnp
        from paddle_tpu.generation import sampling as S
        from paddle_tpu.generation import logits_process as LP
        rng = np.random.RandomState(1)
        lg = jnp.asarray(rng.randn(3, 32).astype(np.float32))
        # static LP filters now delegate; equivalence with per-row
        # operands (the serve loop's form)
        want_k = np.asarray(S.topk_mask(lg, np.full(3, 5, np.int32)))
        got_k = np.asarray(LP.top_k_filter(lg, 5))
        assert np.array_equal(want_k, got_k)
        want_p = np.asarray(S.topp_mask(lg, np.full(3, 0.7, np.float32)))
        got_p = np.asarray(LP.top_p_filter(lg, 0.7))
        assert np.array_equal(want_p, got_p)
        # disabled knobs are identity
        assert np.array_equal(
            np.asarray(S.topk_mask(lg, np.zeros(3, np.int32))),
            np.asarray(lg))
        assert np.array_equal(
            np.asarray(S.topp_mask(lg, np.ones(3, np.float32))),
            np.asarray(lg))

    def test_fused_pipeline_matches_sequential_filters(self):
        """processed_logits computes both filters off ONE sort; it
        must equal the sequential topk-then-topp composition (random
        float logits: no exact ties)."""
        import jax.numpy as jnp
        from paddle_tpu.generation import sampling as S
        rng = np.random.RandomState(3)
        lg = jnp.asarray(rng.randn(6, 64).astype(np.float32))
        temp = np.asarray([1.0, 0.7, 1.3, 1.0, 0.5, 1.0], np.float32)
        topk = np.asarray([0, 5, 1, 64, 7, 0], np.int32)
        topp = np.asarray([1.0, 0.8, 0.5, 0.9, 1.0, 0.3], np.float32)
        got = np.asarray(S.processed_logits(lg, temp, topk, topp))
        scaled = lg / jnp.where(temp <= 0, 1.0,
                                jnp.maximum(temp, 1e-6))[:, None]
        want = np.asarray(S.topp_mask(S.topk_mask(scaled, topk), topp))
        assert np.array_equal(got, want)

    def test_verify_spans_greedy(self):
        import jax.numpy as jnp
        from paddle_tpu.generation import sampling as S
        rng = np.random.RandomState(0)
        B, Qb, V = 4, 5, 64
        lg = jnp.asarray(rng.randn(B, Qb, V).astype(np.float32))
        g = np.asarray(jnp.argmax(lg, -1))
        span = np.zeros((B, Qb), np.int32)
        span[:, 1:] = g[:, :-1]                  # perfect drafts
        zt = np.zeros(B, np.float32)
        zk = np.zeros(B, np.int32)
        op = np.ones(B, np.float32)
        full = np.full(B, Qb, np.int32)
        for sampled_mode in (False, True):
            acc, bon = S.verify_spans(lg, span, full, zt, zk, op, zk,
                                      zk, sampled_mode=sampled_mode)
            assert (np.asarray(acc) == Qb - 1).all()
            assert (np.asarray(bon) == g[:, -1]).all()
            # reject at draft position 1 -> accepted 1, bonus = argmax
            s2 = span.copy()
            s2[:, 2] = (g[:, 1] + 1) % V
            acc2, bon2 = S.verify_spans(lg, s2, full, zt, zk, op, zk,
                                        zk, sampled_mode=sampled_mode)
            assert (np.asarray(acc2) == 1).all()
            assert (np.asarray(bon2) == g[:, 1]).all()
            # no drafts: plain decode tick
            acc3, bon3 = S.verify_spans(lg, span, np.ones(B, np.int32),
                                        zt, zk, op, zk, zk,
                                        sampled_mode=sampled_mode)
            assert (np.asarray(acc3) == 0).all()
            assert (np.asarray(bon3) == g[:, 0]).all()

    def test_rejection_sampling_preserves_target_distribution(self):
        """The accepted-draft-or-residual-bonus rule with a
        deterministic drafter must emit the first token distributed
        exactly as the target distribution p: P(tok) = p(d)·1[tok=d] +
        (1 - p(d))·residual(tok)."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.generation import sampling as S
        Bs, V = 8000, 4
        row = np.array([2.0, 1.0, 0.5, -1.0], np.float32)
        lgs = jnp.asarray(np.tile(row, (Bs, 1))[:, None, :])
        lgs = jnp.concatenate([lgs, lgs], axis=1)      # Qb = 2
        p = np.asarray(jax.nn.softmax(jnp.asarray(row)))
        span = np.zeros((Bs, 2), np.int32)             # draft token 0
        acc, bon = S.verify_spans(
            lgs, span, np.full(Bs, 2, np.int32),
            np.ones(Bs, np.float32), np.zeros(Bs, np.int32),
            np.ones(Bs, np.float32),
            np.arange(Bs, dtype=np.int32), np.zeros(Bs, np.int32))
        first = np.where(np.asarray(acc) >= 1, 0, np.asarray(bon))
        emp = np.bincount(first, minlength=V) / Bs
        assert np.abs(emp - p).max() < 0.03, (emp.tolist(), p.tolist())

    def test_propose_ngram_drafts(self):
        from paddle_tpu.generation.sampling import propose_ngram_drafts
        h = [1, 2, 3, 4, 5, 1, 2, 3]
        assert propose_ngram_drafts(h, 3) == [4, 5, 1]
        assert propose_ngram_drafts(h, 1) == [4]
        assert propose_ngram_drafts([7, 8, 9], 3) == []   # no match
        assert propose_ngram_drafts(h, 0) == []
        # most RECENT earlier occurrence wins
        h2 = [1, 2, 9, 1, 2, 7, 1, 2]
        assert propose_ngram_drafts(h2, 2) == [7, 1]


# ---------------------------------------------------------------------------
# RaggedMetaBuilder rollback
# ---------------------------------------------------------------------------
class TestRollbackSlot:
    def test_rollback_equals_fresh_set_slot(self):
        from paddle_tpu.kernels.paged_attention import RaggedMetaBuilder
        a = RaggedMetaBuilder(2, 4, 8, trash_page=0)
        b = RaggedMetaBuilder(2, 4, 8, trash_page=0)
        row = np.asarray([3, 5, 7, 9], np.int32)
        a.set_slot(1, row, 9)
        b.set_slot(1, row, 9)
        # optimistic span advance (spec dispatch) then rewind to the
        # accepted prefix must equal never having advanced
        a.advance_slot(1, 9 + 5)
        a.rollback_slot(1, 11)
        b.set_slot(1, row, 11)
        for k in RaggedMetaBuilder.FIELDS:
            assert np.array_equal(a.meta()[k], b.meta()[k]), k


# ---------------------------------------------------------------------------
# serve loop: speculative decoding
# ---------------------------------------------------------------------------
class TestSpecServeLoop:
    def test_greedy_spec_bitwise_parity_and_multitoken_steps(self):
        m = _model()
        prompts = _cyclic_prompts(m.config.vocab_size)
        ref_cb = _cb(m)
        ref = ref_cb.generate(prompts, max_new_tokens=24)
        cb = _cb(m, spec_draft_tokens=4)
        out = cb.generate(prompts, max_new_tokens=24)
        assert out == ref                       # lossless acceptance
        # more than one drafted token committed a verify step, on average
        assert cb.stats["spec_accepted"] > cb.stats["decode_steps"]
        assert cb.stats["decode_steps"] < ref_cb.stats["decode_steps"]
        assert _pool_baseline(cb)               # pages back after rejects

    def test_full_reject_ticks_stay_correct(self, monkeypatch):
        """Garbage drafts (forced) are all rejected on device: output
        must STILL equal plain greedy (verification self-corrects) and
        the pool must return to baseline — the K/V the junk drafts
        wrote was rolled back / never attended."""
        from paddle_tpu.generation import sampling as S
        m = _model()
        prompts = _cyclic_prompts(m.config.vocab_size)
        ref = _cb(m).generate(prompts, max_new_tokens=12)
        monkeypatch.setattr(S, "propose_ngram_drafts",
                            lambda h, k, ngram_max=3, window=4096:
                            [1] * k if k > 0 else [])
        cb = _cb(m, spec_draft_tokens=3)
        out = cb.generate(prompts, max_new_tokens=12)
        assert out == ref
        assert cb.stats["spec_proposed"] > 0
        # near-total rejection (token 1 is almost never the argmax)
        assert cb.stats["spec_accepted"] <= cb.stats["spec_proposed"] / 4
        assert _pool_baseline(cb)

    def test_in_graph_rollback_restores_page_contents(self, monkeypatch):
        """Rejected span positions' K/V must be restored byte-for-byte:
        run one prompt greedy, snapshot the pool, then replay with
        forced-garbage drafts — the pages must match the no-spec run
        wherever the committed tokens live (rollback erased the junk
        writes)."""
        from paddle_tpu.generation import sampling as S
        m = _model()
        prompts = _cyclic_prompts(m.config.vocab_size, n=1)
        cb_a = _cb(m, max_batch_size=1)
        out_a = cb_a.generate(prompts, max_new_tokens=8)
        monkeypatch.setattr(S, "propose_ngram_drafts",
                            lambda h, k, ngram_max=3, window=4096:
                            [1] * k if k > 0 else [])
        cb_b = _cb(m, max_batch_size=1, spec_draft_tokens=3)
        out_b = cb_b.generate(prompts, max_new_tokens=8)
        assert out_b == out_a
        # same allocator, same order -> same page ids; committed region
        # = prompt + generated tokens (the last generated token's K/V
        # is never written — it was the final emitted bonus)
        L = len(prompts[0]) + len(out_a[0]) - 1
        ka = np.asarray(cb_a.pool.k[0]).reshape(
            cb_a.pool.num_pages, cb_a.pool.page_size, -1)
        kb = np.asarray(cb_b.pool.k[0]).reshape(
            cb_b.pool.num_pages, cb_b.pool.page_size, -1)
        flat_a = ka.reshape(-1, ka.shape[-1])
        flat_b = kb.reshape(-1, kb.shape[-1])
        # compare the pages the request owned (ids 1..need, allocated
        # in order after the trash page 0)
        page = cb_a.pool.page_size
        used = [(p, o) for p in range(1, -(-L // page) + 1)
                for o in range(page)][:L]
        for p, o in used:
            idx = p * page + o
            assert np.array_equal(flat_a[idx], flat_b[idx]), (p, o)

    def test_eos_inside_span_strips_and_evicts(self):
        m = _model()
        prompts = _cyclic_prompts(m.config.vocab_size, n=2)
        base = _cb(m).generate(prompts, max_new_tokens=24)
        # pick an eos that greedy decode actually emits mid-stream
        eos = base[0][5]
        ref = _cb(m, eos_token_id=eos).generate(prompts,
                                                max_new_tokens=24)
        cb = _cb(m, eos_token_id=eos, spec_draft_tokens=4)
        out = cb.generate(prompts, max_new_tokens=24)
        assert out == ref
        assert _pool_baseline(cb)

    def test_mid_verify_cancel_and_deadline_free_pages(self):
        from paddle_tpu.serving.streaming import ServeRequest
        m = _model()
        prompts = _cyclic_prompts(m.config.vocab_size, n=2)
        cb = _cb(m, spec_draft_tokens=4)
        # cancel mid-decode (spec ticks in flight)
        stream = cb.generate_stream(prompts, max_new_tokens=64)
        seen = 0
        for ev in stream:
            if ev.kind == "token":
                seen += 1
                if seen >= 2:
                    stream.cancel(0)
                    stream.cancel(1)
        assert all(s in ("cancelled", "ok") for s in cb.last_status)
        assert _pool_baseline(cb)
        # deadline expiry mid-verify
        cb2 = _cb(m, spec_draft_tokens=4)
        outs = cb2.generate(prompts, max_new_tokens=64,
                            deadline_s=0.05)
        assert cb2.last_status.count("deadline") >= 1 \
            or cb2.last_status.count("ok") == len(prompts)
        assert _pool_baseline(cb2)

    def test_spec_and_sampling_with_chunked_prefill(self):
        """Interplay with chunked prefill: spec ticks pause while a
        chunk ingests (mixed ticks) and resume after, greedy output
        stays chunk+spec == plain; a sampled decode slot PAUSES during
        ingest ticks (the mixed program is argmax-only) and the greedy
        chunked row is unperturbed; a sampled CHUNKED request draws
        its first token via replay after the final chunk."""
        from paddle_tpu.generation.sampling import SamplingParams
        m = _model()
        rng = np.random.RandomState(0)
        motifs = [rng.randint(2, m.config.vocab_size,
                              (3 + s % 4,)).tolist() for s in range(24)]
        long_p = (motifs[2] * 30)[:70]
        short = (motifs[9] * 8)[:20]
        ref = _cb(m, max_seq_len=256).generate([long_p, short],
                                               max_new_tokens=20)
        cb = _cb(m, max_seq_len=256, prefill_chunk_tokens=16,
                 spec_draft_tokens=4)
        out = cb.generate([long_p, short], max_new_tokens=20)
        assert out == ref
        assert cb.stats["prefill_chunks"] > 0
        assert cb.stats["spec_ticks"] > 0
        assert _pool_baseline(cb)
        cb2 = _cb(m, max_seq_len=256, prefill_chunk_tokens=16,
                  sampling_enabled=True)
        cb_plain = _cb(m, max_seq_len=256, sampling_enabled=True)
        sp = SamplingParams(temperature=0.9, seed=4)
        a = cb2.generate([long_p, short], max_new_tokens=20,
                         sampling=[None, sp])
        b = cb2.generate([long_p, short], max_new_tokens=20,
                         sampling=[None, sp])
        assert a == b and a[0] == ref[0] and len(a[1]) == 20
        # a sampled request PAUSED during the neighbor's chunk-ingest
        # ticks must emit the SAME stream it emits served alone (the
        # pause may not consume counters or chain the mixed argmax)
        alone = cb_plain.generate([short], max_new_tokens=20,
                                  sampling=sp)
        assert a[1] == alone[0]
        # a sampled CHUNKED request must emit the same stream as the
        # unchunked sampled path (first token via replay, counter 0)
        c = cb2.generate([long_p], max_new_tokens=10, sampling=sp)
        d = cb2.generate([long_p], max_new_tokens=10, sampling=sp)
        assert c == d and len(c[0]) == 10
        un = cb_plain.generate([long_p], max_new_tokens=10, sampling=sp)
        assert c == un
        assert _pool_baseline(cb2)

    def test_multitoken_stream_events(self):
        m = _model()
        prompts = _cyclic_prompts(m.config.vocab_size, n=2)
        cb = _cb(m, spec_draft_tokens=4)
        stream = cb.generate_stream(prompts, max_new_tokens=24)
        spans = {0: [], 1: []}
        max_index = {0: 0, 1: 0}
        multi = 0
        for ev in stream:
            if ev.kind != "token":
                continue
            toks = list(ev.span) or [ev.token]
            assert ev.token == toks[-1]
            # index is the LAST token's 1-based ordinal; spans are
            # contiguous and in order
            assert ev.index - len(toks) == max_index[ev.request]
            max_index[ev.request] = ev.index
            spans[ev.request].extend(toks)
            if len(toks) > 1:
                multi += 1
        assert multi > 0                      # spec ticks batched tokens
        for r in (0, 1):
            assert spans[r] == stream.results[r]

    def test_ngram_verify_resolves_before_the_next_is_dispatched(self):
        """The n-gram drafter looks its drafts up in the host's token
        histories: every step is resolved before the next is drafted
        and dispatched (a model's own drafter, part of the tick's
        program, is pipelined instead: tests/test_openpangu_moe.py)."""
        m = _model()
        cb = _cb(m, spec_draft_tokens=4)
        order = []

        def logged(name, mark):
            real = getattr(cb, name)

            def call(*args, **kw):
                order.append(mark)
                return real(*args, **kw)

            setattr(cb, name, call)

        logged("_dispatch_spec_step", "D")
        logged("_resolve_spec_step", "R")
        logged("_resolve_step", "R")        # a tick that drew no drafts
        cb.generate(_cyclic_prompts(m.config.vocab_size),
                    max_new_tokens=24)
        assert "".join(order) == "DR" * cb.stats["decode_steps"]
        assert cb.stats["spec_ticks"] > 0
        assert cb.stats["spec_ticks_chained"] == 0


# ---------------------------------------------------------------------------
# serve loop: on-device sampling
# ---------------------------------------------------------------------------
class TestSamplingServeLoop:
    def test_temp0_token_identical_to_greedy(self):
        from paddle_tpu.generation.sampling import SamplingParams
        m = _model()
        prompts = _cyclic_prompts(m.config.vocab_size)
        ref = _cb(m).generate(prompts, max_new_tokens=12)
        cb = _cb(m, sampling_enabled=True)
        out = cb.generate(prompts, max_new_tokens=12,
                          sampling=SamplingParams(temperature=0.0))
        assert out == ref

    def test_sampled_deterministic_and_seed_sensitive(self):
        from paddle_tpu.generation.sampling import SamplingParams
        m = _model()
        prompts = _cyclic_prompts(m.config.vocab_size)
        cb = _cb(m, sampling_enabled=True)
        sp = SamplingParams(temperature=0.9, top_k=20, seed=11)
        a = cb.generate(prompts, max_new_tokens=12, sampling=sp)
        b = cb.generate(prompts, max_new_tokens=12, sampling=sp)
        c = cb.generate(prompts, max_new_tokens=12,
                        sampling=SamplingParams(temperature=0.9,
                                                top_k=20, seed=12))
        assert a == b
        assert a != c
        assert _pool_baseline(cb)

    def test_mixed_greedy_sampled_batch(self):
        from paddle_tpu.generation.sampling import SamplingParams
        m = _model()
        prompts = _cyclic_prompts(m.config.vocab_size)
        ref = _cb(m).generate(prompts, max_new_tokens=12)
        cb = _cb(m, sampling_enabled=True)
        mix = [None, SamplingParams(temperature=0.8, seed=3),
               SamplingParams(temperature=0.0)]
        out = cb.generate(prompts, max_new_tokens=12, sampling=mix)
        assert out[0] == ref[0]              # greedy rows untouched
        assert out[2] == ref[2]

    def test_sampling_disabled_predictor_rejects(self):
        from paddle_tpu.generation.sampling import SamplingParams
        m = _model()
        prompts = _cyclic_prompts(m.config.vocab_size, n=1)
        cb = _cb(m)
        with pytest.raises(ValueError, match="sampling_enabled"):
            cb.generate(prompts, max_new_tokens=4,
                        sampling=SamplingParams(temperature=0.8))

    def test_eager_static_serve_sampled_parity(self):
        """THE cross-path regression: a fixed seed yields the same
        sampled stream through model.generate (static cache), the
        eager fallback, and the serve loop — the kernels and the
        counter-based key streams are shared."""
        from paddle_tpu.generation.sampling import SamplingParams
        m = _model()
        rng = np.random.RandomState(0)
        prompt = rng.randint(2, m.config.vocab_size, (9,)).tolist()
        kw = dict(max_new_tokens=6, decode_strategy="sampling",
                  temperature=0.8, top_k=12, top_p=0.9, seed=7)
        static_toks = np.asarray(
            m.generate(np.asarray([prompt]), **kw)[0].numpy()
        )[0].tolist()

        class NoCache(type(m)):
            supports_static_cache = False
        m2 = NoCache(m.config)
        m2.set_state_dict(m.state_dict())
        eager_toks = np.asarray(
            m2.generate(np.asarray([prompt]), **kw)[0].numpy()
        )[0].tolist()

        cb = _cb(m, sampling_enabled=True)
        serve_toks = cb.generate(
            [prompt], max_new_tokens=6,
            sampling=SamplingParams(temperature=0.8, top_k=12,
                                    top_p=0.9, seed=7))[0]
        assert static_toks == eager_toks == serve_toks

    def test_sampled_stream_survives_slot_recycling(self):
        """More requests than slots, staggered budgets: a sampled
        request admitted into a slot recycled while the OLD request's
        last double-buffered step is still in flight must start its key
        counter at 0 — the dispatch-side pending set is keyed
        (slot, request) like the resolve guard, not by slot alone
        (which would shift the new request's whole fixed-seed
        stream by one)."""
        from paddle_tpu.generation.sampling import SamplingParams
        from paddle_tpu.serving.streaming import ServeRequest
        m = _model()
        prompts = _cyclic_prompts(m.config.vocab_size, n=3)
        sp = SamplingParams(temperature=0.9, top_k=20, seed=11)
        cb = _cb(m, sampling_enabled=True)     # B=2 < 3: slot recycles
        # r0 finishes early while r1 keeps the pipeline dispatching, so
        # r2 lands in r0's slot with a step snap-listing r0 in flight
        batch = [ServeRequest(prompts[0], 4, sampling=sp),
                 ServeRequest(prompts[1], 24, sampling=sp),
                 ServeRequest(prompts[2], 12, sampling=sp)]
        state = {"sent": False}

        def intake():
            if state["sent"]:
                return None
            state["sent"] = True
            return batch

        stream = cb.serve_stream(intake)
        for _ in stream:
            pass
        out = list(stream.results)
        solo = cb.generate(prompts[2:], max_new_tokens=12,
                           sampling=sp)[0]
        assert out[2] == solo
        assert _pool_baseline(cb)

    def test_spec_plus_sampled_deterministic(self):
        from paddle_tpu.generation.sampling import SamplingParams
        m = _model()
        prompts = _cyclic_prompts(m.config.vocab_size, n=2)
        cb = _cb(m, spec_draft_tokens=3, sampling_enabled=True)
        sp = SamplingParams(temperature=0.8, top_k=20, seed=5)
        a = cb.generate(prompts, max_new_tokens=16, sampling=sp)
        b = cb.generate(prompts, max_new_tokens=16, sampling=sp)
        assert a == b
        assert all(len(o) == 16 for o in a)
        assert cb.stats["spec_proposed"] > 0
        assert _pool_baseline(cb)


# ---------------------------------------------------------------------------
# router: exactly-once multi-token delivery
# ---------------------------------------------------------------------------
class TestRouterSpanDedup:
    def _handle(self):
        from paddle_tpu.serving.router import RequestHandle
        return RequestHandle("r1", [1, 2, 3], 8, None, None)

    def _ev(self, toks, index):
        from paddle_tpu.serving.streaming import StreamEvent
        return StreamEvent(0, "token", toks[-1], index, 0.0, None,
                           None, tuple(toks))

    def test_multitoken_exactly_once_across_readmission(self):
        h = self._handle()
        h._push_token(self._ev([10, 11, 12], 3))     # spec tick: 1..3
        assert h.tokens == [10, 11, 12]
        # replica died; re-admitted elsewhere re-decodes the prefix —
        # overlapping span [2..4]: only ordinal 4 is fresh
        h._push_token(self._ev([11, 12, 13], 4))
        assert h.tokens == [10, 11, 12, 13]
        # full duplicate: dropped entirely
        h._push_token(self._ev([11, 12, 13], 4))
        assert h.tokens == [10, 11, 12, 13]
        # single-token event (legacy shape: span == (token,))
        h._push_token(self._ev([14], 5))
        assert h.tokens == [10, 11, 12, 13, 14]
        # the forwarded overlap event was trimmed to the fresh tail
        evs = []
        while not h._q.empty():
            evs.append(h._q.get())
        assert [list(e.span) for e in evs] == [[10, 11, 12], [13], [14]]


# ---------------------------------------------------------------------------
# config + autotune
# ---------------------------------------------------------------------------
class TestConfigAndAutotune:
    def test_runtime_config_fields_round_trip(self):
        from paddle_tpu.framework.runtime_config import (
            RuntimeConfig, COMPILED_FIELDS, MIGRATED_FLAG_KNOBS)
        rc = RuntimeConfig(spec_draft_tokens=4, spec_ngram_max=5,
                           sampling_enabled=True)
        rc2 = RuntimeConfig.from_dict(rc.to_dict())
        assert rc2 == rc
        assert {"spec_draft_tokens", "sampling_enabled"} \
            <= COMPILED_FIELDS
        assert "spec_ngram_max" not in COMPILED_FIELDS  # runtime-only
        assert MIGRATED_FLAG_KNOBS["serve_spec_draft_tokens"] \
            == "spec_draft_tokens"
        d = RuntimeConfig().diff(rc)
        assert set(d) == {"spec_draft_tokens", "spec_ngram_max",
                          "sampling_enabled"}
        with pytest.raises(ValueError):
            RuntimeConfig(spec_draft_tokens=-1)

    def test_from_flags_reads_spec_knobs(self):
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        set_flags({"serve_spec_draft_tokens": 6, "serve_sampling": True})
        try:
            rc = RuntimeConfig.from_flags()
            assert rc.spec_draft_tokens == 6
            assert rc.sampling_enabled is True
        finally:
            set_flags({"serve_spec_draft_tokens": 0,
                       "serve_sampling": False})

    def _autotune(self):
        import importlib.util
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "autotune_spec_test", os.path.join(repo, "tools",
                                               "autotune.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _telemetry(self, tmp_path, proposed, accepted):
        path = str(tmp_path / "t.jsonl")
        with open(path, "w") as f:
            for name, v in (("serving.spec.proposed_tokens", proposed),
                            ("serving.spec.accepted_tokens", accepted)):
                f.write(json.dumps({"kind": "counter", "name": name,
                                    "value": v, "ts": 1.0,
                                    "labels": {}}) + "\n")
        return path

    def test_propose_spec_raises_on_high_acceptance(self, tmp_path):
        at = self._autotune()
        rep = at.load_replay(
            [self._telemetry(tmp_path, 100, 85)])
        props = at.propose_spec(rep, {**at.CONFIG_DEFAULTS,
                                      "spec_draft_tokens": 4})
        assert props and props[0]["proposed"] == 8
        assert props[0]["evidence"]["value"] == 0.85

    def test_propose_spec_disables_on_low_acceptance(self, tmp_path):
        at = self._autotune()
        rep = at.load_replay([self._telemetry(tmp_path, 100, 10)])
        props = at.propose_spec(rep, {**at.CONFIG_DEFAULTS,
                                      "spec_draft_tokens": 4})
        assert props and props[0]["proposed"] == 0

    def test_propose_spec_silent_without_data(self, tmp_path):
        at = self._autotune()
        rep = at.load_replay([self._telemetry(tmp_path, 2, 2)])
        assert at.propose_spec(rep, dict(at.CONFIG_DEFAULTS)) == []
        # mid-band rate: no proposal either direction
        rep2 = at.load_replay([self._telemetry(tmp_path, 100, 50)])
        assert at.propose_spec(rep2, {**at.CONFIG_DEFAULTS,
                                      "spec_draft_tokens": 4}) == []

    def test_defaults_parity_with_runtime_config(self):
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        at = self._autotune()
        assert at.CONFIG_DEFAULTS == RuntimeConfig().to_dict()


# ---------------------------------------------------------------------------
# spec + sampling program variants from an AOT bundle; the exported gauge
# ---------------------------------------------------------------------------
class TestSpecBundleAndGauge:
    def test_spec_sampling_bundle_warm_starts_without_compiling(
            self, tmp_path):
        """A bundle built with spec_draft_tokens and sampling_enabled
        holds the spec and decode_sample variants: a warm start serves
        from it alone, and its greedy output is plain greedy's."""
        import paddle_tpu.observability as obs
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        from paddle_tpu.inference import LLMPredictor, aot
        from paddle_tpu.inference.aot.builder import EngineBuilder
        m = _model()
        prompts = _cyclic_prompts(m.config.vocab_size, n=2)
        ref = _cb(m).generate(prompts, max_new_tokens=24)
        rc = RuntimeConfig(max_batch_size=2, page_size=8,
                           max_seq_len=128, spec_draft_tokens=4,
                           sampling_enabled=True)
        path = str(tmp_path / "engine")
        manifest = EngineBuilder(
            m, prompt_buckets=(LLMPredictor._bucket(len(prompts[0])),),
            batch_sizes=(1, 2), capture_forward=False,
            runtime_config=rc, enable_prefix_cache=False,
            eos_token_id=None).build(path, wire_cache=False)
        kinds = {rec["kind"] for rec in manifest["artifacts"].values()}
        assert {"spec", "decode_sample"} <= kinds
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            pred, eng = aot.warm_start(m, path, wire_cache=False)
            out = pred.generate(prompts, max_new_tokens=24)
            misses = obs.get_registry().get("aot.bucket_misses")
        finally:
            obs.enabled(was)
        assert out == ref
        assert pred.stats["spec_accepted"] > 0
        assert eng.stats["hits"] > 0 and eng.stats["misses"] == 0
        assert misses is None or not any(
            s.value for s in misses.samples())

    def test_accept_rate_gauge_is_accepted_over_proposed(self):
        import paddle_tpu.observability as obs
        m = _model()
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            cb = _cb(m, spec_draft_tokens=4, name="spec")
            cb.generate(_cyclic_prompts(m.config.vocab_size),
                        max_new_tokens=24)
            g = obs.get_registry().get("serve.spec.accept_rate")
            rates = [s.value for s in g.samples()
                     if s.labels.get("replica") == "spec"]
        finally:
            obs.enabled(was)
        assert rates == [cb.stats["spec_accepted"]
                         / cb.stats["spec_proposed"]]

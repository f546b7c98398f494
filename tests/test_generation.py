"""Generation + paged attention tests.

Mirrors the reference test strategy (SURVEY.md §4): numeric-oracle
comparison (numpy), dual-path parity (jitted static-cache loop vs eager
full-recompute loop — the analog of dygraph/static dual-run), and
determinism checks.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, GPTConfig
from paddle_tpu.generation import GenerationConfig


def tiny_llama():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
    m.eval()
    return m


class TestGreedyGeneration:
    def test_static_cache_matches_eager(self):
        m = tiny_llama()
        ids = np.random.RandomState(0).randint(5, 50, (2, 9))
        out_static, _ = m.generate(ids, max_new_tokens=6)
        out_eager, _ = m.generate(ids, max_new_tokens=6, use_cache=False)
        np.testing.assert_array_equal(out_static.numpy(), out_eager.numpy())

    def test_ragged_prompts_match_solo_runs(self):
        m = tiny_llama()
        ids = np.array([[7, 8, 9, 10, 11], [3, 4, 5, 0, 0]])
        mask = np.array([[1, 1, 1, 1, 1], [1, 1, 1, 0, 0]])
        batched, _ = m.generate(ids, attention_mask=mask, max_new_tokens=5)
        solo0, _ = m.generate(ids[0:1, :], max_new_tokens=5)
        solo1, _ = m.generate(ids[1:2, :3], max_new_tokens=5)
        np.testing.assert_array_equal(batched.numpy()[0], solo0.numpy()[0])
        np.testing.assert_array_equal(batched.numpy()[1], solo1.numpy()[0])

    def test_eos_early_stop_pads_tail(self):
        m = tiny_llama()
        ids = np.random.RandomState(1).randint(5, 50, (1, 6))
        ref, _ = m.generate(ids, max_new_tokens=8)
        eos = int(ref.numpy()[0, 2])  # force the 3rd token to be "eos"
        out, _ = m.generate(ids, max_new_tokens=8, eos_token_id=eos,
                            pad_token_id=0)
        got = out.numpy()[0]
        assert (got[3:] == 0).all()
        np.testing.assert_array_equal(got[:2], ref.numpy()[0, :2])

    def test_generation_config_object(self):
        m = tiny_llama()
        ids = np.random.RandomState(2).randint(5, 50, (1, 5))
        cfg = GenerationConfig(max_new_tokens=3,
                               decode_strategy="greedy_search")
        out, scores = m.generate(ids, generation_config=cfg)
        assert out.shape == [1, 3]
        assert scores.shape == [1]


class TestSampling:
    def test_seeded_sampling_deterministic(self):
        m = tiny_llama()
        ids = np.random.RandomState(0).randint(5, 50, (2, 7))
        a, _ = m.generate(ids, max_new_tokens=5, decode_strategy="sampling",
                          top_k=10, temperature=0.7, seed=3)
        b, _ = m.generate(ids, max_new_tokens=5, decode_strategy="sampling",
                          top_k=10, temperature=0.7, seed=3)
        np.testing.assert_array_equal(a.numpy(), b.numpy())

    def test_top_k1_equals_greedy(self):
        m = tiny_llama()
        ids = np.random.RandomState(0).randint(5, 50, (2, 7))
        greedy, _ = m.generate(ids, max_new_tokens=4)
        topk1, _ = m.generate(ids, max_new_tokens=4,
                              decode_strategy="sampling", top_k=1, seed=0)
        np.testing.assert_array_equal(greedy.numpy(), topk1.numpy())

    def test_top_p_filter_keeps_argmax(self):
        from paddle_tpu.generation import logits_process as LP
        import jax.numpy as jnp
        logits = jnp.asarray(np.array([[3.0, 1.0, 0.5, -2.0]]))
        out = np.asarray(LP.top_p_filter(logits, 0.01))
        assert out[0, 0] == 3.0
        assert (out[0, 1:] < -1e29).all()

    def test_repetition_penalty_discourages_repeats(self):
        from paddle_tpu.generation import logits_process as LP
        import jax.numpy as jnp
        logits = jnp.asarray(np.array([[2.0, 2.0]]))
        counts = jnp.asarray(np.array([[1, 0]], np.int32))
        out = np.asarray(LP.repetition_penalty(logits, counts, 2.0))
        assert out[0, 0] == 1.0 and out[0, 1] == 2.0


class TestEagerFallback:
    def test_plain_model_generates_via_fallback(self):
        # a model WITHOUT the static-cache protocol uses the eager loop
        from paddle_tpu import nn
        from paddle_tpu.generation import GenerationMixin

        class TinyLM(nn.Layer, GenerationMixin):
            class _Cfg:
                vocab_size = 64
            config = _Cfg()

            def __init__(self):
                super().__init__()
                self.emb = nn.Embedding(64, 16)
                self.out = nn.Linear(16, 64)

            def forward(self, input_ids):
                return self.out(self.emb(input_ids))

        paddle.seed(0)
        m = TinyLM()
        m.eval()
        assert not m.supports_static_cache
        ids = np.random.RandomState(0).randint(5, 50, (2, 6))
        out, _ = m.generate(ids, max_new_tokens=4)
        assert out.shape == [2, 4]

    def test_gpt_static_cache_matches_eager(self):
        from paddle_tpu.models import GPTForCausalLM
        paddle.seed(0)
        m = GPTForCausalLM(GPTConfig.tiny(tensor_parallel=False))
        m.eval()
        assert m.supports_static_cache
        ids = np.random.RandomState(0).randint(5, 500, (2, 9))
        s, _ = m.generate(ids, max_new_tokens=6)
        e, _ = m.generate(ids, max_new_tokens=6, use_cache=False)
        np.testing.assert_array_equal(s.numpy(), e.numpy())
        # ragged batch row = solo run
        mask = np.ones_like(ids)
        mask[1, :4] = 0
        rb, _ = m.generate(ids, attention_mask=mask, max_new_tokens=5)
        solo, _ = m.generate(ids[1][mask[1].astype(bool)][None],
                             max_new_tokens=5)
        np.testing.assert_array_equal(rb.numpy()[1], solo.numpy()[0])

    def test_gpt_tuple_cache_incremental_decode(self):
        # manual HF-style incremental decoding with tuple caches must
        # match the full forward's last-position logits
        from paddle_tpu.models import GPTForCausalLM
        paddle.seed(0)
        m = GPTForCausalLM(GPTConfig.tiny(tensor_parallel=False))
        m.eval()
        ids = np.random.RandomState(1).randint(5, 500, (1, 7))
        full = m(paddle.to_tensor(ids))
        full = (full[0] if isinstance(full, tuple) else full).numpy()
        # prefill on the first 4, then decode 3 tokens one at a time
        logits, caches = m(paddle.to_tensor(ids[:, :4]), use_cache=True)
        for t in range(4, 7):
            logits, caches = m(paddle.to_tensor(ids[:, t:t + 1]),
                               past_key_values=caches, use_cache=True)
            np.testing.assert_allclose(logits.numpy()[:, -1],
                                       full[:, t], atol=2e-4)


class TestPagedAttention:
    def _setup(self, hkv):
        rs = np.random.RandomState(0)
        B, H, D, page, P, pps = 3, 8, 128, 16, 12, 3
        q = rs.randn(B, H, D).astype(np.float32)
        kp = rs.randn(P, page, hkv, D).astype(np.float32)
        vp = rs.randn(P, page, hkv, D).astype(np.float32)
        bt = rs.choice(P, (B, pps), replace=False).astype(np.int32)
        cl = np.array([40, 17, 5], np.int32)
        return q, kp, vp, bt, cl

    def _oracle(self, q, kp, vp, bt, cl, b):
        H, hkv, D = q.shape[1], kp.shape[2], q.shape[2]
        k = kp[bt[b]].reshape(-1, hkv, D)
        v = vp[bt[b]].reshape(-1, hkv, D)
        if hkv != H:
            k = np.repeat(k, H // hkv, axis=1)
            v = np.repeat(v, H // hkv, axis=1)
        L = int(cl[b])
        s = np.einsum("hd,khd->hk", q[b], k[:L]) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return np.einsum("hk,khd->hd", p, v[:L])

    def test_xla_fallback_matches_oracle(self):
        import jax.numpy as jnp
        from paddle_tpu.kernels.paged_attention import _paged_attention_xla
        q, kp, vp, bt, cl = self._setup(hkv=8)
        out = np.asarray(_paged_attention_xla(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(cl), 1.0 / np.sqrt(128)))
        for b in range(3):
            np.testing.assert_allclose(
                out[b], self._oracle(q, kp, vp, bt, cl, b), atol=1e-4)

    def test_gqa_fallback_matches_oracle(self):
        import jax.numpy as jnp
        from paddle_tpu.kernels.paged_attention import paged_attention
        q, kp, vp, bt, cl = self._setup(hkv=4)
        out = np.asarray(paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(cl)))
        for b in range(3):
            np.testing.assert_allclose(
                out[b], self._oracle(q, kp, vp, bt, cl, b), atol=1e-4)

    # -- the XLA block-table paths, per KV head group ---------------------
    # geometry of the grouped cases: 2 KV heads, page 4, 4 pages a slot
    _G = dict(hkv=2, d=32, page=4, pps=4, pool=24, span=4)

    def _grouped_case(self, rep, dtype, seed=0):
        """Inputs rounded to `dtype` (so the oracle sees what the pool
        holds), one slot per length: 0, 1, a page boundary, a full
        table."""
        import jax.numpy as jnp
        g = self._G
        rs = np.random.RandomState(seed)
        L = g["page"] * g["pps"]
        lens = np.array([0, 1, g["page"], L], np.int32)
        B, H = len(lens), g["hkv"] * rep
        rnd = lambda *shape: jnp.asarray(
            rs.randn(*shape).astype(np.float32)).astype(dtype)
        q = rnd(B, g["span"], H, g["d"])
        kp = rnd(g["pool"], g["page"], g["hkv"], g["d"])
        vp = rnd(g["pool"], g["page"], g["hkv"], g["d"])
        bt = rs.choice(g["pool"], (B, g["pps"]), replace=False).astype(
            np.int32)
        return q, kp, vp, bt, lens

    @staticmethod
    def _grouped_oracle(q, kp, vp, bt, ok):
        """Plain float32 NumPy: gather the table, REPEAT the KV heads to
        the query heads, mask with the kernels' finite -1e30 (a query
        with no key attends uniformly, as the XLA path always has),
        softmax, P.V. q: [B, Q, H, D]; ok: [B, Q, L]."""
        q, kp, vp = (np.asarray(x.astype("float32")) for x in (q, kp, vp))
        B, Q, H, D = q.shape
        hkv = kp.shape[2]
        out = np.zeros_like(q)
        for b in range(B):
            k = np.repeat(kp[bt[b]].reshape(-1, hkv, D), H // hkv, axis=1)
            v = np.repeat(vp[bt[b]].reshape(-1, hkv, D), H // hkv, axis=1)
            s = np.einsum("qhd,khd->qhk", q[b], k) / np.sqrt(np.float32(D))
            s = np.where(ok[b][:, None, :], s, np.float32(-1e30))
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b] = np.einsum("qhk,khd->qhd", p, v)
        return out

    @staticmethod
    def _grouped_atol(dtype, vp):
        # float32: today's 1e-4. Narrower pools: P and the output are
        # each rounded to the dtype, half an ulp (eps / 2, relative)
        # apiece, against values up to max |v|: eps * max |v| in all.
        import jax.numpy as jnp
        if dtype == "float32":
            return 1e-4
        vmax = float(np.abs(np.asarray(vp.astype("float32"))).max())
        return float(jnp.finfo(dtype).eps) * vmax

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("rep", [1, 2, 4, 8])
    @pytest.mark.parametrize("path", ["decode", "varq"])
    def test_xla_paths_match_repeating_oracle(self, path, rep, dtype):
        import jax.numpy as jnp
        from paddle_tpu.kernels.paged_attention import (
            _paged_attention_xla, _paged_attention_varq_xla)
        q, kp, vp, bt, lens = self._grouped_case(rep, dtype)
        g = self._G
        scale = 1.0 / np.sqrt(g["d"])
        tok = np.arange(g["page"] * g["pps"])
        if path == "decode":
            q = q[:, :1]
            ok = (tok[None, :] < lens[:, None])[:, None, :]
            out = _paged_attention_xla(q[:, 0], kp, vp, jnp.asarray(bt),
                                       jnp.asarray(lens), scale)[:, None]
            want = self._grouped_oracle(q, kp, vp, bt, ok)
        else:
            q_lens = np.minimum(lens, [0, 1, 3, g["span"]]).astype(np.int32)
            qpos = (lens - q_lens)[:, None] + np.arange(g["span"])[None, :]
            ok = ((tok[None, None, :] <= qpos[:, :, None])
                  & (tok[None, None, :] < lens[:, None, None]))
            out = _paged_attention_varq_xla(
                q, kp, vp, jnp.asarray(bt), jnp.asarray(lens),
                jnp.asarray(q_lens), scale)
            want = self._grouped_oracle(q, kp, vp, bt, ok)
            want[np.arange(g["span"])[None, :] >= q_lens[:, None]] = 0
        assert out.dtype == q.dtype and out.shape == q.shape
        np.testing.assert_allclose(
            np.asarray(out.astype("float32")), want, rtol=0,
            atol=self._grouped_atol(dtype, vp))

    @pytest.mark.parametrize("path", ["decode", "varq"])
    def test_xla_paths_never_widen_or_repeat_the_table(self, path):
        """The mechanism, in the traced program: with a bf16 pool at a
        GQA shape nothing is larger than the gathered table
        [B, L, Hkv, D] (a `jnp.repeat` of the KV heads is `rep` times
        it) and nothing of its size is float32. What XLA makes of it on
        the chip is tests/test_chip_compile.py's."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.kernels.paged_attention import (
            _paged_attention_xla, _paged_attention_varq_xla)
        B, H, hkv, D, page, pps, pool, span = 2, 8, 2, 128, 16, 4, 16, 4
        bf = jnp.bfloat16
        pages = jnp.zeros((pool, page, hkv, D), bf)
        bt = jnp.zeros((B, pps), jnp.int32)
        lens = jnp.ones((B,), jnp.int32)
        if path == "decode":
            jaxpr = jax.make_jaxpr(
                lambda q, k, v: _paged_attention_xla(q, k, v, bt, lens,
                                                     0.1))(
                jnp.zeros((B, H, D), bf), pages, pages)
        else:
            jaxpr = jax.make_jaxpr(
                lambda q, k, v: _paged_attention_varq_xla(
                    q, k, v, bt, lens, lens, 0.1))(
                jnp.zeros((B, span, H, D), bf), pages, pages)
        table = B * pps * page * hkv * D

        def avals(jp):
            for eqn in jp.eqns:
                for v in eqn.outvars:
                    yield eqn.primitive.name, v.aval
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from avals(sub)

        seen = list(avals(jaxpr.jaxpr))
        assert any(a.size == table for _, a in seen)     # the gather
        for name, a in seen:
            assert a.size <= table, (name, a)
            assert a.size < table or a.dtype == bf, (name, a)

    def test_pallas_interpret_matches_xla(self):
        import jax.numpy as jnp
        from paddle_tpu.kernels.paged_attention import (
            _paged_attention_pallas, _paged_attention_xla)
        q, kp, vp, bt, cl = self._setup(hkv=8)
        sc = float(1.0 / np.sqrt(128))
        ref = _paged_attention_xla(jnp.asarray(q), jnp.asarray(kp),
                                   jnp.asarray(vp), jnp.asarray(bt),
                                   jnp.asarray(cl), sc)
        out = _paged_attention_pallas(jnp.asarray(q), jnp.asarray(kp),
                                      jnp.asarray(vp), jnp.asarray(bt),
                                      jnp.asarray(cl), sc, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_incubate_api_surface(self):
        import paddle_tpu.incubate.nn.functional as IF
        q, kp, vp, bt, cl = self._setup(hkv=8)
        out = IF.paged_attention(q, kp, vp, bt, cl)
        assert list(out.shape) == [3, 8, 128]


class TestLLMPredictor:
    def test_batched_serving_matches_solo(self):
        from paddle_tpu.inference import LLMPredictor
        m = tiny_llama()
        pred = LLMPredictor(m, max_batch_size=4)
        outs = pred.generate([[5, 6, 7], [8, 9, 10, 11, 12], [13]],
                             max_new_tokens=4)
        assert len(outs) == 3
        solo, _ = m.generate(np.array([[5, 6, 7]]), max_new_tokens=4)
        assert outs[0] == [t for t in solo.numpy()[0].tolist() if t != 0]

    def test_chunking_over_max_batch(self):
        from paddle_tpu.inference import LLMPredictor
        m = tiny_llama()
        pred = LLMPredictor(m, max_batch_size=2)
        prompts = [[5, 6], [7, 8], [9, 10], [11, 12], [13, 14]]
        outs = pred.generate(prompts, max_new_tokens=3)
        assert len(outs) == 5


class TestReviewRegressions:
    def test_generate_sees_updated_weights(self):
        """The compile cache must rebind current params, not snapshot."""
        m = tiny_llama()
        ids = np.random.RandomState(3).randint(5, 50, (1, 6))
        before, _ = m.generate(ids, max_new_tokens=4)
        sd = m.state_dict()
        for k in sd:
            sd[k] = paddle.to_tensor(np.asarray(sd[k].numpy()) * 0.5)
        m.set_state_dict(sd)
        after, _ = m.generate(ids, max_new_tokens=4)
        assert not np.array_equal(before.numpy(), after.numpy())

    def test_eager_fallback_ragged_matches_solo(self):
        from paddle_tpu.models import GPTForCausalLM
        paddle.seed(0)
        m = GPTForCausalLM(GPTConfig.tiny(tensor_parallel=False))
        m.eval()
        ids = np.array([[7, 8, 9, 10], [3, 4, 0, 0]])
        mask = np.array([[1, 1, 1, 1], [1, 1, 0, 0]])
        batched, _ = m.generate(ids, attention_mask=mask, max_new_tokens=3)
        solo, _ = m.generate(ids[1:2, :2], max_new_tokens=3)
        np.testing.assert_array_equal(batched.numpy()[1], solo.numpy()[0])

    def test_generation_config_not_mutated(self):
        m = tiny_llama()
        cfg = GenerationConfig(max_new_tokens=3, top_k=0)
        m.generate(np.array([[5, 6, 7]]), generation_config=cfg, top_k=9)
        assert cfg.top_k == 0

    def test_predictor_kwargs_override(self):
        from paddle_tpu.inference import LLMPredictor
        m = tiny_llama()
        pred = LLMPredictor(m, max_batch_size=2, eos_token_id=1)
        outs = pred.generate([[5, 6, 7]], max_new_tokens=3, eos_token_id=None)
        assert len(outs) == 1  # no TypeError from duplicate kwargs

    def test_block_mha_packed_qkv(self):
        import paddle_tpu.incubate.nn.functional as IF
        rs = np.random.RandomState(0)
        H, D, page, P = 8, 128, 16, 6
        qkv = rs.randn(2, 3 * H * D).astype(np.float32)
        kp = rs.randn(P, page, H, D).astype(np.float32)
        vp = rs.randn(P, page, H, D).astype(np.float32)
        bt = np.array([[0, 1], [2, 3]], np.int32)
        cl = np.array([20, 9], np.int32)
        out = IF.block_multihead_attention(qkv, kp, vp, bt, cl, num_heads=H)
        assert list(out.shape) == [2, H, D]
        ref = IF.paged_attention(
            qkv[:, :H * D].reshape(2, H, D), kp, vp, bt, cl)
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   np.asarray(ref.numpy()), atol=1e-5)


class TestBeamSearch:
    def _model(self):
        import paddle_tpu as paddle
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        paddle.seed(0)
        m = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
        m.eval()
        return m

    def test_beam1_equals_greedy(self):
        import numpy as np
        import paddle_tpu as paddle
        m = self._model()
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(1, 256, (2, 8)))
        g, _ = m.generate(ids, max_new_tokens=5,
                          decode_strategy="greedy_search")
        b, _ = m.generate(ids, max_new_tokens=5,
                          decode_strategy="beam_search", num_beams=1)
        np.testing.assert_array_equal(g.numpy(), b.numpy())

    def test_static_beam_matches_eager_beam(self):
        import numpy as np
        import paddle_tpu as paddle
        m = self._model()
        ids = paddle.to_tensor(
            np.random.RandomState(1).randint(1, 256, (2, 6)))
        s, ss = m.generate(ids, max_new_tokens=5,
                           decode_strategy="beam_search", num_beams=3)
        e, es = m.generate(ids, max_new_tokens=5,
                           decode_strategy="beam_search", num_beams=3,
                           use_cache=False)
        np.testing.assert_array_equal(s.numpy(), e.numpy())
        np.testing.assert_allclose(ss.numpy(), es.numpy(), rtol=1e-4)

    def test_beam_improves_sequence_logp(self):
        # beam search explores a superset of greedy's single path, so the
        # best beam's (unnormalized, lp=0) score must be >= greedy's
        import numpy as np
        import paddle_tpu as paddle
        import jax
        import jax.numpy as jnp
        m = self._model()
        ids = paddle.to_tensor(
            np.random.RandomState(2).randint(1, 256, (1, 6)))

        def seq_logp(new_tokens):
            cur = np.concatenate([ids.numpy(), new_tokens[None]], axis=1)
            out = m(paddle.to_tensor(cur))
            lg = (out[0] if isinstance(out, tuple) else out).numpy()
            lp = np.asarray(jax.nn.log_softmax(
                jnp.asarray(lg, jnp.float32), axis=-1))
            tot = 0.0
            start = ids.shape[1] - 1
            for i, tok in enumerate(new_tokens):
                tot += lp[0, start + i, tok]
            return tot

        g, _ = m.generate(ids, max_new_tokens=4,
                          decode_strategy="greedy_search")
        b, _ = m.generate(ids, max_new_tokens=4,
                          decode_strategy="beam_search", num_beams=4,
                          length_penalty=0.0)
        assert seq_logp(b.numpy()[0]) >= seq_logp(g.numpy()[0]) - 1e-4

    def test_beam_eos_freezes(self):
        import numpy as np
        import paddle_tpu as paddle
        m = self._model()
        ids = paddle.to_tensor(
            np.random.RandomState(3).randint(1, 256, (1, 5)))
        out, _ = m.generate(ids, max_new_tokens=8,
                            decode_strategy="beam_search", num_beams=2,
                            eos_token_id=7, pad_token_id=0)
        row = out.numpy()[0]
        if (row == 7).any():
            after = row[np.argmax(row == 7) + 1:]
            assert (after == 0).all()

    def test_eager_beam_min_new_tokens(self):
        # regression: the eos mask writes into a copied (writable) array
        import numpy as np
        import paddle_tpu as paddle
        m = self._model()
        ids = paddle.to_tensor(
            np.random.RandomState(4).randint(1, 256, (1, 5)))
        out, _ = m.generate(ids, max_new_tokens=4,
                            decode_strategy="beam_search", num_beams=2,
                            eos_token_id=7, min_new_tokens=2,
                            use_cache=False)
        assert (out.numpy()[0, :2] != 7).all()

    def test_num_beams_requires_beam_strategy(self):
        import numpy as np
        import pytest
        import paddle_tpu as paddle
        m = self._model()
        ids = paddle.to_tensor(
            np.random.RandomState(5).randint(1, 256, (1, 4)))
        with pytest.raises(ValueError, match="num_beams"):
            m.generate(ids, max_new_tokens=2,
                       decode_strategy="sampling", num_beams=3)


class TestQuantizedPredictor:
    def test_llm_predictor_weight_only(self):
        import numpy as np
        import pytest
        import paddle_tpu as paddle
        from paddle_tpu.inference import LLMPredictor
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        paddle.seed(0)
        m = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
        w_proj_ref = np.array(
            m.llama.layers[0].self_attn.q_proj.weight.numpy())
        w_emb_ref = np.array(m.llama.embed_tokens.weight.numpy())
        paddle.seed(0)
        m2 = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
        q = LLMPredictor(m2, quant_type="weight_only_int8", seed=0)
        # quantization actually happened: projections changed (rounded
        # through int8), embeddings untouched
        w_proj = m2.llama.layers[0].self_attn.q_proj.weight.numpy()
        assert np.abs(w_proj - w_proj_ref).max() > 0
        np.testing.assert_allclose(w_proj, w_proj_ref, atol=2e-3)
        np.testing.assert_array_equal(
            m2.llama.embed_tokens.weight.numpy(), w_emb_ref)
        out = q.generate([[5, 9, 23]], max_new_tokens=4)
        assert len(out[0]) == 4
        # int8 weight error rarely flips the greedy argmax on a tiny
        # model; identical prefixes are expected but not guaranteed —
        # assert structure + determinism instead
        out2 = q.generate([[5, 9, 23]], max_new_tokens=4)
        assert out == out2
        with pytest.raises(ValueError, match="quant_type"):
            LLMPredictor(m2, quant_type="fp4")


class TestSpeculativeDecoding:
    def test_exact_greedy_parity_and_fewer_calls(self):
        import paddle_tpu as paddle
        from paddle_tpu.inference import LLMPredictor, SpeculativePredictor
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        paddle.seed(0)
        target = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
        paddle.seed(1)
        draft = LlamaForCausalLM(LlamaConfig(
            vocab_size=256, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=512,
            tensor_parallel=False))
        prompt = [5, 9, 23, 7]
        ref = LLMPredictor(target, seed=0).generate(
            [prompt], max_new_tokens=10,
            decode_strategy="greedy_search")[0]
        # arbitrary draft: output must STILL be exactly target-greedy
        spec = SpeculativePredictor(target, draft, gamma=4)
        assert spec.generate(prompt, max_new_tokens=10) == ref
        # perfect draft (target as its own draft): every proposal
        # accepted, so ~N/(gamma+1) target calls instead of N
        spec2 = SpeculativePredictor(target, target, gamma=4)
        assert spec2.generate(prompt, max_new_tokens=10) == ref
        assert spec2.stats["target_calls"] <= 3
        assert spec2.stats["accepted"] == spec2.stats["proposed"]

    def test_speculative_eos_stops(self):
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu.inference import SpeculativePredictor
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        paddle.seed(0)
        m = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
        # pick the model's own first greedy token as "eos" to force a stop
        spec = SpeculativePredictor(m, m, gamma=3)
        first = spec.generate([5, 9], max_new_tokens=1)[0]
        spec2 = SpeculativePredictor(m, m, gamma=3, eos_token_id=first)
        out = spec2.generate([5, 9], max_new_tokens=8)
        assert out[-1] == first and len(out) <= 8



class TestContinuousBatching:
    """round 5 (VERDICT r4 #5): continuous batching — sequences join and
    leave the running batch mid-flight over a shared paged-KV pool;
    greedy outputs must match the static-cache generate path exactly."""

    def _model(self):
        paddle.seed(0)
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        return LlamaForCausalLM(LlamaConfig.tiny())

    def test_streaming_mixed_lengths_matches_static_greedy(self):
        from paddle_tpu.inference import (ContinuousBatchingPredictor,
                                          LLMPredictor)
        model = self._model()
        rng = np.random.RandomState(0)
        vocab = model.config.vocab_size
        prompts = [rng.randint(2, vocab, (n,)).tolist()
                   for n in (5, 11, 3, 17, 8, 6, 9, 4)]
        cb = ContinuousBatchingPredictor(model, max_batch_size=3,
                                         page_size=8, max_seq_len=64)
        out = cb.generate(prompts, max_new_tokens=8)
        ref = LLMPredictor(model, max_batch_size=1).generate(
            prompts, max_new_tokens=8)
        assert out == ref
        # slots were actually shared: more requests than slots, fewer
        # decode steps than sequential decode would need
        assert cb.stats["max_in_flight"] == 3
        assert cb.stats["evictions"] == len(prompts)
        assert cb.stats["decode_steps"] < len(prompts) * 8

    def test_pool_accounting_and_overlong_rejection(self):
        import pytest
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = self._model()
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, max_seq_len=32)
        free0 = cb.pool.free_count
        prompts = [[3, 4, 5], list(range(2, 60)), [7, 8]]
        # strict (default): an unservable request raises up front
        with pytest.raises(ValueError, match="max_seq_len"):
            cb.generate(prompts, max_new_tokens=4)
        assert cb.pool.free_count == free0  # nothing leaked by the raise
        # strict=False: rejected per-request with a status, rest served
        out = cb.generate(prompts, max_new_tokens=4, strict=False)
        assert out[1] == []           # over max_seq_len: rejected
        assert cb.last_status[1] == "rejected_over_max_seq_len"
        assert cb.last_status[0] == cb.last_status[2] == "ok"
        assert len(out[0]) == 4 and len(out[2]) == 4
        assert cb.pool.free_count == free0  # every page returned

    def test_over_pool_capacity_rejection(self):
        import pytest
        from paddle_tpu.inference import ContinuousBatchingPredictor
        model = self._model()
        # pool of 2 pages total: a request needing 3 pages can never be
        # admitted — previously the serve loop broke and EVERY queued
        # request silently got [] (ADVICE r5 #1)
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, num_pages=2,
                                         max_seq_len=64)
        ok, too_big = [3, 4, 5], list(range(2, 20))
        with pytest.raises(ValueError, match="pool"):
            cb.generate([ok, too_big], max_new_tokens=8)
        out = cb.generate([ok, too_big, ok], max_new_tokens=8,
                          strict=False)
        assert out[1] == []
        assert cb.last_status[1] == "rejected_over_pool_capacity"
        # the servable requests around it still complete
        assert len(out[0]) == 8 and len(out[2]) == 8
        assert cb.last_status[0] == cb.last_status[2] == "ok"


class TestRaggedPagedAttention:
    """The ragged metadata (PAPERS.md ragged paged attention): a grid
    over valid (seq, page) pairs only, scalar-prefetched, bucketed entry
    count; read by the variable-query kernel, here at one query a slot."""

    def test_parity_with_xla_oracle(self):
        import jax.numpy as jnp
        from paddle_tpu.framework.flags import set_flags, get_flags
        old = get_flags(["use_pallas_kernels", "pallas_interpret"])
        set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
        try:
            from paddle_tpu.kernels.paged_attention import (
                paged_attention_ragged_varq, build_ragged_meta,
                _paged_attention_xla)
            rs = np.random.RandomState(1)
            B, H, D, page, P = 5, 8, 128, 16, 40
            q = jnp.asarray(rs.randn(B, H, D).astype("f") * 0.3)
            kp = jnp.asarray(rs.randn(P, page, H, D).astype("f") * 0.3)
            vp = jnp.asarray(rs.randn(P, page, H, D).astype("f") * 0.3)
            lens = np.asarray([37, 5, 0, 64, 16], np.int32)
            perm = rs.permutation(P)
            tables = np.zeros((B, 4), np.int32)
            k = 0
            for b in range(B):
                n = -(-int(lens[b]) // page)
                tables[b, :n] = perm[k:k + n]
                k += n
            meta = build_ragged_meta(tables, lens, page)
            # ragged: only the 9 real pages enter the grid (bucketed 16)
            assert int(meta["valid"].sum()) == 9
            out = paged_attention_ragged_varq(
                q[:, None], kp, vp, jnp.asarray(lens),
                jnp.ones(B, jnp.int32), meta)[:, 0]
            ref = _paged_attention_xla(q, kp, vp, jnp.asarray(tables),
                                       jnp.asarray(lens), 1 / np.sqrt(D))
            ref = jnp.where((jnp.asarray(lens) > 0)[:, None, None],
                            ref, 0)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5)
        finally:
            set_flags({k.removeprefix("FLAGS_"): v
                       for k, v in old.items()})


def test_continuous_batching_ragged_decode_parity():
    """An MHA model (H == Hkv, D % 128 == 0) decodes through the
    block-table kernel with no metadata operands, token-exact with the
    static greedy oracle."""
    from paddle_tpu.framework.flags import set_flags, get_flags
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import (ContinuousBatchingPredictor,
                                      LLMPredictor)
    old = get_flags(["use_pallas_kernels", "pallas_interpret"])
    set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
    try:
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=128, hidden_size=1024,
                          intermediate_size=256, num_hidden_layers=2,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=128)
        model = LlamaForCausalLM(cfg)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(2, 128, (n,)).tolist()
                   for n in (5, 11, 3, 8)]
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, max_seq_len=48)
        assert not cb.span_ragged
        out = cb.generate(prompts, max_new_tokens=6)
        assert out == LLMPredictor(model, max_batch_size=1).generate(
            prompts, max_new_tokens=6)
    finally:
        set_flags({k.removeprefix("FLAGS_"): v for k, v in old.items()})

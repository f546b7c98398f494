"""GLM-5's language model (latent attention under an indexer with a
compressed query, dense then routed expert layers with a shared expert)
at tiny sizes on the CPU: the program against the benchmark's plain
reference (`benchmarks/reference/glm_moe_dsa.py`: float32, decompressed
attention, the whole [T, T] index matrix, selection by a sort, experts
one at a time) on seeded weights, through the model alone and through
`ContinuousBatchingPredictor`'s prefill and decode programs at contexts
on both sides of `topk` and across page boundaries; absorbed against
decompressed attention; the selected sets against the reference's; the
pages' contract (left padding, slot and page reuse); each Pallas kernel
in interpret mode against its XLA form; the expert shares against the
uncut layer; what is derived off and refused.
"""
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.generation.kv_cache import (LatentCacheEntry,  # noqa: E402
                                            LayerCache)
from paddle_tpu.inference import ContinuousBatchingPredictor  # noqa: E402
from paddle_tpu.kernels import latent_attention as la  # noqa: E402
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.kernels import sparse_attention as sa  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402

from benchmarks.checks import served_tokens  # noqa: E402
from benchmarks.lib import harness  # noqa: E402

SEED = 5_000_000_039
TOPK = 8

# 3 layers (one dense, two with experts), 8 heads of [24 | 8] on a
# 32-wide latent, values 32 wide, a 32-wide compressed query, 4 index
# heads of 16 (the first 8 rotated), top-8 selection in chunks of 8
# queries, 16 experts top-4 and a shared one; float32 so that the limits
# can be tight
CFG = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=8,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=24,
    qk_rope_head_dim=8, v_head_dim=32, index_n_heads=4, index_head_dim=16,
    index_topk=TOPK, index_norm_eps=1e-6, q_chunk_size=8, vocab_size=384,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
    n_group=1, topk_group=1, routed_scaling_factor=2.5, norm_topk_prob=True,
    experts_held=list(range(16)), published={"n_routed_experts": 16},
    rms_norm_eps=1e-5, rope_parameters={"rope_theta": 10000.0},
    router_bias_std=0.05, max_position_embeddings=256,
    initializer_range=0.25, dtype="float32")
# pages of 8, a table of 16: whole blocks for the kernels' interpret mode
GEO = dict(max_batch_size=4, page_size=8, max_seq_len=128)
# float32 on both sides: a served token is the reference's argmax but
# for a near-tie at the 6th decimal
TIGHT = {"gap_max": 2e-4, "gap_mean": 2e-5}


@pytest.fixture(scope="module")
def builder():
    return harness.load_module(ROOT, "models", "glm_moe_dsa")


@pytest.fixture(scope="module")
def reference():
    return harness.load_module(ROOT, "reference", "glm_moe_dsa")


@pytest.fixture(scope="module")
def model(builder):
    return builder.build(CFG, SEED)[0]


def _prompts(lengths, stream=0):
    rng = np.random.default_rng([SEED & 0xFFFFFFFF, stream])
    return [rng.integers(2, CFG["vocab_size"], n).tolist() for n in lengths]


def _served(model, prompts, max_new=10, **kw):
    pred = ContinuousBatchingPredictor(model, **dict(GEO, **kw))
    return pred, pred.generate(prompts, max_new_tokens=max_new)


def _reference_selections(reference, ids, cfg=CFG):
    """S_t of every layer, [T, T] bool each, by the reference's own
    functions."""
    gw = reference.gw
    key = gw.base_key(SEED)
    f32 = lambda tree: {n: a.astype(jnp.float32) for n, a in tree.items()}
    eps = cfg["rms_norm_eps"]
    x = gw.top(cfg, key)["embed"].astype(jnp.float32)[np.asarray(ids)]
    keeps = []
    for i in range(cfg["num_hidden_layers"]):
        out, keep = reference.attention_layer(
            reference._rms(x, eps), f32(gw.attn(cfg, key, i)), cfg, None,
            with_selection=True)
        x = x + out
        h = reference._rms(x, eps)
        if i < cfg["first_k_dense_replace"]:
            w = f32(gw.dense(cfg, key, i))
            x = x + reference._swiglu(h, w["w_in"], w["w_out"], None)
        else:
            x = x + reference.experts_layer(h, f32(gw.moe(cfg, key, i)), key,
                                            jnp.int32(i), cfg, None)
        keeps.append(np.asarray(keep))
    return keeps


def _layer0_inputs(model, ids):
    layer = model.model.layers[0]
    with paddle.no_grad():
        x = layer.input_layernorm(model.model.embed_tokens(
            paddle.to_tensor(np.asarray(ids, np.int32)[None])))._value
    return layer.self_attn, x, [p._value for p in
                                layer.self_attn._weights()]


# ------------------------------------------- model against the reference --

@pytest.mark.parametrize("length", [6, 45])
def test_model_logits_are_the_references(model, reference, length):
    """Under `topk` keys (dense) and five times past it."""
    ids = np.array(_prompts([length])[0], np.int32)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    want = reference.logits_at(CFG, SEED, ids, np.arange(len(ids)))
    assert got.shape == want.shape == (length, CFG["vocab_size"])
    err = np.abs(got - want).max()
    assert err < 2e-5 * np.abs(want).max()
    low = reference.logits_at(CFG, SEED, ids, np.arange(len(ids)),
                              quant="int8")
    assert np.abs(low - want).max() > 100 * err


def test_prefill_selects_the_references_sets(model, reference):
    """Layer 0's index scores and selection, by the program's own
    projections and kernels, against the reference's I and S_t."""
    ids = _prompts([40], stream=9)[0]
    keeps = _reference_selections(reference, ids)
    assert keeps[0].sum(-1).tolist() == [min(t + 1, TOPK)
                                         for t in range(40)]
    attn, x, ws = _layer0_inputs(model, ids)
    wqa, gqa, wqb, wkva, gkv, wkvb, wo, wiq, *index = ws
    pos = jnp.arange(40, dtype=jnp.int32)[None]
    c_q, ang, _, ki, w = attn._keys(x, pos, wqa, gqa, wkva, gkv, *index)
    qi = attn._index_queries(c_q, ang, wiq)
    scores = sa.prefill_index_scores(qi, w, ki, sa.chunk_key_blocks(
        jnp.ones((1, 40), jnp.bool_), 40, 1)[0])
    seen = np.tril(np.ones((40, 40), bool))
    got = np.asarray(sa.select_topk(scores, jnp.asarray(seen)[None], TOPK))[0]
    assert np.array_equal(got, keeps[0])


def test_prefill_then_decode_agrees_with_the_full_forward(model, reference):
    """Contexts from under `topk` to 50, six times it, over pages of 8
    (a prompt of 8 and one of 16 end on a page's last row); a
    left-padded batch of unequal lengths; more requests than slots."""
    prompts = _prompts([5, 17, 8, 30, 16, 7, 23, 3, 40])
    pred, outs = _served(model, prompts)
    assert all(len(o) == 10 for o in outs)
    rec = served_tokens.compare(reference, CFG, SEED,
                                list(zip(prompts, outs)), TIGHT, 9)
    assert rec["correct"], rec
    assert rec["positions_compared"] == 90
    assert pred.stats["prefills"] == 9 and pred.B == 4
    # one prompt a prefill program (`long_prefill_rows`)
    assert pred._prefill_rows == 1 and pred.stats["prefill_batches"] == 9


def test_decode_logits_are_the_references(model, reference, monkeypatch):
    """Not the tokens alone: the float32 logits a decode step gives at
    every position, against the reference's full forward pass."""
    got = []
    real = ContinuousBatchingPredictor._raw_decode_step

    def spy(self, *args):
        keep = self.model.forward

        def forward(*a, **kw):
            logits, caches = keep(*a, **kw)
            jax.debug.callback(lambda v: got.append(np.asarray(v[0, 0])),
                               logits._value, ordered=True)
            return logits, caches

        self.model.forward = forward
        try:
            return real(self, *args)
        finally:
            self.model.forward = keep

    monkeypatch.setattr(ContinuousBatchingPredictor, "_raw_decode_step", spy)
    prompt = _prompts([21], stream=12)[0]
    _, outs = _served(model, [prompt], max_new=8, max_batch_size=1)
    jax.effects_barrier()
    ids = prompt + outs[0][:-1]
    want = reference.logits_at(CFG, SEED, ids, np.arange(21, len(ids)))
    assert len(got) >= 7
    for i in range(7):
        assert np.abs(got[i] - want[i]).max() < 2e-5 * np.abs(want).max()


def test_a_lower_precision_fails_the_limit(model, reference):
    prompts = _prompts([21, 34, 11, 40], stream=1)
    _, outs = _served(model, prompts, max_new=12)
    rec = served_tokens.compare(reference, CFG, SEED,
                                list(zip(prompts, outs)), TIGHT, 4,
                                control=("int8",))
    assert rec["correct"], rec
    assert rec["control_fails"]["int8"], rec["control"]


def test_logits_are_float32_whatever_the_weights(builder):
    low = builder.build(dict(CFG, dtype="bfloat16"), SEED)[0]
    ids = np.array(_prompts([20])[0], np.int32)
    with paddle.no_grad():
        got = low(paddle.to_tensor(ids[None]))._value
    assert got.dtype == jnp.float32
    rounded = got.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.mean(got != rounded)) > 0.9


def test_absorbed_attention_is_the_decompressed(model):
    """One layer: the last position of a 37-token sequence by the
    prefill's decompressed form, and by one absorbed decode step over
    pages that hold the 36 rows and index keys before it."""
    ids = _prompts([37], stream=13)[0]
    attn, x, _ = _layer0_inputs(model, ids)
    t = len(ids)
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    valid = jnp.ones((1, t), jnp.bool_)
    with paddle.no_grad():
        whole, (rows, keys) = attn(
            paddle.to_tensor(x), paddle.to_tensor(pos),
            paddle.to_tensor(valid), None,
            (paddle.to_tensor(sa.chunk_plan(valid, 8, TOPK)),
             paddle.to_tensor(sa.chunk_key_blocks(valid, 8, 1))))
    page, pps = 8, 8
    table = 1 + np.arange(pps, dtype=np.int32)[None]
    put = lambda a, lanes: jnp.zeros((pps + 1, page, lanes), jnp.float32).at[
        (np.arange(t - 1) // page + 1, np.arange(t - 1) % page)].set(
        la.latent_rows(a._value[0, :t - 1], jnp.zeros((1, 1, lanes))))
    entry = LatentCacheEntry(
        paddle.to_tensor(put(rows, 128)), paddle.to_tensor(table),
        paddle.to_tensor(np.array([t - 1], np.int32)),
        index_pages=paddle.to_tensor(put(keys, 128)))
    with paddle.no_grad():
        step, new, n_sel = attn(paddle.to_tensor(x[:, -1:]),
                                paddle.to_tensor(pos[:, -1:]), None, entry)
    assert int(n_sel._value[0]) == TOPK
    want = np.asarray(whole._value)[0, -1]
    assert np.abs(np.asarray(step._value)[0, 0] - want).max() \
        < 1e-5 * np.abs(want).max()
    # the step wrote the token's row and index key where the prefill
    # would have: page 5 (position 36), row 4
    assert np.allclose(np.asarray(new.pages._value)[5, 4, :40],
                       np.asarray(rows._value)[0, -1], atol=1e-6)
    assert np.allclose(np.asarray(new.index_pages._value)[5, 4, :16],
                       np.asarray(keys._value)[0, -1], atol=1e-6)


def test_decode_selects_the_references_sets(model, reference, monkeypatch):
    """Every decode step of one request, layer by layer: the rows the
    paged kernels keep are the reference's S_t of that position, which
    is what a prefill of the same position selects."""
    seen = []
    real = la.paged_sparse_latent_attention

    def spy(q, pages, index_pages, qi, w, tables, lens, topk, scale=None,
            interpret=False):
        out, keep = real(q, pages, index_pages, qi, w, tables, lens, topk,
                         scale, interpret)
        jax.debug.callback(lambda n, m: seen.append(
            (int(n[0]), np.asarray(m[0]))), lens, keep, ordered=True)
        return out, keep

    monkeypatch.setattr(la, "paged_sparse_latent_attention", spy)
    prompt = _prompts([19], stream=6)[0]
    _, outs = _served(model, [prompt], max_new=9, max_batch_size=1)
    jax.effects_barrier()
    ids = prompt + outs[0][:-1]
    keeps = _reference_selections(reference, ids)
    layers = CFG["num_hidden_layers"]
    assert len(seen) >= 8 * layers
    for call, (n_keys, keep) in enumerate(seen[:8 * layers]):
        t = n_keys - 1                       # the query's position
        assert t == 19 + call // layers
        want = keeps[call % layers][t, :n_keys]
        assert np.array_equal(keep[:n_keys], want), (call, t)
        assert not keep[n_keys:].any() and keep.sum() == TOPK


def test_idle_slots_attend_over_nothing(model, reference, monkeypatch):
    """One request in a predictor of four slots: every decode step hands
    the kernels the request's rows and a length of 0 for the three slots
    that carry none, and the served tokens are the reference's."""
    seen = []
    real = la.paged_sparse_latent_attention

    def spy(q, pages, index_pages, qi, w, tables, lens, *rest):
        jax.debug.callback(lambda n: seen.append(np.asarray(n)), lens,
                           ordered=True)
        return real(q, pages, index_pages, qi, w, tables, lens, *rest)

    monkeypatch.setattr(la, "paged_sparse_latent_attention", spy)
    prompt = _prompts([19], stream=16)[0]
    pred, outs = _served(model, [prompt], max_new=9)
    jax.effects_barrier()
    rec = served_tokens.compare(reference, CFG, SEED, [(prompt, outs[0])],
                                TIGHT, 1)
    assert rec["correct"], rec
    layers = CFG["num_hidden_layers"]
    assert len(seen) == pred.stats["decode_steps"] * layers >= 8 * layers
    for call, lens in enumerate(seen):
        assert sorted(lens.tolist()) == [0, 0, 0, 20 + call // layers]


def test_short_contexts_are_plain_latent_attention(builder, reference):
    """At most `topk` keys everywhere: the indexer changes nothing."""
    wide = dict(CFG, index_topk=64)
    model = builder.build(wide, SEED)[0]
    prompts = _prompts([5, 17, 30], stream=7)
    _, outs = _served(model, prompts, max_new=6)
    rec = served_tokens.compare(reference, wide, SEED,
                                list(zip(prompts, outs)), TIGHT, 3)
    assert rec["correct"], rec
    ids = prompts[2] + outs[2][:-1]
    for keep in _reference_selections(reference, ids, wide):
        assert np.array_equal(keep, np.tril(np.ones(keep.shape, bool)))


def test_a_reused_slot_and_page_owe_nothing_to_their_last_tenant(model):
    """One slot: the second request gets the first one's pages back
    (index pages included) with whatever lies on them, stale rows and
    index keys past its own among them; so does a request served from a
    pool filled with junk whose index keys would score highest."""
    long, short = _prompts([40, 6], stream=3)
    pred = ContinuousBatchingPredictor(model, **dict(GEO, max_batch_size=1))
    first = pred.generate([long], max_new_tokens=12)[0]
    reused = pred.generate([short], max_new_tokens=12)[0]
    fresh = _served(model, [short], max_new=12, max_batch_size=1)[1][0]
    assert reused == fresh
    assert first == _served(model, [long], max_new=12)[1][0]
    junk = ContinuousBatchingPredictor(model, **dict(GEO, max_batch_size=1))
    for name in ("k", "index"):
        setattr(junk.pool, name, [jnp.full_like(a, 37.0)
                                  for a in getattr(junk.pool, name)])
    assert junk.generate([long], max_new_tokens=12)[0] == first


def test_copy_on_write_copies_the_index_page(model):
    pred = ContinuousBatchingPredictor(model, **GEO)
    pred.pool.index = [a.at[3].set(float(i + 1))
                       for i, a in enumerate(pred.pool.index)]
    pred.pool.copy_into(3, 5)
    for i, a in enumerate(pred.pool.index):
        assert float(a[5].min()) == float(a[5].max()) == float(i + 1)


# ------------------------------- Pallas kernels against their XLA forms --

def _paged_case(rng, slots=3, heads=8, page=8, pps=16, lens=(100, 37, 8)):
    pool = slots * pps + 1
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    pages = f(pool, page, 128).at[..., 40:].set(0.0)
    index = f(pool, page, 128).at[..., 16:].set(0.0)
    tables = jnp.asarray(1 + rng.permutation(pool - 1)[:slots * pps].reshape(
        slots, pps), jnp.int32)
    return (f(slots, heads, 40), pages, index, f(slots, 4, 16),
            f(slots, 4), tables, jnp.asarray(lens, jnp.int32))


def test_index_score_kernel_is_the_xla_form():
    q, pages, index, qi, w, tables, lens = _paged_case(
        np.random.default_rng(1))
    got = pa.paged_index_scores(qi, w, index, tables, lens, interpret=True)
    want = pa._index_scores_xla(pa.index_key_rows(qi, index), w, index,
                                tables, lens)
    assert np.array_equal(np.isinf(np.asarray(got)),
                          np.isinf(np.asarray(want)))
    live = np.isfinite(np.asarray(want))
    assert np.abs(np.asarray(got)[live] - np.asarray(want)[live]).max() < 1e-4


@pytest.mark.parametrize("selected", [False, True])
def test_latent_kernel_is_the_xla_form(selected):
    """The latent decode kernel over every live row, and under a
    selection's mask."""
    rng = np.random.default_rng(2)
    q, pages, index, qi, w, tables, lens = _paged_case(rng)
    keep = None
    if selected:
        keep = jnp.asarray(rng.random((3, 128)) < 0.3) \
            & (jnp.arange(128)[None, :] < lens[:, None])
        keep = keep.at[:, 0].set(True)
    qr = la.latent_rows(q, pages)
    got = la._latent_attention_pallas(qr, pages, tables, lens, 0.2, True,
                                      keep=keep)
    want = la._latent_attention_xla(qr, pages, tables, lens, 0.2, keep)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_sparse_latent_decode_in_interpret_mode_is_the_xla_route():
    """Scores, selection and masked attention together; the kernels'
    route says so in `kernels.paged_decode`."""
    q, pages, index, qi, w, tables, lens = _paged_case(
        np.random.default_rng(3))

    def kernel_count():
        return {s.labels["kernel"]: s.value for s in
                metrics.counter("kernels.paged_decode").samples()}
    before = kernel_count().get("paged_sparse_latent_attention", 0)
    got, keep_k = la.paged_sparse_latent_attention(
        q, pages, index, qi, w, tables, lens, TOPK, 0.2, interpret=True)
    assert kernel_count()["paged_sparse_latent_attention"] == before + 1
    want, keep_x = la.paged_sparse_latent_attention(
        q, pages, index, qi, w, tables, lens, TOPK, 0.2)
    assert np.array_equal(np.asarray(keep_k), np.asarray(keep_x))
    assert np.asarray(keep_k).sum(-1).tolist() == [TOPK] * 3
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_a_table_that_is_not_whole_blocks_takes_the_xla_route():
    assert la.sparse_latent_gate_reason(8, 128, 8, 12) == "table_tiling"
    assert la.sparse_latent_gate_reason(8, 128, 8, 16) is None
    assert la.sparse_latent_gate_reason(4, 128, 8, 16) == "head_count_tiling"
    assert la.sparse_latent_gate_reason(64, 640, 16, 1024) is None


def test_prefill_kernels_at_mla_shapes_are_their_xla_forms():
    """The chunk's index scores at a 128-wide key, and the masked flash
    kernel at `rep` 1 with 256-wide keys and values."""
    rng = np.random.default_rng(4)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    n, c, s, h, d = 1, 8, 256, 2, 256
    qi, w, ki = f(n, c, 4, 128), f(n, c, 4), f(n, s, 128)
    # the bucket's last chunk: every key block runs
    blocks = sa.chunk_key_blocks(jnp.ones((n, s), jnp.bool_), c, 1)[-1]
    got = sa.prefill_index_scores(qi, w, ki, blocks, interpret=True)
    assert np.abs(np.asarray(got) - np.asarray(
        sa._index_scores_xla(qi, w, ki))).max() < 1e-3
    keep = jnp.asarray(rng.random((n, c, s)) < 0.2).at[:, :, 0].set(True)
    q, k, v = f(n, c, h, d), f(n, h, s, d), f(n, h, s, d)
    got = sa.selected_attention(q, k, v, keep, blocks, d ** -0.5,
                                interpret=True)
    want = sa.selected_attention(q, k, v, keep, blocks, d ** -0.5)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4


def test_the_predictor_serves_through_the_kernels_in_interpret_mode(
        model, reference):
    from paddle_tpu.framework.flags import flag_value, set_flags
    before = {k: flag_value(k) for k in ("use_pallas_kernels",
                                         "pallas_interpret")}
    set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
    try:
        prompts = _prompts([33, 12], stream=14)
        _, outs = _served(model, prompts, max_new=6)
    finally:
        set_flags(before)
    rec = served_tokens.compare(reference, CFG, SEED,
                                list(zip(prompts, outs)), TIGHT, 2)
    assert rec["correct"], rec


# ------------------------------------------------------------- the layers --

def test_expert_shares_add_up_to_the_uncut_layer(reference):
    """16 experts in 16 shares of 1 (16 ranks that share a layer): the
    routed parts of the shares, with the shared expert (which every rank
    computes alike) counted once, add up to the uncut layer, in the
    program and against the reference."""
    from paddle_tpu.incubate.distributed.models.moe.dropless import (
        dropless_moe, group_limited_sigmoid_route)
    gw = reference.gw
    key = gw.base_key(SEED)
    index = jnp.int32(1)
    f32 = lambda tree: {n: a.astype(jnp.float32) for n, a in tree.items()}
    h = jax.random.normal(jax.random.PRNGKey(3), (37, CFG["hidden_size"]),
                          jnp.float32)
    w = f32(gw.moe(CFG, key, 1))
    shares = [[r] for r in range(16)]
    whole = np.asarray(reference.experts_layer(h, w, key, index, CFG, None))
    shared = np.asarray(reference._swiglu(h, w["shared_in"], w["shared_out"],
                                          None))
    scale = np.abs(whole).max()
    route = lambda logits: group_limited_sigmoid_route(
        logits, w["bias"], 4, 1, 1, 2.5, True)

    def program(held):
        bank = f32(gw.experts(CFG, key, 1, held))
        y, counts = dropless_moe(h, None, w["router"], bank["w_in"],
                                 bank["w_out"], held=tuple(held), top_k=4,
                                 route=route)
        return np.asarray(y), np.asarray(counts)

    parts = [program(s) for s in shares]
    ref_parts = [np.asarray(reference.routed_part(h, w, key, index, CFG,
                                                  None, held=s))
                 for s in shares]
    assert np.abs(sum(ref_parts) + shared - whole).max() < 1e-5 * scale
    assert np.abs(sum(y for y, _ in parts) + shared - whole).max() \
        < 1e-5 * scale
    assert np.abs(parts[3][0] - ref_parts[3]).max() < 1e-5 * scale
    # the shared expert is not small beside the routed part: counted 16
    # times it would not go unseen
    assert np.abs(shared).max() > 0.05 * scale
    # every assignment is somebody's: none dropped, none counted twice
    assert all(c[0] == 37 * 4 for _, c in parts)
    assert sum(c[1] for _, c in parts) == 37 * 4


def test_routing_without_groups_is_the_plain_top_k():
    """`n_group` 1, `topk_group` 1: the group-limited rule closes no
    group, and picks the k largest of `sigmoid(logits) + bias`."""
    from paddle_tpu.incubate.distributed.models.moe.dropless import (
        group_limited_sigmoid_route)
    logits = jax.random.normal(jax.random.PRNGKey(5), (64, 32)) * 2.0
    bias = jax.random.normal(jax.random.PRNGKey(6), (32,)) * 0.1
    gates, ids = group_limited_sigmoid_route(logits, bias, 4, 1, 1, 2.5)
    s = jax.nn.sigmoid(logits)
    _, want = jax.lax.top_k(s + bias[None], 4)
    assert np.array_equal(np.asarray(ids), np.asarray(want))
    chosen = np.take_along_axis(np.asarray(s), np.asarray(want), 1)
    assert np.abs(np.asarray(gates)
                  - 2.5 * chosen / chosen.sum(-1, keepdims=True)).max() < 1e-6


def test_the_index_rotation_turns_the_first_part_alone(model):
    attn, x, ws = _layer0_inputs(model, _prompts([12], stream=15)[0])
    v = jax.random.normal(jax.random.PRNGKey(7), (1, 12, 16))
    ang = jnp.ones((1, 12, 4)) * 0.3
    got = np.asarray(attn._rotate_index(v, ang))
    assert np.array_equal(got[..., 8:], np.asarray(v)[..., 8:])
    assert not np.allclose(got[..., :8], np.asarray(v)[..., :8])
    # interleaved pairs (2i, 2i + 1): a rotation keeps each pair's norm
    pair = lambda a: (a[..., 0:8:2] ** 2 + a[..., 1:8:2] ** 2)
    assert np.allclose(pair(got), pair(np.asarray(v)), atol=1e-5)


# ------------------------------------------- declared, derived, refused --

def test_layout_declares_latent_rows_with_an_index_key(model):
    layout = model.cache_layout()
    assert [(c.kind, c.shape, c.index_dim) for c in layout] \
        == [("latent", (40,), 16)] * 3
    pred = ContinuousBatchingPredictor(model, **GEO)
    pages = pred.pool.num_pages
    assert pages == GEO["max_batch_size"] * 16 + 1
    # rows and index keys on whole 128-lane rows; no K/V anywhere
    assert [a.shape for a in pred.pool.k] == [(pages, 8, 128)] * 3
    assert [a.shape for a in pred.pool.index] == [(pages, 8, 128)] * 3
    assert pred.pool.v == [None] * 3 and pred.state_pool is None
    assert metrics.gauge("serving.index_pool_bytes").value() \
        == 3 * pages * 8 * 128 * 4
    assert metrics.gauge("serving.latent_pool_bytes").value() \
        == 3 * pages * 8 * 128 * 4


def test_selection_counters_come_down_with_the_tokens(model):
    names = ("dsa.keys_live", "dsa.keys_selected", "mla.keys_live",
             "moe.assignments", "moe.assignments_local")

    def read():
        return {n: sum(s.value for s in metrics.counter(n).samples())
                for n in names}
    before = read()
    prompt = _prompts([20], stream=4)[0]
    _served(model, [prompt], max_new=6, max_batch_size=2)
    grew = {n: v - before[n] for n, v in read().items()}
    layers = CFG["num_hidden_layers"]
    # decode steps at 20, 21, ... cached rows, the new token's beside
    # them; a step in flight when the request ends may add one more
    steps = [21 + i for i in range(5)]
    assert grew["dsa.keys_live"] in (layers * sum(steps),
                                     layers * (sum(steps) + 26))
    assert grew["mla.keys_live"] == grew["dsa.keys_live"]
    assert grew["dsa.keys_selected"] in (layers * TOPK * 5,
                                         layers * TOPK * 6)
    # every expert is held: each assignment is local; the prefill's 20
    # tokens and the decode steps' in the two expert layers, top-4
    assert grew["moe.assignments"] == grew["moe.assignments_local"]
    assert grew["moe.assignments"] in (2 * 4 * 25, 2 * 4 * 26)


def test_prefill_chunk_counters_come_down_with_the_first_tokens(model):
    """A 20-token prompt left-padded into a bucket of 32, chunks of 8
    queries, top-8, a layer: one chunk of padding, one whose queries see
    at most 4 keys, two that select, each over the bucket's 32 keys."""
    def read():
        return {(n, s.labels.get("kind")): s.value
                for n in ("dsa.prefill_chunks", "dsa.prefill_keys_counted",
                          "dsa.prefill_keys_bucket", "dsa.prefill_key_blocks")
                for s in metrics.counter(n).samples()}
    before = read()
    pred, _ = _served(model, [_prompts([20], stream=6)[0]], max_new=2,
                      max_batch_size=2)
    assert pred._bucket_len(20) == 32
    after = read()
    layers = CFG["num_hidden_layers"]
    grew = {k: after[k] - before.get(k, 0) for k in after}
    assert grew == {("dsa.prefill_chunks", "padding"): layers,
                    ("dsa.prefill_chunks", "dense"): layers,
                    ("dsa.prefill_chunks", "selected"): 2 * layers,
                    ("dsa.prefill_keys_counted", None): 2 * 32 * layers,
                    ("dsa.prefill_keys_bucket", None): 4 * 32 * layers,
                    # a tile is a chunk and the bucket one key block: the
                    # three chunks that run visit it, padding or none
                    ("dsa.prefill_key_blocks", "attended"): 3 * layers,
                    ("dsa.prefill_key_blocks", "bucket"): 3 * layers}


def test_prefix_cache_is_derived_off_and_says_so(model):
    def fallbacks():
        return {tuple(sorted(s.labels.items())): s.value for s in
                metrics.counter("kernels.pallas_fallbacks").samples()}
    key = (("kernel", "prefix_cache"), ("reason", "sparse_index"))
    before = fallbacks().get(key, 0)
    pred = ContinuousBatchingPredictor(model, enable_prefix_cache=True,
                                       **GEO)
    assert pred.prefix_cache is None
    assert fallbacks()[key] == before + 1
    prompt = _prompts([24], stream=5)[0]
    a = pred.generate([prompt], max_new_tokens=4)
    assert pred.generate([prompt], max_new_tokens=4) == a
    assert pred.stats["prefix_hits"] == 0 and pred.stats["prefills"] == 2


@pytest.mark.parametrize("kw,name", [
    (dict(prefill_chunk_tokens=16), "prefill_chunk_tokens"),
    (dict(spec_draft_tokens=2), "spec_draft_tokens"),
    (dict(tp_degree=2), "tp_degree"),
    (dict(role="prefill"), "role='prefill'"),
    (dict(role="decode"), "role='decode'")])
def test_what_an_indexer_cannot_serve_is_refused_by_name(model, kw, name):
    with pytest.raises(ValueError, match=re.escape(name)) as e:
        ContinuousBatchingPredictor(model, **dict(GEO, **kw))
    assert "attention indexer" in str(e.value)


def test_a_query_span_is_refused_by_name(model):
    attn, x, _ = _layer0_inputs(model, _prompts([4], stream=16)[0])
    entry = LatentCacheEntry(None, None, None, index_pages=None)
    with pytest.raises(NotImplementedError, match="one token a slot"):
        attn(paddle.to_tensor(x), paddle.to_tensor(
            np.arange(4, dtype=np.int32)[None]), None, entry)


def test_paged_layers_share_one_index_width(model, monkeypatch):
    monkeypatch.setattr(model, "cache_layout", lambda: [
        LayerCache("latent", (40,), 16), LayerCache("latent", (40,))])
    with pytest.raises(ValueError, match="index_dim") as e:
        ContinuousBatchingPredictor(model, **GEO)
    assert "'state'" in str(e.value)


# --------------------------- the benchmark's kernel counts, by hand --

def test_sparse_latent_decode_bytes_against_a_hand_count():
    k = harness.load_module(ROOT, "kernels", "mla_sparse_decode")
    # 20 slots: 19 of 9000 rows attend to 2048, one of 1500 to all 1500;
    # a selected row is 576 x 2 B = 1152 B; 20 queries of 64 x 576 x 2 B
    # in, 20 x 64 x 512 x 2 B of summed latents back
    ctx = [9000] * 19 + [1500]
    selected = 19 * 2048 + 1500
    assert selected == 40_412
    want = selected * 1152 + 20 * 64 * 576 * 2 + 20 * 64 * 512 * 2
    assert want == 49_339_904
    assert k.bytes_per_call(ctx, 2048, 64, 512, 64, 2) == want
    assert k.flops_per_call(ctx, 2048, 64, 512, 64) == \
        selected * 64 * (2 * 576 + 2 * 512)
    peaks = harness.peaks_for("TPU v5 lite")
    # 139,264 operations a selected row against 1152 B: 121 a byte,
    # under the chip's 240, so bound by memory: 60 us a layer
    assert 64 * (2 * 576 + 2 * 512) // 1152 == 120
    assert k.least_seconds(ctx, 2048, 64, 512, 64, 2, peaks) == \
        pytest.approx(want / 819e9)
    # dense below topk: the whole context is the selection
    assert k.bytes_per_call([100], 2048, 64, 512, 64, 2) == \
        100 * 1152 + 64 * 576 * 2 + 64 * 512 * 2


def test_sparse_latent_prefill_operations_against_a_hand_count():
    k = harness.load_module(ROOT, "kernels", "mla_sparse_prefill")
    # a prompt of 5000 tokens: the first 2048 see 1 + 2 + ... + 2048 =
    # 2,098,176 keys, the other 2952 see 2048 each = 6,045,696
    assert k.pairs_of_prompt(5000, 2048) == 2_098_176 + 6_045_696
    assert k.pairs_of_prompt(100, 2048) == 5050
    # as a trace shows it: 4 chunks of 512 before any selection, 6 under
    # one (the last 120 queries are the next prompt's business)
    assert k.chunk_pairs(4, 0, 512, 2048) == 2_098_176
    assert k.chunk_pairs(4, 6, 512, 2048) == 2_098_176 + 6 * 512 * 2048
    # 64 heads x (2 x 256 + 2 x 256) = 65,536 operations a pair
    assert k.flops_per_call(1000, 64, 256, 256) == 65_536_000
    peaks = harness.peaks_for("TPU v5 lite")
    pairs = k.pairs_of_prompt(8192, 2048)
    # bound by the MXU: 4.9 ms a layer for an 8192-token prompt
    assert k.least_seconds(pairs, 8192, 64, 256, 256, 2, peaks) == \
        pytest.approx(pairs * 65_536 / 197e12)
    assert 8192 * 64 * 1024 * 2 / 819e9 < pairs * 65_536 / 197e12

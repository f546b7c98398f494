"""Keye-VL-2.0's language model (GQA under an attention indexer, routed
experts) at tiny sizes on the CPU: the program against the benchmark's
plain reference (`benchmarks/reference/keye_vl2.py`: float32, the whole
[T, T] index matrix, selection by a sort, experts one at a time) on
seeded weights, through the model alone and through
`ContinuousBatchingPredictor`'s prefill and decode programs at contexts
past `topk`; the selected sets against the reference's; the index pages'
contract (left padding, batching, slot and page reuse); the expert
shares against the uncut layer; what is derived off and refused.
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
from paddle_tpu.inference import ContinuousBatchingPredictor  # noqa: E402
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.models import keye_vl2  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402

from benchmarks.checks import served_tokens  # noqa: E402
from benchmarks.lib import harness  # noqa: E402

SEED = 5_000_000_033
TOPK = 8

# 3 layers, 8 query heads in 2 groups, head size apart from hidden /
# heads (16, not 8), 4 index heads of 8, top-8 selection in chunks of 8
# queries, 8 experts top-2; float32 so that the limits can be tight
CFG = dict(
    hidden_size=64, head_dim=16, num_hidden_layers=3,
    num_attention_heads=8, num_key_value_heads=2, vocab_size=384,
    moe_intermediate_size=32, num_experts_per_tok=2, num_experts=8,
    num_local_experts=8, experts_held=list(range(8)),
    published={"num_experts": 8}, rms_norm_eps=1e-6, rope_theta=10000.0,
    rope_scaling={"mrope_section": [2, 3, 3]},
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "topk": TOPK, "q_chunk_size": 8,
               "kv_chunk_size": 8},
    max_position_embeddings=256, initializer_range=0.25, dtype="float32")
GEO = dict(max_batch_size=4, page_size=8, max_seq_len=96)
# float32 on both sides: a served token is the reference's argmax but
# for a near-tie at the 6th decimal
TIGHT = {"gap_max": 2e-4, "gap_mean": 2e-5}


@pytest.fixture(scope="module")
def builder():
    return harness.load_module(ROOT, "models", "keye_vl2")


@pytest.fixture(scope="module")
def reference():
    return harness.load_module(ROOT, "reference", "keye_vl2")


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
    kw = reference.kw
    key = kw.base_key(SEED)
    x = kw.top(cfg, key)["embed"].astype(jnp.float32)[np.asarray(ids)]
    keeps = []
    for i in range(cfg["num_hidden_layers"]):
        w = {n: a.astype(jnp.float32)
             for n, a in kw.attn(cfg, key, i).items()}
        out, keep = reference.attention_layer(
            reference._rms(x, cfg["rms_norm_eps"]), w, cfg, None,
            with_selection=True)
        x = x + out
        x = x + reference.experts_layer(
            reference._rms(x, cfg["rms_norm_eps"]), w["router"], key,
            jnp.int32(i), cfg, None)
        keeps.append(np.asarray(keep))
    return keeps


# ------------------------------------------- model against the reference --

def test_model_logits_are_the_references(model, reference):
    ids = np.array(_prompts([45])[0], np.int32)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    want = reference.logits_at(CFG, SEED, ids, np.arange(len(ids)))
    assert got.shape == want.shape == (45, CFG["vocab_size"])
    err = np.abs(got - want).max()
    assert err < 2e-5 * np.abs(want).max()
    low = reference.logits_at(CFG, SEED, ids, np.arange(len(ids)),
                              quant="int8")
    assert np.abs(low - want).max() > 100 * err
    assert len(set(want.argmax(-1).tolist())) > 10


def test_prefill_selects_the_references_sets(model, reference):
    """Layer 0's index scores and selection, by the program's own
    projections and kernels, against the reference's I and S_t."""
    from paddle_tpu.kernels import sparse_attention as sa
    ids = np.array(_prompts([40], stream=9)[0], np.int32)
    keeps = _reference_selections(reference, ids)
    assert keeps[0].sum(-1).tolist() == [min(t + 1, TOPK)
                                         for t in range(40)]
    layer = model.model.layers[0]
    with paddle.no_grad():
        x = layer.input_layernorm(model.model.embed_tokens(
            paddle.to_tensor(ids[None])))._value
    attn = layer.self_attn
    ws = [p._value for p in attn._weights()]
    pos = jnp.arange(40, dtype=jnp.int32)[None]
    _, _, _, qi, w, ki = attn._project(x, pos, *ws[:3], *ws[4:])
    scores = sa.prefill_index_scores(qi, w, ki, sa.chunk_key_blocks(
        jnp.ones((1, 40), jnp.bool_), 40, 1)[0])
    seen = np.tril(np.ones((40, 40), bool))
    got = np.asarray(sa.select_topk(scores, jnp.asarray(seen)[None], TOPK))[0]
    assert np.array_equal(got, keeps[0])


def test_prefill_then_decode_agrees_with_the_full_forward(model, reference):
    """Contexts to 40, five times `topk`; a left-padded batch of unequal
    lengths; more requests than slots."""
    prompts = _prompts([5, 17, 9, 30, 12, 7, 23, 3])
    pred, outs = _served(model, prompts)
    assert all(len(o) == 10 for o in outs)
    rec = served_tokens.compare(reference, CFG, SEED,
                                list(zip(prompts, outs)), TIGHT, 8)
    assert rec["correct"], rec
    assert rec["positions_compared"] == 80
    assert pred.stats["prefills"] == 8 and pred.B == 4


def test_a_lower_precision_fails_the_limit(model, reference):
    prompts = _prompts([21, 34, 11, 40], stream=1)
    _, outs = _served(model, prompts, max_new=12)
    rec = served_tokens.compare(reference, CFG, SEED,
                                list(zip(prompts, outs)), TIGHT, 4,
                                control=("int8",))
    assert rec["correct"], rec
    assert rec["control_fails"]["int8"], rec["control"]


def test_logits_are_float32_whatever_the_weights(builder):
    """bfloat16 weights and activations, float32 logits: rounded to
    bfloat16 the best logits of the real size lie 1/64 apart, wider than
    most gaps between a token's two best (PERF.md, PR 33)."""
    low = builder.build(dict(CFG, dtype="bfloat16"), SEED)[0]
    ids = np.array(_prompts([20])[0], np.int32)
    with paddle.no_grad():
        got = low(paddle.to_tensor(ids[None]))._value
    assert got.dtype == jnp.float32
    rounded = got.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.mean(got != rounded)) > 0.9


def test_decode_selects_the_references_sets(model, reference, monkeypatch):
    """Every decode step of one request, layer by layer: the keys the
    paged kernels keep are the reference's S_t of that position."""
    seen = []
    real = pa.paged_sparse_attention

    def spy(q, k_pages, v_pages, index_pages, qi, w, tables, lens, topk,
            scale=None, interpret=False):
        out, keep = real(q, k_pages, v_pages, index_pages, qi, w, tables,
                         lens, topk, scale, interpret)
        jax.debug.callback(lambda n, m: seen.append(
            (int(n[0]), np.asarray(m[0]))), lens, keep, ordered=True)
        return out, keep

    monkeypatch.setattr(pa, "paged_sparse_attention", spy)
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
    the kernels the request's keys and a length of 0 for the three slots
    that carry none, and the served tokens are the reference's."""
    seen = []
    real = pa.paged_sparse_attention

    def spy(q, k_pages, v_pages, index_pages, qi, w, tables, lens, *rest):
        jax.debug.callback(lambda n: seen.append(np.asarray(n)), lens,
                           ordered=True)
        return real(q, k_pages, v_pages, index_pages, qi, w, tables, lens,
                    *rest)

    monkeypatch.setattr(pa, "paged_sparse_attention", spy)
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


def test_short_contexts_are_plain_gqa(builder, reference):
    """At most `topk` keys everywhere: the indexer changes nothing, and
    the model is the same model with dense causal attention."""
    wide = dict(CFG, sa_config=dict(CFG["sa_config"], topk=64))
    model = builder.build(wide, SEED)[0]
    prompts = _prompts([5, 17, 30], stream=7)
    _, outs = _served(model, prompts, max_new=6)
    rec = served_tokens.compare(reference, wide, SEED,
                                list(zip(prompts, outs)), TIGHT, 3)
    assert rec["correct"], rec
    ids = prompts[2] + outs[2][:-1]
    for keep in _reference_selections(reference, ids, wide):
        assert np.array_equal(keep, np.tril(np.ones(keep.shape, bool)))


def test_batched_left_padded_admission_is_each_alone(model):
    prompts = _prompts([9, 16, 12, 10], stream=2)       # one bucket: 16
    pred, together = _served(model, prompts)
    # a prefill program of this model takes two prompts at most
    assert pred.stats["prefill_batches"] == 2
    alone = [_served(model, [p])[1][0] for p in prompts]
    assert together == alone


def test_a_reused_slot_and_page_owe_nothing_to_their_last_tenant(model):
    """One slot: the second request gets the first one's pages back
    (index pages included) with whatever lies on them; so does a
    request served from a pool filled with junk."""
    long, short = _prompts([40, 6], stream=3)
    pred = ContinuousBatchingPredictor(model, **dict(GEO, max_batch_size=1))
    first = pred.generate([long], max_new_tokens=12)[0]
    reused = pred.generate([short], max_new_tokens=12)[0]
    fresh = _served(model, [short], max_new=12, max_batch_size=1)[1][0]
    assert reused == fresh
    assert first == _served(model, [long], max_new=12)[1][0]
    junk = ContinuousBatchingPredictor(model, **dict(GEO, max_batch_size=1))
    for name in ("k", "v", "index"):
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


# ------------------------------------------------------------- the layers --

def test_expert_shares_add_up_to_the_uncut_layer(reference):
    """16 experts in 8 shares of 2 (8 ranks that share a layer): the
    partial results add up to the uncut layer, in the program and
    against the reference. Nothing is replicated inside the expert layer
    (no shared expert), so nothing is counted twice."""
    from paddle_tpu.incubate.distributed.models.moe import dropless_moe
    kw = reference.kw
    cfg = dict(CFG, num_experts=16, num_local_experts=16,
               experts_held=list(range(16)), published={"num_experts": 16})
    key = kw.base_key(SEED)
    h = jax.random.normal(jax.random.PRNGKey(3), (37, cfg["hidden_size"]),
                          jnp.float32)
    router = kw.attn(cfg, key, 1)["router"].astype(jnp.float32)
    shares = [[2 * r, 2 * r + 1] for r in range(8)]

    def ref_layer(held):
        return np.asarray(reference.experts_layer(
            h, router, key, jnp.int32(1), cfg, None, held=held))

    def program(held):
        bank = {n: a.astype(jnp.float32)
                for n, a in kw.experts(cfg, key, 1, held).items()}
        y, counts = dropless_moe(h, None, router, bank["w_in"],
                                 bank["w_out"], held=tuple(held), top_k=2)
        return np.asarray(y), np.asarray(counts)

    whole = ref_layer(list(range(16)))
    scale = np.abs(whole).max()
    parts = [program(s) for s in shares]
    assert np.abs(sum(ref_layer(s) for s in shares) - whole).max() \
        < 1e-5 * scale
    assert np.abs(sum(y for y, _ in parts) - whole).max() < 1e-5 * scale
    assert np.abs(parts[3][0] - ref_layer(shares[3])).max() < 1e-5 * scale
    # every assignment is somebody's: none dropped, none counted twice
    assert all(c[0] == 37 * 2 for _, c in parts)
    assert sum(c[1] for _, c in parts) == 37 * 2


def test_gates_of_the_full_softmax_are_the_top_k_softmax():
    """`norm_topk_prob`: softmax over every expert, the k largest,
    renormalised = the softmax over the k largest logits, which is what
    the dropless layer computes; the same k either way."""
    logits = jax.random.normal(jax.random.PRNGKey(5), (64, 128)) * 3.0
    r = jax.nn.softmax(logits, axis=-1)
    rv, ri = jax.lax.top_k(r, 8)
    lv, li = jax.lax.top_k(logits, 8)
    assert np.array_equal(np.asarray(ri), np.asarray(li))
    assert np.abs(np.asarray(rv / rv.sum(-1, keepdims=True))
                  - np.asarray(jax.nn.softmax(lv, axis=-1))).max() < 1e-6


def test_mrope_of_a_text_token_is_the_plain_rotation():
    pos = jnp.asarray([[0, 1, 7, 300, 16000]], jnp.int32)
    plain = keye_vl2.rope_angles(pos, 128, 1e7)
    text = keye_vl2.mrope_angles(jnp.stack([pos] * 3), 128, 1e7,
                                 (16, 24, 24))
    assert np.array_equal(np.asarray(text), np.asarray(plain))
    # and the sections really are taken from t, h, w in turn
    triple = jnp.stack([pos, pos + 5, pos + 9])
    got = np.asarray(keye_vl2.mrope_angles(triple, 128, 1e7, (16, 24, 24)))
    for lo, hi, shift in ((0, 16, 0), (16, 40, 5), (40, 64, 9)):
        want = keye_vl2.rope_angles(pos + shift, 128, 1e7)
        assert np.array_equal(got[..., lo:hi], np.asarray(want)[..., lo:hi])
    with pytest.raises(ValueError, match="mrope_section"):
        keye_vl2.KeyeVL2Config(mrope_section=(16, 24, 20))


def test_a_position_triple_is_refused_by_name(model):
    ids = paddle.to_tensor(np.zeros((1, 4), np.int32))
    triple = paddle.to_tensor(np.zeros((3, 1, 4), np.int32))
    with pytest.raises(NotImplementedError, match="position_ids"):
        model(ids, position_ids=triple)


# ------------------------------------------- declared, derived, refused --

def test_layout_declares_the_index_pages(model):
    layout = model.cache_layout()
    assert [(c.kind, c.shape, c.index_dim) for c in layout] \
        == [("kv", (2, 16), 8)] * 3
    pred = ContinuousBatchingPredictor(model, **GEO)
    pages = pred.pool.num_pages
    assert pages == GEO["max_batch_size"] * 12 + 1
    # an index key lies on a whole 128-lane row, zeros past its 8
    assert [a.shape for a in pred.pool.index] == [(pages, 8, 128)] * 3
    assert pred.pool.k[0].shape == (pages, 8, 2, 16)
    assert pred.state_pool is None
    assert metrics.gauge("serving.index_pool_bytes").value() \
        == 3 * pages * 8 * 128 * 4


def test_selection_counters_come_down_with_the_tokens(model):
    def read():
        return {n: sum(s.value for s in metrics.counter(n).samples())
                for n in ("dsa.keys_live", "dsa.keys_selected")}
    before = read()
    prompt = _prompts([20], stream=4)[0]
    _served(model, [prompt], max_new=6, max_batch_size=2)
    after = read()
    layers = CFG["num_hidden_layers"]
    # decode steps at 20, 21, ... cached keys, the new token's beside
    # them; a step in flight when the request ends may add one more
    steps = [21 + i for i in range(5)]
    live = after["dsa.keys_live"] - before["dsa.keys_live"]
    chosen = after["dsa.keys_selected"] - before["dsa.keys_selected"]
    assert live in (layers * sum(steps), layers * (sum(steps) + 26))
    assert chosen in (layers * TOPK * 5, layers * TOPK * 6)


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


def test_a_layout_the_predictor_cannot_serve_names_what_it_can(model,
                                                               monkeypatch):
    from paddle_tpu.generation.kv_cache import LayerCache
    monkeypatch.setattr(model, "cache_layout", lambda: [
        LayerCache("kv", (2, 16), 8), LayerCache("kv", (2, 16))])
    with pytest.raises(ValueError, match="index keys") as e:
        ContinuousBatchingPredictor(model, **GEO)
    assert "'state'" in str(e.value)


# --------------------------- the benchmark's kernel counts, by hand --

def test_index_score_bytes_against_a_hand_count():
    k = harness.load_module(ROOT, "kernels", "dsa_indexer")
    # 20 slots of 7000 keys: 140,000 index keys of 64 x 2 B = 17,920,000
    # B; 20 queries of 16 x (64 x 2 + 4) B = 42,240 B; 140,000 float32
    # scores = 560,000 B
    ctx = [7000] * 20
    assert k.bytes_per_call(ctx, 16, 64, 2) == 17_920_000 + 42_240 + 560_000
    assert k.flops_per_call(ctx, 16, 64) == 140_000 * 16 * 131
    peaks = harness.peaks_for("TPU v5 lite")
    # bound by memory: 22.6 us a layer
    assert k.least_seconds(ctx, 16, 64, 2, peaks) == pytest.approx(
        18_522_240 / 819e9)
    assert k.flops_per_call(ctx, 16, 64) / 197e12 < 18_522_240 / 819e9


def test_sparse_attend_bytes_against_a_hand_count():
    k = harness.load_module(ROOT, "kernels", "dsa_sparse_attend")
    # 20 slots: 19 of 7000 keys attend to 2048, one of 1500 to all 1500;
    # a selected key is K and V of 4 x 128 x 2 B each = 2048 B;
    # q in and out 20 x 32 x 128 x 2 B each
    ctx = [7000] * 19 + [1500]
    selected = 19 * 2048 + 1500
    assert selected == 40_412
    assert k.bytes_per_call(ctx, 2048, 4, 128, 32, 2) == \
        selected * 2048 + 2 * 20 * 32 * 128 * 2
    assert k.flops_per_call(ctx, 2048, 32, 128) == 4 * 32 * 128 * selected
    peaks = harness.peaks_for("TPU v5 lite")
    # bound by memory: 0.101 ms a layer, against 0.35 ms for every live
    # key (19 x 7000 + 1500 keys of 2048 B)
    assert k.least_seconds(ctx, 2048, 4, 128, 32, 2, peaks) == \
        pytest.approx(83_091_456 / 819e9)
    # dense below topk: the whole context is the selection
    assert k.bytes_per_call([100], 2048, 4, 128, 32, 2) == \
        100 * 2048 + 2 * 32 * 128 * 2

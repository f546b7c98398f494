"""Granite-4.0-H (Mamba-2 + NoPE attention + dropless experts) at tiny
sizes on the CPU: the program against the benchmark's plain reference
(`benchmarks/reference/granite_hybrid.py`, float32, sequential scan,
experts one at a time) on seeded weights, through the model alone and
through `ContinuousBatchingPredictor`'s prefill and decode programs;
the state pool's contract (left padding, batching, slot reuse); the
expert shares against the uncut layer; what is derived off and refused;
and the Llama programs, which must not have moved.
"""
import collections
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
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.models.granite_hybrid import (ssd_chunked,  # noqa: E402
                                              ssd_sequential)
from paddle_tpu.observability import metrics  # noqa: E402

from benchmarks.checks import served_tokens  # noqa: E402
from benchmarks.lib import harness  # noqa: E402

SEED = 5_000_000_017

# both kinds of layer, 8 experts top-2 plus the shared one, the three
# multipliers not 1, two groups of B and C; float32 so that the limits
# can be tight
CFG = dict(
    hidden_size=64, intermediate_size=32, shared_intermediate_size=48,
    num_hidden_layers=4, layer_types=["mamba", "attention", "mamba", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, vocab_size=384,
    attention_multiplier=0.2, embedding_multiplier=1.5, logits_scaling=0.5,
    residual_multiplier=0.6, rms_norm_eps=1e-5, num_experts_per_tok=2,
    num_local_experts=8, experts_held=list(range(8)),
    published={"num_local_experts": 8},
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=2,
    mamba_d_conv=4, mamba_chunk_size=8, max_position_embeddings=256,
    initializer_range=0.25, dtype="float32")
GEO = dict(max_batch_size=4, page_size=8, max_seq_len=96)
# float32 on both sides: a served token is the reference's argmax but
# for a near-tie at the 6th decimal
TIGHT = {"gap_max": 2e-4, "gap_mean": 2e-5}


@pytest.fixture(scope="module")
def builder():
    return harness.load_module(ROOT, "models", "granite_hybrid")


@pytest.fixture(scope="module")
def reference():
    return harness.load_module(ROOT, "reference", "granite_hybrid")


@pytest.fixture(scope="module")
def model(builder):
    return builder.build(CFG, SEED)[0]


def _prompts(lengths, stream=0):
    rng = np.random.default_rng([SEED & 0xFFFFFFFF, stream])
    return [rng.integers(2, CFG["vocab_size"], n).tolist() for n in lengths]


# ------------------------------------------------------------ the scan --

@pytest.mark.parametrize("length,chunk,groups", [
    (32, 8, 1), (20, 8, 2), (7, 16, 1), (48, 16, 4)],
    ids=["whole-chunks", "ragged-tail", "shorter-than-a-chunk", "4-groups"])
def test_chunked_scan_is_the_sequential_recurrence(length, chunk, groups):
    k = jax.random.split(jax.random.PRNGKey(length), 6)
    b, h, p, n = 2, 8, 16, 16
    x = jax.random.normal(k[0], (b, length, h, p), jnp.float32)
    f32 = jnp.float32
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, length, h), f32))
    a_log = jax.random.normal(k[2], (h,), f32) * 0.5
    bm = jax.random.normal(k[3], (b, length, groups, n), f32)
    cm = jax.random.normal(k[4], (b, length, groups, n), f32)
    d = jax.random.normal(k[5], (h,), f32)
    y_seq, s_seq = ssd_sequential(x, dt, a_log, bm, cm, d)
    y_chk, s_chk = ssd_chunked(x, dt, a_log, bm, cm, d, chunk)
    scale = float(jnp.abs(y_seq).max())
    assert float(jnp.abs(y_seq - y_chk).max()) < 2e-5 * scale
    assert float(jnp.abs(s_seq - s_chk).max()) < 2e-5 * float(
        jnp.abs(s_seq).max())


# ------------------------------------------- model against the reference --

def test_model_logits_are_the_references(model, reference):
    ids = np.array(_prompts([45])[0], np.int32)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    want = reference.logits_at(CFG, SEED, ids, np.arange(len(ids)))
    assert got.shape == want.shape == (45, CFG["vocab_size"])
    err = np.abs(got - want).max()
    assert err < 2e-5 * np.abs(want).max()
    # the 8-bit control is two orders further off, and not degenerate:
    # the argmax moves along the sequence
    low = reference.logits_at(CFG, SEED, ids, np.arange(len(ids)),
                              quant="int8")
    assert np.abs(low - want).max() > 100 * err
    assert len(set(want.argmax(-1).tolist())) > 10


def _served(model, prompts, max_new=10, **kw):
    pred = ContinuousBatchingPredictor(model, **dict(GEO, **kw))
    return pred, pred.generate(prompts, max_new_tokens=max_new)


def test_prefill_then_decode_agrees_with_the_full_forward(model, reference):
    prompts = _prompts([5, 17, 9, 30, 12, 7, 23, 3])
    pred, outs = _served(model, prompts)
    assert all(len(o) == 10 for o in outs)
    rec = served_tokens.compare(reference, CFG, SEED,
                                list(zip(prompts, outs)), TIGHT, 8)
    assert rec["correct"], rec
    assert rec["positions_compared"] == 80
    # more requests than slots: slots were reused on the way
    assert pred.stats["prefills"] == 8 and pred.B == 4


def test_a_lower_precision_fails_the_limit(model, reference):
    prompts = _prompts([21, 34, 11, 40], stream=1)
    _, outs = _served(model, prompts, max_new=12)
    rec = served_tokens.compare(reference, CFG, SEED,
                                list(zip(prompts, outs)), TIGHT, 4,
                                control=("int8",))
    assert rec["correct"], rec
    assert rec["control_fails"]["int8"], rec["control"]
    assert rec["control"]["int8"]["gap_mean"]["fails"], rec["control"]


def test_batched_left_padded_admission_is_each_alone(model):
    """Unequal lengths in one prefill batch (and one bucket): every
    request's tokens are those it gets when served alone."""
    prompts = _prompts([9, 16, 12, 10], stream=2)       # one bucket: 16
    pred, together = _served(model, prompts)
    assert pred.stats["prefill_batches"] == 1
    alone = [_served(model, [p])[1][0] for p in prompts]
    assert together == alone


def test_a_reused_slot_is_a_fresh_one(model):
    """One slot: the second request inherits the first one's state row
    (and whatever the junk steps after its end left there)."""
    long, short = _prompts([40, 6], stream=3)
    pred = ContinuousBatchingPredictor(model, **dict(GEO, max_batch_size=1))
    first = pred.generate([long], max_new_tokens=12)[0]
    reused = pred.generate([short], max_new_tokens=12)[0]
    fresh = _served(model, [short], max_new=12, max_batch_size=1)[1][0]
    assert reused == fresh
    assert first == _served(model, [long], max_new=12)[1][0]


def test_expert_shares_add_up_to_the_uncut_layer(builder, reference):
    """Experts 0-3 here, 4-7 there: the two partial results, with the
    shared expert (which both compute alike) counted once, are the
    uncut layer, in the program and against the reference."""
    from paddle_tpu.incubate.distributed.models.moe import dropless_moe
    gw = reference.gw
    key = gw.base_key(SEED)
    h = jax.random.normal(jax.random.PRNGKey(3), (37, CFG["hidden_size"]),
                          jnp.float32)
    w = reference._f32(gw.moe(CFG, key, 2))

    def ref_layer(held):
        return np.asarray(reference._experts(
            h, w, key, jnp.int32(2), dict(CFG, experts_held=held), None))

    def program(held):
        bank = reference._f32(gw.experts(CFG, key, 2, held))
        y, counts = dropless_moe(h, None, w["router"], bank["w_in"],
                                 bank["w_out"], held=tuple(held), top_k=2)
        return np.asarray(y), np.asarray(counts)

    shared = np.asarray(reference._swiglu(h, w["shared_in"],
                                          w["shared_out"], None))
    whole = ref_layer(list(range(8)))
    lo, hi = ref_layer([0, 1, 2, 3]), ref_layer([4, 5, 6, 7])
    scale = np.abs(whole).max()
    assert np.abs(lo + hi - shared - whole).max() < 1e-5 * scale
    (y_lo, c_lo), (y_hi, c_hi) = program([0, 1, 2, 3]), program([4, 5, 6, 7])
    assert np.abs(y_lo + shared - lo).max() < 1e-5 * scale
    assert np.abs(y_lo + y_hi + shared - whole).max() < 1e-5 * scale
    # every assignment is somebody's: none dropped, none counted twice
    assert c_lo[0] == c_hi[0] == 37 * 2
    assert c_lo[1] + c_hi[1] == 37 * 2
    assert c_lo[2:].sum() == c_lo[1] and c_hi[2:].sum() == c_hi[1]
    assert 0 < c_lo[1] < 37 * 2


def test_routing_counters_come_down_with_the_tokens(builder):
    """`moe.assignments` counts what real tokens route (pads, dummy
    rows and idle slots are not counted); the held half sees its
    share."""
    half = dict(CFG, num_local_experts=4, experts_held=[0, 2, 4, 6])
    model = builder.build(half, SEED)[0]

    def read():
        out = {n: sum(s.value for s in metrics.counter(n).samples())
               for n in ("moe.assignments", "moe.assignments_local")}
        out["experts"] = {s.labels["expert"]: s.value for s in
                          metrics.counter("moe.expert_tokens").samples()}
        return out

    before = read()
    prompts = _prompts([13, 6, 21], stream=4)
    _served(model, prompts, max_new=5)
    after = read()
    layers, k = CFG["num_hidden_layers"], CFG["num_experts_per_tok"]
    # every prompt token once, and one decode token for each new token
    # but the first (the last step's token is never fed back); a step in
    # flight when its request ends may add a token's worth
    tokens = sum(len(p) for p in prompts) + 3 * (5 - 1)
    got = after["moe.assignments"] - before["moe.assignments"]
    assert tokens * layers * k <= got <= (tokens + 3) * layers * k
    local = after["moe.assignments_local"] - before["moe.assignments_local"]
    assert 0.2 * got < local < 0.8 * got
    assert set(after["experts"]) >= {"0", "2", "4", "6"}
    assert sum(after["experts"].values()) - sum(
        before["experts"].values()) == local
    assert metrics.gauge("serving.state_slots").value() == GEO[
        "max_batch_size"]
    assert metrics.gauge("serving.state_pool_bytes").value() > 0


# ------------------------------------------- derived off, and refused --

def test_prefix_cache_is_derived_off_and_says_so(model):
    def fallbacks():
        return {tuple(sorted(s.labels.items())): s.value for s in
                metrics.counter("kernels.pallas_fallbacks").samples()}
    key = (("kernel", "prefix_cache"), ("reason", "recurrent_state"))
    before = fallbacks().get(key, 0)
    pred = ContinuousBatchingPredictor(model, enable_prefix_cache=True,
                                       **GEO)
    assert pred.prefix_cache is None
    assert fallbacks()[key] == before + 1
    prompt = _prompts([24], stream=5)[0]
    a = pred.generate([prompt], max_new_tokens=4)
    assert pred.generate([prompt], max_new_tokens=4) == a
    assert pred.stats["prefix_hits"] == 0 and pred.stats["prefills"] == 2
    assert pred.export_page_span(prompt) is None     # not exportable


@pytest.mark.parametrize("kw,name", [
    (dict(prefill_chunk_tokens=16), "prefill_chunk_tokens"),
    (dict(spec_draft_tokens=2), "spec_draft_tokens"),
    (dict(tp_degree=2), "tp_degree"),
    (dict(role="prefill"), "role='prefill'"),
    (dict(role="decode"), "role='decode'")])
def test_what_needs_re_readable_state_is_refused_by_name(model, kw, name):
    with pytest.raises(ValueError, match=re.escape(name)) as e:
        ContinuousBatchingPredictor(model, **dict(GEO, **kw))
    assert "recurrent layers" in str(e.value)


def test_layout_is_the_models_declaration(model):
    kinds = [c.kind for c in model.cache_layout()]
    assert kinds == ["state", "kv", "state", "state"]
    pred = ContinuousBatchingPredictor(model, **GEO)
    assert len(pred.pool.k) == 1 and len(pred.state_pool.ssm) == 3
    assert pred.state_pool.ssm[0].shape == (GEO["max_batch_size"] + 1,
                                            8, 16, 16)
    assert pred.state_pool.ssm[0].dtype == jnp.float32
    assert pred.state_pool.conv[0].shape == (GEO["max_batch_size"] + 1,
                                             3, 8 * 16 + 2 * 2 * 16)
    llama = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
    assert {c.kind for c in llama.cache_layout()} == {"kv"}
    assert ContinuousBatchingPredictor(llama, **GEO).state_pool is None


# ------------------------------------------------ Llama did not move --

# Operations of the tiny Llama's serve programs as lowered at the parent
# commit (PR 26, 2dd77df): the predictor's loops over the declared
# layout must lower to the same list.
LLAMA_OPS_AT_PARENT = {'decode': {'stablehlo.add': 36,
            'stablehlo.and': 5,
            'stablehlo.broadcast_in_dim': 153,
            'stablehlo.compare': 36,
            'stablehlo.concatenate': 10,
            'stablehlo.constant': 94,
            'stablehlo.convert': 7,
            'stablehlo.divide': 9,
            'stablehlo.dot_general': 19,
            'stablehlo.dynamic_slice': 1,
            'stablehlo.exponential': 3,
            'stablehlo.gather': 8,
            'stablehlo.iota': 5,
            'stablehlo.maximum': 2,
            'stablehlo.multiply': 28,
            'stablehlo.negate': 5,
            'stablehlo.or': 2,
            'stablehlo.reduce': 12,
            'stablehlo.remainder': 2,
            'stablehlo.reshape': 35,
            'stablehlo.rsqrt': 5,
            'stablehlo.scatter': 4,
            'stablehlo.select': 26,
            'stablehlo.sign': 2,
            'stablehlo.slice': 16,
            'stablehlo.subtract': 3,
            'stablehlo.transpose': 2},
 'prefill': {'stablehlo.add': 28,
             'stablehlo.and': 7,
             'stablehlo.broadcast_in_dim': 141,
             'stablehlo.compare': 32,
             'stablehlo.concatenate': 8,
             'stablehlo.constant': 80,
             'stablehlo.convert': 8,
             'stablehlo.divide': 9,
             'stablehlo.dot_general': 19,
             'stablehlo.exponential': 3,
             'stablehlo.gather': 3,
             'stablehlo.iota': 6,
             'stablehlo.maximum': 3,
             'stablehlo.minimum': 1,
             'stablehlo.multiply': 28,
             'stablehlo.negate': 5,
             'stablehlo.or': 2,
             'stablehlo.reduce': 13,
             'stablehlo.remainder': 2,
             'stablehlo.reshape': 19,
             'stablehlo.rsqrt': 5,
             'stablehlo.scatter': 4,
             'stablehlo.select': 21,
             'stablehlo.sign': 2,
             'stablehlo.slice': 8,
             'stablehlo.subtract': 6,
             'stablehlo.transpose': 2}}


def _llama_programs():
    paddle.seed(7)
    llama = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
    pred = ContinuousBatchingPredictor(llama, **GEO)
    pred._ensure_ready()
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    B, pps = pred.B, pred.pages_per_seq
    with pred._trace_lock:
        dec = jax.jit(pred._raw_decode_step).lower(
            pred._p_vals, pred._b_vals, pred.pool.k, pred.pool.v,
            i32(B, pps), i32(B), i32(B))
        pre = jax.jit(pred._raw_prefill).lower(
            pred._p_vals, pred._b_vals, pred.pool.k, pred.pool.v,
            i32(2, 16), i32(2, 16), i32(2), i32(2, 2))
    return {"decode": dec.as_text(), "prefill": pre.as_text()}


def _ops(text):
    return dict(collections.Counter(
        re.findall(r"= \"?([a-z_]+\.[a-z_]+)", text)))


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_llama_programs_lower_to_the_parents_ops(program):
    assert _ops(_llama_programs()[program]) == LLAMA_OPS_AT_PARENT[program]

"""Ling-3.0-flash (KDA state rows beside MLA latent pages, group-limited
sigmoid routing, a shared expert, two leading dense layers) at tiny
sizes on the CPU: the program against the benchmark's plain reference
(`benchmarks/reference/ling_hybrid.py`, float32, the recurrence token by
token, MLA decompressed, experts one at a time) on seeded weights,
through the model alone and through `ContinuousBatchingPredictor`'s
prefill and decode programs; the chunked delta rule against the
sequential one; absorbed against decompressed latent attention; the
routing rule against one written out by hand; four expert shares against
the uncut layer; what the pools hold, what is derived off and refused.

Every tolerance is float32's: a bfloat16 state or an 8-bit matmul is
two orders past it, and the controls below show that they are.
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
from paddle_tpu.framework.flags import set_flags  # noqa: E402
from paddle_tpu.generation.kv_cache import LayerCache  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe.dropless import (  # noqa: E402
    dropless_moe, group_limited_sigmoid_route, softmax_topk_route)
from paddle_tpu.inference import ContinuousBatchingPredictor  # noqa: E402
from paddle_tpu.kernels import latent_attention as la  # noqa: E402
from paddle_tpu.kernels.kda import (kda_chunked, kda_sequential,  # noqa: E402
                                    kda_step, unit_lower_inverse)
from paddle_tpu.models import (LingHybridConfig,  # noqa: E402
                               LingHybridForCausalLM)
from paddle_tpu.observability import metrics  # noqa: E402

from benchmarks.checks import served_tokens  # noqa: E402
from benchmarks.lib import harness  # noqa: E402

SEED = 5_000_000_035
F32 = jnp.float32

# four layers: dense + KDA, experts + KDA, experts + MLA, experts + KDA;
# 16 experts in 4 groups of which 2 stay, top-4; chunks of 8 tokens in
# sub-chunks of 4; float32 so that the limits can be tight
CFG = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, num_hidden_layers=4,
    layer_group_size=3, first_k_dense_replace=1, num_attention_heads=4,
    head_dim=16, short_conv_kernel_size=4, kda_lower_bound=-5,
    kda_chunk_size=8, kda_sub_chunk_size=4, kda_segment_size=16,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=10000.0, num_experts=16, num_experts_per_tok=4, n_group=4,
    topk_group=2, routed_scaling_factor=2.5, norm_topk_prob=True,
    experts_held=list(range(16)), published={"num_experts": 16},
    vocab_size=384, rms_norm_eps=1e-6, max_position_embeddings=256,
    initializer_range=0.25, router_bias_std=0.1, dtype="float32")
GEO = dict(max_batch_size=4, page_size=8, max_seq_len=96)
# float32 on both sides: a served token is the reference's argmax but
# for a near-tie at the 6th decimal of logits of size 8
TIGHT = {"gap_max": 2e-4, "gap_mean": 2e-5}


@pytest.fixture(scope="module")
def builder():
    return harness.load_module(ROOT, "models", "ling_hybrid")


@pytest.fixture(scope="module")
def reference():
    return harness.load_module(ROOT, "reference", "ling_hybrid")


@pytest.fixture(scope="module")
def model(builder):
    return builder.build(CFG, SEED)[0]


def _prompts(lengths, stream=0):
    rng = np.random.default_rng([SEED & 0xFFFFFFFF, stream])
    return [rng.integers(2, CFG["vocab_size"], n).tolist() for n in lengths]


def _served(model, prompts, max_new=10, **kw):
    pred = ContinuousBatchingPredictor(model, **dict(GEO, **kw))
    return pred, pred.generate(prompts, max_new_tokens=max_new)


# ---------------------------------------------------------- the delta rule --

def _kda_inputs(length, b=2, h=3, dk=16, dv=8, low=-5.0, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed + length), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(k[0], (b, length, h, dk), F32)) * dk ** -0.5
    kk = unit(jax.random.normal(k[1], (b, length, h, dk), F32))
    v = jax.random.normal(k[2], (b, length, h, dv), F32)
    g = low * jax.random.uniform(k[3], (b, length, h, dk), F32)
    beta = jax.random.uniform(k[4], (b, length, h), F32)
    return q, kk, v, g, beta


@pytest.mark.parametrize("length,kw", [
    (64, dict(chunk=16, sub=4)), (150, dict(chunk=64, sub=16)),
    (5, dict(chunk=8, sub=4)), (100, dict(chunk=16, sub=8)),
    (37, dict(chunk=8, sub=8))],
    ids=["whole-chunks", "real-chunk-ragged-tail", "shorter-than-a-chunk",
         "two-sub-chunks", "one-sub-chunk"])
def test_chunked_delta_rule_is_the_sequential_recurrence(length, kw):
    """Outputs and final state of the WY form are the token-by-token
    recurrence's to float32 rounding (1e-5 of the largest: a state kept
    in bfloat16 is 4e-3 off, the last assertion)."""
    args = _kda_inputs(length)
    o_seq, s_seq = kda_sequential(*args)
    o_chk, s_chk = jax.jit(lambda *a: kda_chunked(*a, **kw))(*args)
    assert float(jnp.abs(o_seq - o_chk).max()) \
        < 1e-5 * float(jnp.abs(o_seq).max())
    assert float(jnp.abs(s_seq - s_chk).max()) \
        < 1e-5 * float(jnp.abs(s_seq).max())
    rounded = s_seq.astype(jnp.bfloat16).astype(F32)
    assert float(jnp.abs(s_seq - rounded).max()) \
        > 1e-3 * float(jnp.abs(s_seq).max())


def _strict_systems(case, c, lead=(2, 3, 5), dk=16):
    """N = diag(beta) tril(K K^T, -1), [2, 3, 5, c, c] float64, from
    L2-normalised keys (g = 0: no decay, the largest entries)."""
    rng = np.random.default_rng(c)
    keys = rng.normal(size=lead + (c, dk))
    beta = rng.uniform(size=lead + (c,))
    if case == "near-identical-keys":
        keys = keys[..., :1, :] + 1e-3 * keys
        beta = np.full_like(beta, 0.999)
    if case == "padding-rows":
        beta[..., ::3] = 0
        beta[..., :c // 4] = 0
    keys /= np.linalg.norm(keys, axis=-1, keepdims=True)
    return beta[..., None] * np.tril(
        np.einsum("...td,...id->...ti", keys, keys), -1)


@pytest.mark.parametrize("case", ["random-keys", "near-identical-keys",
                                  "padding-rows"])
@pytest.mark.parametrize("c,sub", [(64, 16), (16, 4), (16, 8), (8, 8)])
def test_block_inverse_is_the_triangular_solve(case, c, sub):
    """The chunk's inverse alone, from `sub`-wide diagonal blocks and
    matmuls, against XLA's float32 solve and a float64 solve of the same
    systems: 1e-6 of the largest entry, nearly identical keys under
    beta = 0.999 (the worst conditioning L2-normalised keys allow)
    included. A row with beta = 0 (padding) is the identity's."""
    strict = _strict_systems(case, c)
    rhs = np.random.default_rng(7).normal(size=strict.shape[:-1] + (24,))
    system = np.eye(c) + strict
    want = np.linalg.solve(system, rhs)
    inv = jax.jit(lambda n: unit_lower_inverse(n, sub))(
        jnp.asarray(strict, F32))
    assert inv.dtype == F32 and inv.shape == strict.shape
    got = np.einsum("...ti,...ix->...tx", np.asarray(inv, np.float64), rhs)
    assert np.abs(got - want).max() < 1e-6 * np.abs(want).max()
    exact = np.linalg.inv(system)
    assert np.abs(np.asarray(inv) - exact).max() < 1e-6 * np.abs(exact).max()
    xla = jax.lax.linalg.triangular_solve(
        jnp.asarray(system, F32), jnp.asarray(rhs, F32), left_side=True,
        lower=True, unit_diagonal=True)
    assert np.abs(got - np.asarray(xla)).max() < 1e-6 * np.abs(want).max()
    if case == "padding-rows":
        dead = np.all(strict == 0, axis=-1)
        assert dead[..., :c // 4].all()
        assert (np.asarray(inv)[dead] == np.eye(c)[np.nonzero(dead)[-1]]).all()


def _primitives(jaxpr):
    """Every primitive's name in a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def test_chunked_delta_rule_holds_no_triangular_solve():
    """The solve was a sixth of a prefill on the chip (XLA expands it
    into a sweep over rows): it may not come back through a refactoring.
    The walk finds one where there is one."""
    args = _kda_inputs(150)
    names = set(_primitives(jax.make_jaxpr(jax.jit(
        lambda *a: kda_chunked(*a, chunk=64, sub=16)))(*args).jaxpr))
    assert "dot_general" in names and "scan" in names
    assert not [n for n in names if "triangular" in n or "solve" in n]
    old = jax.make_jaxpr(jax.jit(lambda a, b: jax.lax.scan(
        lambda c, x: (c, jax.lax.linalg.triangular_solve(
            a, x, left_side=True, lower=True)), 0, b)[1]))(
        jnp.eye(4), jnp.ones((3, 4, 2)))
    assert "triangular_solve" in set(_primitives(old.jaxpr))


def test_decay_at_the_lower_bound_stays_inside_float32():
    """Every channel of every token at g = -5: the decay cumulated over
    a chunk of 64 is exp(-320), far below float32, and no factor the
    chunked form makes leaves its range (sub-chunks of 16: exp(80))."""
    q, k, v, g, beta = _kda_inputs(130)
    g = jnp.full_like(g, -4.999)
    o_seq, s_seq = kda_sequential(q, k, v, g, beta)
    o_chk, s_chk = kda_chunked(q, k, v, g, beta, chunk=64, sub=16)
    assert bool(jnp.all(jnp.isfinite(o_chk)) & jnp.all(jnp.isfinite(s_chk)))
    # exponents are differences of sums near 320: rounding 3e-5 each
    assert float(jnp.abs(o_seq - o_chk).max()) \
        < 1e-3 * float(jnp.abs(o_seq).max())
    assert float(jnp.abs(s_seq - s_chk).max()) < 1e-5


def test_padding_leaves_the_state_and_prefill_hands_it_to_decode():
    """A left-padded prompt (g = 0, beta = 0 on the padding) ends in the
    state of the prompt alone, and `kda_step` continues from it as the
    sequential recurrence continues."""
    q, k, v, g, beta = _kda_inputs(41, b=1)
    o_all, s_all = kda_sequential(q, k, v, g, beta)
    cut, pad = 30, 11
    lead = lambda a: jnp.pad(a[:, :cut], [(0, 0), (pad, 0)]
                             + [(0, 0)] * (a.ndim - 2))
    _, s_cut = kda_chunked(lead(q), lead(k), lead(v), lead(g), lead(beta),
                           chunk=8, sub=4)
    _, s_ref = kda_sequential(q[:, :cut], k[:, :cut], v[:, :cut],
                              g[:, :cut], beta[:, :cut])
    assert float(jnp.abs(s_cut - s_ref).max()) < 1e-6
    # and a prompt taken in two pieces, the state handed on, is the
    # prompt taken whole
    _, s_half = kda_chunked(q[:, :16], k[:, :16], v[:, :16], g[:, :16],
                            beta[:, :16], chunk=8, sub=4)
    o_rest, s_two = kda_chunked(q[:, 16:cut], k[:, 16:cut], v[:, 16:cut],
                                g[:, 16:cut], beta[:, 16:cut], s_half,
                                chunk=8, sub=4)
    assert float(jnp.abs(s_two - s_ref).max()) < 1e-6
    assert float(jnp.abs(o_rest - o_all[:, 16:cut]).max()) < 1e-6
    state = s_cut
    for t in range(cut, 41):
        state, o = kda_step(state, q[:, t], k[:, t], v[:, t], g[:, t],
                            beta[:, t])
        assert float(jnp.abs(o - o_all[:, t]).max()) < 1e-6
    assert float(jnp.abs(state - s_all).max()) < 1e-6


def test_state_kernel_advances_the_occupied_rows_alone():
    """The Pallas decode update (interpret mode) against the plain
    recurrence on the rows that carry a request; the others keep their
    state bit for bit and give zeros; the pool's last row is nobody's."""
    q, k, v, g, beta = (a[:, 0] for a in _kda_inputs(1, b=7, h=16, dk=16,
                                                      dv=128))
    state = jax.random.normal(jax.random.PRNGKey(9), (7, 16, 16, 128), F32)
    active = jnp.asarray([1, 0, 1, 1, 0, 1, 0], jnp.bool_)
    want_s, want_o = kda_step(state, q, k, v, g, beta)
    got_s, got_o = jax.jit(lambda *a: kda_step(*a, interpret=True))(
        state, q, k, v, g, beta, active)
    on = np.asarray(active)
    assert float(jnp.abs(got_s - want_s)[on].max()) < 1e-5
    assert float(jnp.abs(got_o - want_o)[on].max()) < 1e-5
    assert (np.asarray(got_s)[~on][:-1] == np.asarray(state)[~on][:-1]).all()
    assert (np.asarray(got_o)[~on][:-1] == 0).all()
    from paddle_tpu.kernels.kda import step_gate_reason
    assert step_gate_reason(32, 128, 128) is None
    assert step_gate_reason(4, 16, 16) == "head_count_tiling"


# ---------------------------------------------------- latent attention --

def _latent_case(seed=0, b=4, h=8, width=40, lanes=128, page=8, pps=6):
    rng = np.random.default_rng(seed)
    n_pages = b * pps + 3
    pages = np.zeros((n_pages, page, lanes), np.float32)
    pages[..., :width] = rng.normal(size=(n_pages, page, width))
    tables = rng.permutation(n_pages - 1)[:b * pps].reshape(b, pps)
    lens = np.array([1, page + 1, page * pps, 3 * page + 5][:b])
    q = rng.normal(size=(b, h, width)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(pages),
            jnp.asarray(tables, jnp.int32), jnp.asarray(lens, jnp.int32))


def test_latent_kernel_is_the_gathered_attention():
    """The Pallas kernel (interpret mode) over live pages only against
    the XLA form that gathers every slot's whole table."""
    q, pages, tables, lens = _latent_case()
    want = la._latent_attention_xla(la.latent_rows(q, pages), pages, tables,
                                    lens, 0.2)
    got = la.paged_latent_attention(q, pages, tables, lens, 0.2,
                                    interpret=True)
    assert got.shape == want.shape == (4, 8, 128)
    assert float(jnp.abs(got - want).max()) < 2e-6
    assert la.latent_gate_reason(32, 640, 16) is None
    assert la.latent_gate_reason(32, 576, 16) == "latent_dim_tiling"


def test_absorbed_decode_is_decompressed_attention(model, reference):
    """One MLA layer, a decode step in absorbed form over latent pages
    (the query takes W_bK in, the summed latent W_bV out) against the
    reference's decompressed attention at the same position: float32
    rounding apart (2e-5 of the output; an 8-bit W_b is 1e-2 off)."""
    from paddle_tpu.generation.kv_cache import LatentCacheEntry
    mla = model.model.layers[2].self_attn
    lw = reference.lw
    w = reference._f32(lw.mixer(CFG, lw.base_key(SEED), 2))
    t = 21
    x = jax.random.normal(jax.random.PRNGKey(4), (t, CFG["hidden_size"]), F32)
    want = np.asarray(reference._mla_mixer(x, w, CFG, None))
    low = np.asarray(reference._mla_mixer(x, w, CFG, "int8"))
    page, width = 8, CFG["kv_lora_rank"] + CFG["qk_rope_head_dim"]
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    with paddle.no_grad():
        out, (rows,) = mla(paddle.to_tensor(x[None]), paddle.to_tensor(pos),
                           paddle.to_tensor(jnp.ones((1, t), jnp.bool_)))
        whole = np.asarray(out._value)[0]
        assert np.abs(whole - want).max() < 2e-5 * np.abs(want).max()
        # the rows of positions 0 .. t - 2 on pages 2, 0, 1; the last
        # position decodes
        pages = jnp.zeros((4, page, 128), F32)
        kept = la.latent_rows(rows._value[0, :t - 1], pages)
        order = jnp.asarray([2, 0, 1], jnp.int32)
        pages = pages.at[order[jnp.arange(t - 1) // page],
                         jnp.arange(t - 1) % page].set(kept)
        entry = LatentCacheEntry(
            paddle.to_tensor(pages),
            paddle.to_tensor(jnp.asarray([[2, 0, 1]], jnp.int32)),
            paddle.to_tensor(jnp.asarray([t - 1], jnp.int32)))
        step, entry = mla(paddle.to_tensor(x[None, t - 1:]),
                          paddle.to_tensor(pos[:, t - 1:]), None, entry)
    got = np.asarray(step._value)[0, 0]
    assert np.abs(got - want[t - 1]).max() < 2e-5 * np.abs(want).max()
    assert np.abs(low - want).max() > 100 * np.abs(got - want[t - 1]).max()
    # the step wrote its own row where the table says: page 1, offset 4
    new = np.asarray(entry.pages._value)[1, (t - 1) % page, :width]
    assert np.abs(new - np.asarray(rows._value)[0, t - 1]).max() < 1e-6


# ----------------------------------------------------------- the routing --

def _by_hand(scores, bias, k, groups, keep, scale):
    """The rule in plain Python, one token at a time."""
    gates, ids = [], []
    for s in np.asarray(scores, np.float64):
        choice = s + np.asarray(bias, np.float64)
        size = len(s) // groups
        group_score = [np.sort(choice[g * size:(g + 1) * size])[-2:].sum()
                       for g in range(groups)]
        open_groups = sorted(range(groups),
                             key=lambda g: (-group_score[g], g))[:keep]
        cands = [e for e in range(len(s)) if e // size in open_groups]
        chosen = sorted(cands, key=lambda e: (-choice[e], e))[:k]
        raw = np.array([s[e] for e in chosen])
        gates.append(raw / raw.sum() * scale)
        ids.append(chosen)
    return np.array(gates), np.array(ids)


def test_routing_rule_is_the_one_written_by_hand(reference):
    rng = np.random.default_rng(11)
    logits = jnp.asarray(rng.normal(size=(64, 16)) * 1.5, F32)
    bias = jnp.asarray(rng.normal(size=(16,)) * 0.3, F32)
    gates, ids = group_limited_sigmoid_route(logits, bias, 4, 4, 2, 2.5)
    scores = np.asarray(jax.nn.sigmoid(logits))
    want_g, want_i = _by_hand(scores, bias, 4, 4, 2, 2.5)
    assert (np.asarray(ids) == want_i).all()
    assert np.abs(np.asarray(gates) - want_g).max() < 1e-6
    # and the reference's, which the served tokens are held to
    ref_g, ref_i = reference.route(jnp.asarray(scores), bias, CFG)
    assert (np.asarray(ref_i) == want_i).all()
    assert np.abs(np.asarray(ref_g) - want_g).max() < 1e-6
    # the group limit binds: some token's 4 largest choice scores are
    # not all in its two best groups
    free = np.argsort(-(scores + np.asarray(bias)), axis=1)[:, :4]
    assert any(set(a) != set(b) for a, b in zip(free, want_i))
    # the bias changes choices, and never a gate: gates are the chosen
    # experts' own scores over their sum
    _, unbiased = group_limited_sigmoid_route(logits, jnp.zeros(16), 4, 4, 2,
                                              2.5)
    assert (np.asarray(unbiased) != want_i).any()
    picked = np.take_along_axis(scores, want_i, axis=1)
    assert np.abs(np.asarray(gates)
                  - 2.5 * picked / picked.sum(1, keepdims=True)).max() < 1e-6
    assert np.abs(np.asarray(gates).sum(1) - 2.5).max() < 1e-5


def test_default_rule_is_softmax_over_the_top_k():
    """`dropless_moe` without `route` computes what it did: the other
    models' layers lower unchanged (tests/test_chip_compile.py pins
    their decode steps)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(19, 32)), F32)
    rw = jnp.asarray(rng.normal(size=(32, 8)) * 0.3, F32)
    wi = jnp.asarray(rng.normal(size=(8, 32, 24)) * 0.2, F32)
    wo = jnp.asarray(rng.normal(size=(8, 12, 32)) * 0.2, F32)
    kw = dict(held=tuple(range(8)), top_k=2)
    y0, c0 = dropless_moe(x, None, rw, wi, wo, **kw)
    y1, c1 = dropless_moe(x, None, rw, wi, wo, **kw,
                          route=lambda lg: softmax_topk_route(lg, 2))
    assert (np.asarray(y0) == np.asarray(y1)).all()
    assert (np.asarray(c0) == np.asarray(c1)).all()


def test_four_expert_shares_add_up_to_the_uncut_layer(reference):
    """Experts 0-3, 4-7, 8-11, 12-15 on four chips: the four partial
    results, the shared expert (which every rank computes alike) counted
    once, are the uncut layer, in the program and against the
    reference. 1e-5 of the layer's largest output: float32 sums in
    another order; an int8 layer is 1e-2 off."""
    lw = reference.lw
    key = lw.base_key(SEED)
    h = jax.random.normal(jax.random.PRNGKey(3), (37, CFG["hidden_size"]),
                          F32)
    w = reference._f32(lw.moe(CFG, key, 1))
    shares = [list(range(s, s + 4)) for s in (0, 4, 8, 12)]

    def ref_layer(held, quant=None):
        return np.asarray(reference._experts(
            h, w, key, jnp.int32(1), dict(CFG, experts_held=held), quant))

    def program(held):
        bank = reference._f32(lw.experts(CFG, key, 1, held))
        route = lambda lg: group_limited_sigmoid_route(
            lg, w["bias"], 4, 4, 2, 2.5)
        y, counts = dropless_moe(h, None, w["router"], bank["w_in"],
                                 bank["w_out"], held=tuple(held), top_k=4,
                                 route=route)
        return np.asarray(y), np.asarray(counts)

    shared = np.asarray(reference._swiglu(h, w["shared_in"],
                                          w["shared_out"], None))
    whole = ref_layer(list(range(16)))
    scale = np.abs(whole).max()
    parts = [ref_layer(s) for s in shares]
    assert np.abs(sum(parts) - 3 * shared - whole).max() < 1e-5 * scale
    got = [program(s) for s in shares]
    for (y, _), part in zip(got, parts):
        assert np.abs(y + shared - part).max() < 1e-5 * scale
    assert np.abs(sum(y for y, _ in got) + shared - whole).max() \
        < 1e-5 * scale
    assert np.abs(ref_layer(list(range(16)), "int8") - whole).max() \
        > 1e-3 * scale
    # every assignment is somebody's: none dropped, none counted twice
    assert all(c[0] == 37 * 4 for _, c in got)
    assert sum(c[1] for _, c in got) == 37 * 4
    assert all(c[2:].sum() == c[1] for _, c in got)


# ------------------------------------------- model against the reference --

def test_model_logits_are_the_references(model, reference):
    ids = np.array(_prompts([45])[0], np.int32)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    want = reference.logits_at(CFG, SEED, ids, np.arange(len(ids)))
    assert got.shape == want.shape == (45, CFG["vocab_size"])
    err = np.abs(got - want).max()
    assert err < 2e-5 * np.abs(want).max()
    # both controls are two orders further off, and the text is not
    # degenerate: the argmax moves along the sequence
    low = reference.logits_at(CFG, SEED, ids, np.arange(len(ids)),
                              quant="int8")
    assert np.abs(low - want).max() > 100 * err
    half = reference.logits_at(CFG, SEED, ids, np.arange(len(ids)),
                               state_dtype="bfloat16")
    assert np.abs(half - want).max() > 20 * err
    assert len(set(want.argmax(-1).tolist())) > 10


def test_prefill_then_decode_agrees_with_the_full_forward(model, reference):
    """Prompts that end inside a sub-chunk (5, 30), on a sub-chunk's
    edge (12), on a chunk's edge (16, 24), past several chunks (33),
    shorter than the convolution (3); more requests than slots."""
    prompts = _prompts([5, 16, 12, 30, 33, 24, 3, 9])
    pred, outs = _served(model, prompts)
    assert all(len(o) == 10 for o in outs)
    rec = served_tokens.compare(reference, CFG, SEED,
                                list(zip(prompts, outs)), TIGHT, 8)
    assert rec["correct"], rec
    assert rec["positions_compared"] == 80
    assert pred.stats["prefills"] == 8 and pred.B == 4
    assert pred._prefill_rows == 2      # the long prefill's two prompts


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
    """Two lengths in one prefill program (one bucket): each request's
    tokens are those it gets when served alone."""
    prompts = _prompts([9, 16], stream=2)                # one bucket: 16
    pred, together = _served(model, prompts)
    assert pred.stats["prefill_batches"] == 1
    alone = [_served(model, [p])[1][0] for p in prompts]
    assert together == alone


def test_a_reused_slot_is_a_fresh_one(model):
    """One slot: the second request inherits the first one's state row
    and latent pages (and what the junk steps after its end left)."""
    long, short = _prompts([40, 6], stream=3)
    pred = ContinuousBatchingPredictor(model, **dict(GEO, max_batch_size=1))
    first = pred.generate([long], max_new_tokens=12)[0]
    reused = pred.generate([short], max_new_tokens=12)[0]
    fresh = _served(model, [short], max_new=12, max_batch_size=1)[1][0]
    assert reused == fresh
    assert first == _served(model, [long], max_new=12)[1][0]


def test_latent_decode_through_the_kernel_serves_the_same_tokens(builder):
    """The decode step with the two Pallas kernels (interpret mode: the
    latent attention and the state update over the occupied rows)
    against the XLA forms, at 8 heads of 128 (the kernels take whole
    sublane tiles of heads and whole lanes of d_v)."""
    model = builder.build(dict(CFG, num_attention_heads=8, head_dim=128),
                          SEED)[0]
    prompts = _prompts([19, 7, 26], stream=6)
    _, plain = _served(model, prompts, max_new=6)
    set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
    try:
        before = {s.labels["kernel"]: s.value for s in
                  metrics.counter("kernels.paged_decode").samples()}
        pred, kernel = _served(model, prompts, max_new=6)
        interpreted_text = pred.lower_decode_step().as_text(debug_info=True)
        after = {s.labels["kernel"]: s.value for s in
                 metrics.counter("kernels.paged_decode").samples()}
    finally:
        set_flags({"use_pallas_kernels": False, "pallas_interpret": False})
    assert kernel == plain
    assert "kda.state_update" in interpreted_text
    assert after["paged_latent_attention"] \
        > before.get("paged_latent_attention", 0)


# -------------------------------------------- pools, counters, refusals --

def test_layout_is_state_rows_and_latent_pages_and_no_kv(model):
    kinds = [c.kind for c in model.cache_layout()]
    assert kinds == ["state", "state", "latent", "state"]
    assert model.long_prefill
    pred = ContinuousBatchingPredictor(model, **GEO)
    b = GEO["max_batch_size"]
    # one page array for the one MLA layer: a row a token for all heads
    # on whole 128-lane rows; nothing stands where V would
    assert [a.shape for a in pred.pool.k] == [(pred.capacity + 1, 8, 128)]
    assert pred.pool.v == [None] and pred.pool.index == []
    assert pred.pool.latent[0] is pred.pool.k[0]
    assert len(pred.state_pool.ssm) == 3
    assert pred.state_pool.ssm[0].shape == (b + 1, 4, 16, 16)
    assert pred.state_pool.ssm[0].dtype == jnp.float32
    assert pred.state_pool.conv[0].shape == (b + 1, 3, 3 * 4 * 16)
    assert metrics.gauge("serving.latent_pool_bytes").value() \
        == pred.pool.k[0].nbytes
    assert metrics.gauge("serving.state_pool_bytes").value() \
        == pred.state_pool.nbytes
    # pages come back when a request ends
    free = pred.pool.free_count
    pred.generate(_prompts([20], stream=7), max_new_tokens=3)
    assert pred.pool.free_count == free


def test_a_layout_the_loop_cannot_serve_is_refused(model):
    class Odd:
        config = model.config

        def __init__(self, layout):
            self.layout = layout

        def eval(self):
            return self

        def parameters(self):
            return model.parameters()

        def cache_layout(self):
            return self.layout

    state = model.cache_layout()[0]
    for layout in ([state, state],                       # nothing paged
                   [LayerCache("latent", (40,)), LayerCache("latent", (72,))],
                   [LayerCache("ring", (4,))]):
        with pytest.raises(ValueError, match="cache_layout"):
            ContinuousBatchingPredictor(Odd(layout), **GEO)


def test_step_counters_come_down_with_the_tokens(builder):
    quarter = dict(CFG, num_experts=4, experts_held=[0, 5, 10, 15])
    model = builder.build(quarter, SEED)[0]
    names = ("kda.rows_live", "mla.keys_live", "moe.assignments",
             "moe.assignments_local")

    def read():
        return {n: sum(s.value for s in metrics.counter(n).samples())
                for n in names}

    before = read()
    prompts = _prompts([13, 6, 21], stream=4)
    _served(model, prompts, max_new=5)
    got = {n: v - before[n] for n, v in read().items()}
    # decode steps: one for each new token but the first; a step in
    # flight when its request ends may add one
    steps = 3 * (5 - 1)
    assert 3 * steps <= got["kda.rows_live"] <= 3 * (steps + 3)
    keys = sum(len(p) + j for p in prompts for j in range(1, 5))
    assert keys <= got["mla.keys_live"] <= keys + 3 * (21 + 6)
    tokens = sum(len(p) for p in prompts) + steps
    assert tokens * 3 * 4 <= got["moe.assignments"] \
        <= (tokens + 3) * 3 * 4                 # 3 expert layers, top-4
    assert 0.05 * got["moe.assignments"] < got["moe.assignments_local"] \
        < 0.6 * got["moe.assignments"]


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


@pytest.mark.parametrize("kw,name", [
    (dict(prefill_chunk_tokens=16), "prefill_chunk_tokens"),
    (dict(spec_draft_tokens=2), "spec_draft_tokens"),
    (dict(tp_degree=2), "tp_degree"),
    (dict(role="prefill"), "role='prefill'")])
def test_what_needs_re_readable_state_is_refused_by_name(model, kw, name):
    with pytest.raises(ValueError, match=re.escape(name)) as e:
        ContinuousBatchingPredictor(model, **dict(GEO, **kw))
    assert "recurrent layers" in str(e.value)


def test_tiny_config_builds_and_serves():
    """`LingHybridConfig.tiny()` as the package exports it, with the
    constructor's own initialisation."""
    cfg = LingHybridConfig.tiny()
    assert cfg.layer_kinds == ("kda", "kda", "mla", "kda")
    paddle.seed(7)
    tiny = LingHybridForCausalLM(cfg)
    pred = ContinuousBatchingPredictor(tiny, max_batch_size=2, page_size=8,
                                       max_seq_len=64)
    outs = pred.generate([[3, 9, 27, 81, 5], [11, 12]], max_new_tokens=4)
    assert [len(o) for o in outs] == [4, 4]
    # published shape: MLA closes every group of six
    full = LingHybridConfig()
    assert full.layer_kinds.count("mla") == 7
    assert [i for i, k in enumerate(full.layer_kinds) if k == "mla"][:2] \
        == [5, 11]

"""Xing4.0 (four residual streams mixed by manifold-constrained hyper-
connections at every sublayer, latent attention at YaRN's frequencies,
dense then routed expert layers with a choice bias over a WHOLE bank)
at tiny sizes on the CPU, float32: the program against the benchmark's
plain reference (`benchmarks/reference/xing_moe.py`) on seeded weights,
through the model alone and through `ContinuousBatchingPredictor` and
the router; that the comparison SEES the mechanism (three damaged
programs fail it); the maps themselves (doubly stochastic, finite at
the clamp and on zero streams); the two kernels in interpret mode
against their XLA forms; YaRN's numbers against numbers written out by
hand; the expert layer against the reference's; what is declared,
counted and refused.
"""
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.generation.kv_cache import LayerCache  # noqa: E402
from paddle_tpu.inference import ContinuousBatchingPredictor  # noqa: E402
from paddle_tpu.kernels import hyper_connections as hc  # noqa: E402
from paddle_tpu.kernels import latent_attention as la  # noqa: E402
from paddle_tpu.models import keye_vl2, xing_moe  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402

from benchmarks.checks import served_tokens  # noqa: E402
from benchmarks.lib import harness  # noqa: E402

SEED = 5_000_000_045

# 3 layers (one dense, two with experts), 4 streams of 64, 8 heads of
# [16 | 8] on a 32-wide latent, values 16 wide, a 32-wide compressed
# query, 16 experts top-4 (all held) and a shared one; YaRN over an
# original context of 32 so that its blend lies inside the 4 pairs;
# float32 so that the limits can be tight
CFG = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=8,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, vocab_size=384,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
    n_group=1, topk_group=1, routed_scaling_factor=2.0, norm_topk_prob=True,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30,
    rope_scaling=dict(type="yarn", factor=64, beta_fast=32, beta_slow=1,
                      mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=32),
    rms_norm_eps=1e-6, rope_theta=10000.0, router_bias_std=0.05,
    max_position_embeddings=2048, initializer_range=0.25, dtype="float32")
GEO = dict(max_batch_size=4, page_size=8, max_seq_len=128)
# float32 on both sides: a served token is the reference's argmax but
# for a near-tie at the 6th decimal; logits within 2e-5 of the largest
TIGHT = {"gap_max": 2e-4, "gap_mean": 2e-5}
LOGIT_TOL = 2e-5
MAPS = dict(n=4, iters=20, eps=1e-6, hc_eps=1e-6, clamp=(-30.0, 30.0))


@pytest.fixture(scope="module")
def builder():
    return harness.load_module(ROOT, "models", "xing_moe")


@pytest.fixture(scope="module")
def reference():
    return harness.load_module(ROOT, "reference", "xing_moe")


@pytest.fixture(scope="module")
def model(builder):
    return builder.build(CFG, SEED)[0]


def _prompts(lengths, stream=0):
    rng = np.random.default_rng([SEED & 0xFFFFFFFF, stream])
    return [rng.integers(2, CFG["vocab_size"], n).tolist() for n in lengths]


def _served(model, prompts, max_new=10, **kw):
    pred = ContinuousBatchingPredictor(model, **dict(GEO, **kw))
    return pred, pred.generate(prompts, max_new_tokens=max_new)


def _compare(reference, prompts, outs, **kw):
    return served_tokens.compare(reference, CFG, SEED,
                                 list(zip(prompts, outs)), TIGHT,
                                 len(prompts), **kw)


# ------------------------------------------- model against the reference --

@pytest.mark.parametrize("length", [6, 45])
def test_model_logits_are_the_references(model, reference, length):
    ids = np.array(_prompts([length])[0], np.int32)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    want = reference.logits_at(CFG, SEED, ids, np.arange(len(ids)))
    assert got.shape == want.shape == (length, CFG["vocab_size"])
    err = np.abs(got - want).max()
    assert err < LOGIT_TOL * np.abs(want).max()
    low = reference.logits_at(CFG, SEED, ids, np.arange(len(ids)),
                              quant="int8")
    assert np.abs(low - want).max() > 100 * err


def test_prefill_gives_the_references_last_logits(model, reference):
    """A left-padded batch of unequal lengths: the last position's
    logits alone, a row of latents a layer."""
    prompts = _prompts([9, 21], stream=2)
    bucket = 32
    ids = np.zeros((2, bucket), np.int32)
    pos = np.zeros((2, bucket), np.int32)
    valid = np.zeros((2, bucket), bool)
    for i, p in enumerate(prompts):
        ids[i, -len(p):], pos[i, -len(p):] = p, np.arange(len(p))
        valid[i, -len(p):] = True
    with paddle.no_grad():
        logits, caches = model(
            paddle.to_tensor(ids), attn_mask=paddle.to_tensor(valid),
            position_ids=paddle.to_tensor(pos), use_cache=True)
    assert logits.shape == [2, 1, CFG["vocab_size"]] and len(caches) == 3
    assert tuple(caches[0][0].shape) == (2, bucket, 40)
    for i, p in enumerate(prompts):
        want = reference.logits_at(CFG, SEED, p, [len(p) - 1])[0]
        got = np.asarray(logits._value)[i, 0]
        assert np.abs(got - want).max() < LOGIT_TOL * np.abs(want).max()


def test_logits_are_float32_whatever_the_weights(builder):
    low = builder.build(dict(CFG, dtype="bfloat16"), SEED)[0]
    ids = np.array(_prompts([20])[0], np.int32)
    with paddle.no_grad():
        got = low(paddle.to_tensor(ids[None]))._value
    assert got.dtype == jnp.float32
    rounded = got.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.mean(got != rounded)) > 0.9
    layer = low.model.layers[1]
    assert layer.attn_hc.phi._value.dtype == jnp.bfloat16
    assert layer.attn_hc.alpha._value.dtype == jnp.float32
    assert layer.mlp_hc.beta._value.dtype == jnp.float32
    assert layer.moe.expert_bias._value.dtype == jnp.float32


# --------------------------------------------- through the serve loop --

def test_prefill_then_decode_agrees_with_the_full_forward(model, reference):
    """Contexts over pages of 8 (a prompt of 8 and one of 16 end on a
    page's last row), more requests than slots; the 8-bit control
    fails."""
    prompts = _prompts([5, 17, 8, 30, 16, 7, 23, 3, 40])
    pred, outs = _served(model, prompts)
    assert all(len(o) == 10 for o in outs)
    rec = _compare(reference, prompts, outs, control=("int8",))
    assert rec["correct"], rec
    assert rec["positions_compared"] == 90 and rec["argmax_share"] == 1.0
    assert rec["control_fails"]["int8"], rec["control"]
    assert pred.stats["prefills"] == 9 and pred.B == 4
    # one prompt a prefill program (`long_prefill_rows`)
    assert pred._prefill_rows == 1 and pred.stats["prefill_batches"] == 9


def test_served_through_the_router_with_an_idle_slot(model, reference):
    """Three requests of different lengths behind a router, four slots:
    one slot carries no request in any step, and its rows ride through
    both kernels' arithmetic all the same."""
    from paddle_tpu.serving import Router
    prompts = _prompts([33, 6, 19], stream=4)
    router = Router([ContinuousBatchingPredictor(model, **GEO)])
    try:
        handles = [router.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, (12, 9, 7))]
        outs = [[t for ev in h.stream(timeout=300) if ev.kind == "token"
                 for t in (ev.span or (ev.token,))] for h in handles]
    finally:
        router.shutdown(timeout=60.0)
    assert [len(o) for o in outs] == [12, 9, 7]
    rec = _compare(reference, prompts, outs)
    assert rec["correct"], rec
    assert rec["argmax_share"] == 1.0


def test_decode_logits_are_the_references(model, reference, monkeypatch):
    """Not the tokens alone: the float32 logits a decode step gives at
    every position, against the reference's full forward pass; the
    three slots that carry no request give finite logits too."""
    got = []
    real = ContinuousBatchingPredictor._raw_decode_step

    def spy(self, *args):
        keep = self.model.forward

        def forward(*a, **kw):
            logits, caches = keep(*a, **kw)
            jax.debug.callback(lambda v: got.append(np.asarray(v[:, 0])),
                               logits._value, ordered=True)
            return logits, caches

        self.model.forward = forward
        try:
            return real(self, *args)
        finally:
            self.model.forward = keep

    monkeypatch.setattr(ContinuousBatchingPredictor, "_raw_decode_step", spy)
    prompt = _prompts([21], stream=12)[0]
    _, outs = _served(model, [prompt], max_new=8)
    jax.effects_barrier()
    ids = prompt + outs[0][:-1]
    want = reference.logits_at(CFG, SEED, ids, np.arange(21, len(ids)))
    assert len(got) >= 7
    for i in range(7):
        assert got[i].shape == (4, CFG["vocab_size"])
        assert np.isfinite(got[i]).all()        # idle slots among them
        assert np.abs(got[i][0] - want[i]).max() \
            < LOGIT_TOL * np.abs(want).max()


def _identity_res(real):
    """`mhc_pre` with H_res = I in place of the projected map."""
    lanes = hc.coef_lanes(4)[8:]
    eye = jnp.eye(4, dtype=jnp.float32).reshape(-1)

    def pre(x, *args, **kw):
        u, coef = real(x, *args, **kw)
        return u, coef.at[:, lanes].set(eye)
    return pre


@pytest.mark.parametrize("damage", ["three_sweeps", "identity_res",
                                    "no_mscale"])
def test_a_damaged_mechanism_fails_the_limits(builder, reference, damage,
                                              monkeypatch):
    """The comparison sees the mechanism: the same program with 3 sweeps
    in place of 20, with H_res = I, and with the softmax scale without
    YaRN's m^2 each FAIL the limits the sound program meets."""
    cfg = dict(CFG)
    if damage == "three_sweeps":
        cfg["hc_sinkhorn_iters"] = 3
    elif damage == "identity_res":
        monkeypatch.setattr(xing_moe, "mhc_pre",
                            _identity_res(xing_moe.mhc_pre))
    else:
        monkeypatch.setattr(
            xing_moe.XingMoEConfig, "softmax_scale",
            property(lambda self: self.qk_head_dim ** -0.5))
    damaged = builder.build(cfg, SEED)[0]
    prompts = _prompts([21, 34, 11, 40], stream=1)
    _, outs = _served(damaged, prompts, max_new=12)
    rec = _compare(reference, prompts, outs)
    assert not rec["correct"], rec
    assert rec["compared"]["gap_mean"]["value"] > 100 * TIGHT["gap_mean"]


def test_idle_slots_attend_over_nothing_and_stay_finite(model, reference,
                                                        monkeypatch):
    """One request in a predictor of four slots: the latent kernel is
    handed a length of 0 for the three slots that carry none, their rows
    go through `mhc_pre` / `mhc_post` like any row, and every stream and
    every map of every row is finite."""
    lens_seen, finite = [], []
    real_attend, real_post = la.paged_latent_attention, xing_moe.mhc_post

    def attend(q, pages, tables, lens, *rest, **kw):
        jax.debug.callback(lambda n: lens_seen.append(np.asarray(n)), lens,
                           ordered=True)
        return real_attend(q, pages, tables, lens, *rest, **kw)

    def post(x, f, coef, **kw):
        out = real_post(x, f, coef, **kw)
        if x.shape[0] == 4:     # a decode step's rows
            jax.debug.callback(
                lambda a, b: finite.append(bool(a) and bool(b)),
                jnp.isfinite(out).all(), jnp.isfinite(coef).all(),
                ordered=True)
        return out

    from paddle_tpu.generation import kv_cache
    monkeypatch.setattr(kv_cache, "paged_latent_attention", attend,
                        raising=False)
    monkeypatch.setattr(la, "paged_latent_attention", attend)
    monkeypatch.setattr(xing_moe, "mhc_post", post)
    prompt = _prompts([19], stream=16)[0]
    pred, outs = _served(model, [prompt], max_new=9)
    jax.effects_barrier()
    rec = _compare(reference, [prompt], outs)
    assert rec["correct"], rec
    layers = CFG["num_hidden_layers"]
    assert len(lens_seen) == pred.stats["decode_steps"] * layers >= 8 * layers
    for call, lens in enumerate(lens_seen):
        assert sorted(lens.tolist()) == [0, 0, 0, 20 + call // layers]
    assert len(finite) == 2 * len(lens_seen) and all(finite)


def test_eos_and_cancel_return_every_page(model):
    """A request that meets its eos and one that is cancelled mid-stream
    leave no page behind; the third is served what it is served alone."""
    prompts = _prompts([19, 9, 27], stream=6)
    alone = _served(model, [prompts[2]], max_new=20)[1][0]
    first = _served(model, [prompts[0]], max_new=20)[1][0]
    eos = first[4]
    pred = ContinuousBatchingPredictor(model, **dict(GEO, eos_token_id=eos))
    stream = pred.generate_stream(prompts, max_new_tokens=20)
    kept = [[], [], []]
    for ev in stream:
        if ev.kind != "token":
            continue
        kept[ev.request] += list(ev.span or (ev.token,))
        if ev.request == 1 and ev.index >= 3:
            stream.cancel(1)
    assert pred.last_status[1] == "cancelled"
    # eos is stripped, with everything after it
    assert kept[0] == first[:first.index(eos)] and len(kept[0]) <= 4
    assert pred.pool.free_count == pred.capacity
    cut = alone.index(eos) if eos in alone else len(alone)
    assert kept[2] == alone[:cut]


def test_a_reused_slot_and_page_owe_nothing_to_their_last_tenant(model):
    long, short = _prompts([40, 6], stream=3)
    pred = ContinuousBatchingPredictor(model, **dict(GEO, max_batch_size=1))
    first = pred.generate([long], max_new_tokens=12)[0]
    reused = pred.generate([short], max_new_tokens=12)[0]
    assert reused == _served(model, [short], max_new=12,
                             max_batch_size=1)[1][0]
    junk = ContinuousBatchingPredictor(model, **dict(GEO, max_batch_size=1))
    junk.pool.k = [jnp.full_like(a, 37.0) for a in junk.pool.k]
    assert junk.generate([long], max_new_tokens=12)[0] == first


def test_the_predictor_serves_through_the_kernels_in_interpret_mode(
        builder, reference):
    """Streams of 128 so that both mHC kernels (and the latent decode
    kernel) take their Pallas route, 16 slots so that a step's rows are
    whole tiles; no fallback is noted for them."""
    from paddle_tpu.framework.flags import flag_value, set_flags
    cfg = dict(CFG, hidden_size=128)
    wide = builder.build(cfg, SEED)[0]
    before = {k: flag_value(k) for k in ("use_pallas_kernels",
                                         "pallas_interpret")}

    def lost():
        return sum(s.value for s in metrics.counter(
            "kernels.pallas_fallbacks").samples()
            if s.labels.get("kernel", "").startswith("mhc_"))

    set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
    try:
        n = lost()
        prompts = _prompts([16, 12], stream=14)
        pred = ContinuousBatchingPredictor(
            wide, **dict(GEO, max_batch_size=16))
        outs = pred.generate(prompts, max_new_tokens=5)
        assert lost() == n
    finally:
        set_flags(before)
    rec = served_tokens.compare(reference, cfg, SEED,
                                list(zip(prompts, outs)), TIGHT, 2)
    assert rec["correct"], rec


# ------------------------------------------------------------- the maps --

def _maps_case(t, c, dtype, seed=0, logit_std=1.0):
    """Streams, parameters drawn as `assumed.mhc_init` says, and a
    sublayer's output."""
    n = 4
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (t, n * c), jnp.float32).astype(dtype)
    phi = (jax.random.normal(k[1], (n * c, 24), jnp.float32)
           * logit_std / math.sqrt(n * c)).astype(dtype)
    b = 0.5 * logit_std * jax.random.normal(k[2], (24,), jnp.float32)
    b = b.at[8:].add(2.0 * jnp.eye(4, dtype=jnp.float32).reshape(-1))
    f = jax.random.normal(k[3], (t, c), jnp.float32).astype(dtype)
    return x, phi, jnp.ones((3,), jnp.float32), b, f


def _errors(coef):
    res = hc.unpack(coef, 4)[2]
    return (np.abs(np.asarray(res.sum(axis=2)) - 1).max(axis=1),
            np.abs(np.asarray(res.sum(axis=1)) - 1).max(axis=1))


def test_the_projected_map_is_doubly_stochastic():
    """After 20 sweeps every row sums to 1 (the last thing a sweep does)
    and, on maps of moderate spread, every column within 1e-4; under
    `assumed.mhc_init` (logits of std 1 around 2 I) the typical token's
    columns do too and the slowest of 512 within 2e-2; 3 sweeps leave
    them a hundred times further off."""
    x, phi, a, b, _ = _maps_case(512, 64, jnp.float32, logit_std=0.3)
    rows, cols = _errors(hc._mhc_pre_xla(x, phi, a, b, **MAPS)[1])
    assert rows.max() < 1e-5 and cols.max() < 1e-4
    x, phi, a, b, _ = _maps_case(512, 64, jnp.float32)
    rows, cols = _errors(hc._mhc_pre_xla(x, phi, a, b, **MAPS)[1])
    assert rows.max() < 1e-5
    assert np.median(cols) < 1e-4 and cols.max() < 2e-2
    few = _errors(hc._mhc_pre_xla(x, phi, a, b, **dict(MAPS, iters=3))[1])[1]
    assert np.median(few) > 100 * np.median(cols)


@pytest.mark.parametrize("route", ["xla", "kernel"])
@pytest.mark.parametrize("case", ["clamp_high", "clamp_low", "zeros"])
def test_the_maps_stay_finite_at_the_clamp_and_on_zero_streams(route, case):
    """`Ht_res` at +-1e4 meets the clamp before the exp; all-zero
    streams (rsqrt of eps alone) give the biases' maps."""
    x, phi, a, b, f = _maps_case(32, 128, jnp.float32)
    if case == "zeros":
        x = jnp.zeros_like(x)
    else:
        b = b.at[8:].set(1e4 if case == "clamp_high" else -1e4)
        b = b.at[8].set(-1e4 if case == "clamp_high" else 1e4)
    pre = (lambda *args: hc._mhc_pre_xla(*args, **MAPS)) if route == "xla" \
        else (lambda *args: hc._mhc_pre_pallas(*args, *MAPS.values(), True))
    u, coef = pre(x, phi, a, b)
    out = hc._mhc_post_xla(x, f, coef, 4) if route == "xla" \
        else hc._mhc_post_pallas(x, f, coef, 4, True)
    assert all(bool(jnp.isfinite(v).all()) for v in (u, coef, out))
    rows, _ = _errors(coef)
    assert rows.max() < 1e-4
    if case == "zeros":
        want = np.asarray(1.0 / (1.0 + np.exp(-np.asarray(b[:4]))))
        assert np.abs(np.asarray(hc.unpack(coef, 4)[0]) - want).max() < 1e-6


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tokens", [512, 32], ids=["prompt", "step"])
def test_the_kernels_are_their_xla_forms(tokens, dtype):
    """`mhc_pre` and `mhc_post` in interpret mode against their oracles
    at a prompt's row count (two blocks of tokens) and a step's (one
    short block)."""
    dt = jnp.dtype(dtype)
    x, phi, a, b, f = _maps_case(tokens, 128, dt, seed=tokens)
    u0, c0 = hc._mhc_pre_xla(x, phi, a, b, **MAPS)
    u1, c1 = hc._mhc_pre_pallas(x, phi, a, b, *MAPS.values(), True)
    assert u1.dtype == dt and c1.dtype == jnp.float32
    assert c1.shape == (tokens, hc.COEF_LANES)
    ulp = 2.0 ** -8 if dtype == "bfloat16" else 1e-6
    f32 = lambda v: np.asarray(v.astype(jnp.float32))
    assert np.abs(np.asarray(c0) - np.asarray(c1)).max() < 2e-6
    assert np.abs(f32(u0) - f32(u1)).max() <= ulp * np.abs(f32(u0)).max()
    y0 = hc._mhc_post_xla(x, f, c0, 4)
    y1 = hc._mhc_post_pallas(x, f, c0, 4, True)
    assert y1.dtype == dt and y1.shape == x.shape
    assert np.abs(f32(y0) - f32(y1)).max() <= ulp * np.abs(f32(y0)).max()


def test_the_public_calls_route_by_geometry(monkeypatch):
    """Interpret mode takes the kernels where the streams are whole
    lanes and the tokens whole tiles, and the XLA form (with a fallback
    noted) elsewhere; both give the same maps."""
    def lost(reason):
        return sum(s.value for s in metrics.counter(
            "kernels.pallas_fallbacks").samples()
            if s.labels == {"kernel": "mhc_pre", "reason": reason})
    x, phi, a, b, f = _maps_case(32, 128, jnp.float32)
    u, coef = hc.mhc_pre(x, phi, a, b, interpret=True, **MAPS)
    want = hc._mhc_pre_xla(x, phi, a, b, **MAPS)
    assert np.abs(np.asarray(coef) - np.asarray(want[1])).max() < 2e-6
    narrow = _maps_case(32, 64, jnp.float32)
    n = lost("stream_width_tiling")
    got = hc.mhc_pre(*narrow[:4], interpret=True, **MAPS)
    assert lost("stream_width_tiling") == n + 1
    assert np.array_equal(np.asarray(got[1]), np.asarray(
        hc._mhc_pre_xla(*narrow[:4], **MAPS)[1]))
    assert hc.mhc_gate_reason(5, 512, 4) == "token_tiling"
    assert hc.mhc_gate_reason(2048, 14336, 4) is None
    out = hc.mhc_post(x, f, coef, n=4, interpret=True)
    assert np.abs(np.asarray(out) - np.asarray(
        hc._mhc_post_xla(x, f, want[1], 4))).max() < 1e-5


def test_the_maps_are_the_references(reference):
    """One sublayer's three maps, the read and the write-back, program
    against reference on the benchmark's own draws."""
    xw = reference.xw
    w = xw.mhc(CFG, xw.base_key(SEED), 1, "ffn")
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    streams = jax.random.normal(jax.random.PRNGKey(5), (37, 4, 64),
                                jnp.float32)
    pre, post, res = reference.maps(streams, f32, CFG)
    u, coef = hc._mhc_pre_xla(streams.reshape(37, -1), f32["phi"], w["a"],
                              w["b"], **MAPS)
    got = hc.unpack(coef, 4)
    for mine, theirs in zip(got, (pre, post, res)):
        assert np.abs(np.asarray(mine) - np.asarray(theirs)).max() < 1e-5
    assert np.asarray(res).std(axis=0).min() > 1e-2     # a map a token
    fn = lambda h: jnp.tanh(h)
    want = reference.sublayer(streams, f32, CFG, None, fn)
    f = fn(reference._rms(u, CFG["rms_norm_eps"]))
    mine = hc._mhc_post_xla(streams.reshape(37, -1), f, coef, 4)
    assert np.abs(np.asarray(mine).reshape(37, 4, 64)
                  - np.asarray(want)).max() < 1e-5


# ----------------------------------------------------------------- YaRN --

# theta 10000, 64 rotated numbers, factor 64 over 4096 positions,
# beta_fast 32, beta_slow 1, by hand: pair i turns 4096 f_i / 2 pi times
# over the original context, f_i = 10000^(-i / 32); 32 turns at i =
# 10.47 (lo 10), 1 turn at i = 22.51 (hi 23); pair i between them keeps
# 1 - (i - 10) / 13 of f_i and takes the rest from f_i / 64
YARN = dict(dim=64, theta=10000.0, factor=64, original=4096, beta_fast=32,
            beta_slow=1)
BY_HAND = {0: 1.0, 5: 10000 ** (-5 / 32), 10: 10000 ** (-10 / 32),
           11: 10000 ** (-11 / 32) * (12 / 13 + 1 / 13 / 64),
           16: 10000 ** (-16 / 32) * (7 / 13 + 6 / 13 / 64),
           22: 10000 ** (-22 / 32) * (1 / 13 + 12 / 13 / 64),
           23: 10000 ** (-23 / 32) / 64, 31: 10000 ** (-31 / 32) / 64}


def test_yarn_numbers_are_the_hand_written_ones(reference):
    assert xing_moe.yarn_range(64, 10000.0, 4096, 32, 1) == (10, 23)
    inv = xing_moe.yarn_frequencies(**YARN)
    assert inv.shape == (32,) and inv.dtype == np.float32
    for i, want in BY_HAND.items():
        assert abs(inv[i] - want) < 1e-6 * want, i
    assert abs(BY_HAND[11] - 0.0389765) < 1e-7      # digits, not formulas
    assert abs(BY_HAND[23] - 2.08363e-5) < 1e-10
    assert np.all(np.diff(inv) < 0)
    cfg = xing_moe.XingMoEConfig()
    assert np.array_equal(cfg.inv_freq, inv)
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.4158883) < 1e-6
    assert abs(cfg.softmax_scale - 2.0047397 / math.sqrt(192)) < 1e-7
    assert cfg.rotation_scale == 1.0
    full = dict(CFG, qk_rope_head_dim=64, rope_scaling=dict(
        CFG["rope_scaling"], original_max_position_embeddings=4096))
    lo, hi, theirs = reference.yarn_inv_freq(full)
    assert (lo, hi) == (10, 23) and np.array_equal(theirs, inv)
    assert abs(reference.score_scale(dict(full, qk_nope_head_dim=128))
               - cfg.softmax_scale) < 1e-9


def test_rope_angles_default_path_is_bitwise_what_it_was():
    """The optional frequency table leaves the three earlier callers'
    angles as they were, bit for bit."""
    pos = jnp.arange(0, 5000, 37, dtype=jnp.int32).reshape(2, -1)
    for dim, theta in ((64, 1e6), (128, 1e7), (64, 25.6e6)):
        was = pos.astype(jnp.float32)[..., None] * (
            jnp.float32(1.0) / (jnp.float32(theta) ** (
                jnp.arange(0, dim, 2, dtype=jnp.float32)
                / jnp.float32(dim))))
        assert np.array_equal(np.asarray(keye_vl2.rope_angles(
            pos, dim, theta)), np.asarray(was))
    table = xing_moe.yarn_frequencies(**YARN)
    got = keye_vl2.rope_angles(pos, 64, 10000.0, inv_freq=table)
    assert np.array_equal(np.asarray(got), np.asarray(
        pos.astype(jnp.float32)[..., None] * table))


def test_yarn_scales_short_contexts_too(model):
    """Static scaling: the scores' scale is m^2 / sqrt(d) at every
    length, and the slow pairs turn a 64th as fast from position 0."""
    attn = model.model.layers[0].self_attn
    c = model.config
    assert abs(attn._scale() - (0.1 * math.log(64) + 1) ** 2
               / math.sqrt(24)) < 1e-7
    ang = np.asarray(attn._angles(jnp.arange(8, dtype=jnp.int32)[None]))[0]
    plain = np.asarray(keye_vl2.rope_angles(
        jnp.arange(8, dtype=jnp.int32), 8, c.rope_theta))
    lo, hi = xing_moe.yarn_range(8, 10000.0, 32, 32, 1)
    assert (lo, hi) == (0, 1)
    assert np.allclose(ang[:, 0], plain[:, 0])
    assert np.allclose(ang[:, 1:], plain[:, 1:] / 64, rtol=1e-6)


# --------------------------------------------------------- expert layer --

def test_the_whole_bank_expert_layer_is_the_references(model, reference):
    """`experts_held` None: the layer holds all 16 experts and its
    result is the reference's layer; the choice bias moves a choice and
    no gate."""
    xw = reference.xw
    key = xw.base_key(SEED)
    f32 = lambda tree: {n: a.astype(jnp.float32) for n, a in tree.items()}
    h = jax.random.normal(jax.random.PRNGKey(3), (37, 64), jnp.float32)
    w = f32(xw.moe(CFG, key, 1))
    want = np.asarray(reference.experts_layer(h, w, key, jnp.int32(1), CFG,
                                              None))
    layer = model.model.layers[1]
    assert layer.moe.held == tuple(range(16)) == tuple(reference.xw.held(CFG))
    assert model.config.experts_held is None \
        and layer.moe.w_in.shape[0] == 16
    with paddle.no_grad():
        x = paddle.to_tensor(np.asarray(h)[None])
        routed, counts = layer.moe(x, paddle.to_tensor(
            np.ones((1, 37), bool)))
        got = np.asarray((routed + layer.shared_mlp(x))._value)[0]
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    assert int(counts._value[0]) == int(counts._value[1]) == 37 * 4
    scores = jax.nn.sigmoid(h @ w["router"])
    gates, ids = reference.route(scores, w["bias"], CFG)
    plain, _ = reference.route(scores, jnp.zeros_like(w["bias"]), CFG)
    biased = reference.route(scores, 100.0 * w["bias"], CFG)
    assert not np.array_equal(np.asarray(ids), np.asarray(biased[1]))
    assert np.allclose(np.asarray(gates.sum(-1)), 2.0, atol=1e-5)
    assert np.allclose(np.asarray(biased[0].sum(-1)), 2.0, atol=1e-5)


# ------------------------------------- what is declared, counted, refused --

def test_layout_declares_latent_rows_and_no_drafter(model):
    assert model.cache_layout() == [LayerCache("latent", (40,))] * 3
    assert not hasattr(model, "drafter")
    assert model.long_prefill and model.long_prefill_rows == 1
    pred = ContinuousBatchingPredictor(model, **GEO)
    assert pred._drafter is None
    assert len(pred.pool.k) == 3 and all(v is None for v in pred.pool.v)
    assert pred.prefix_cache is None
    pred.generate(_prompts([9]), max_new_tokens=3)
    assert {s[0] for s in pred._traced_sigs} == {"prefill", "decode"}


def test_the_maps_counters_come_down_with_the_tokens(model):
    def read():
        g = lambda n: sum(s.value for s in metrics.gauge(n).samples())
        c = lambda n: sum(s.value for s in metrics.counter(n).samples())
        return {"row": g("mhc.sinkhorn_row_err_max"),
                "col": g("mhc.sinkhorn_col_err_max"),
                "mass": c("mhc.offdiag_mass"), "maps": c("mhc.maps"),
                "moe": c("moe.assignments"), "mla": c("mla.keys_live")}
    for name in ("mhc.sinkhorn_row_err_max", "mhc.sinkhorn_col_err_max"):
        metrics.gauge(name).reset()     # a damaged program's may stand there
    before = read()
    prompt = _prompts([11], stream=9)[0]
    pred, outs = _served(model, [prompt], max_new=6, max_batch_size=2)
    got = {k: v - before[k] for k, v in read().items()}
    steps = pred.stats["decode_steps"]
    # two maps a layer a token that belongs to a request: the prompt's
    # 11 and one a decode step; the idle slot's rows are not counted
    assert got["maps"] == 2 * 3 * (11 + steps) and steps >= 5
    assert got["moe"] == 4 * 2 * (11 + steps)
    assert got["mla"] > 3 * 11 * steps
    mean = got["mass"] / got["maps"]
    assert 0.02 < mean < 0.75       # mixed, and not uniform (0.75)
    after = read()
    # maxima, not sums: a run cannot read over a map's worst case
    assert 0 < after["row"] < 1e-4 and 0 < after["col"] < 0.1
    _served(model, [prompt], max_new=6, max_batch_size=2)
    again = read()
    assert again["row"] == after["row"] and again["col"] == after["col"]
    assert again["maps"] == after["maps"] + got["maps"]


@pytest.mark.parametrize("kw,name", [
    (dict(prefill_chunk_tokens=16), "prefill_chunk_tokens"),
    (dict(spec_draft_tokens=2), "spec_draft_tokens"),
    (dict(tp_degree=2), "tp_degree"),
    (dict(role="prefill"), "role='prefill'")])
def test_what_latent_pages_cannot_serve_is_refused_by_name(model, kw, name):
    with pytest.raises(ValueError) as err:
        ContinuousBatchingPredictor(model, **dict(GEO, **kw))
    assert name in str(err.value) and "latent pages" in str(err.value)


def test_only_yarn_is_built():
    with pytest.raises(ValueError, match="YaRN"):
        xing_moe.XingMoEConfig(rope_scaling=dict(type="linear", factor=2))
    with pytest.raises(ValueError, match="served is 1"):
        xing_moe.XingMoEConfig(rope_scaling=dict(
            type="yarn", factor=64, original_max_position_embeddings=4096,
            beta_fast=32, beta_slow=1, mscale=0.707, mscale_all_dim=1))


def test_stream_traffic_against_a_hand_count():
    """`benchmarks/kernels/mhc_stream.py`: a token a sublayer reads its
    streams once and writes them once, writes u and reads f."""
    kernel = harness.load_module(ROOT, "kernels", "mhc_stream")
    # 4 streams of 3584 in bfloat16: (4 + 4 + 1 + 1) x 3584 x 2 B
    assert kernel.bytes_per_token(4, 3584, 2) == 71680
    # 24 dot products over 14336 numbers, a multiply and an add each
    assert kernel.flops_per_token(4, 3584) == 2 * 24 * 14336
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # 2048 tokens x 12 sublayers: bound by the bytes, 2.15 ms
    least = kernel.least_seconds(2048 * 12, 4, 3584, 2, peaks)
    assert abs(least - 2048 * 12 * 71680 / 819e9) < 1e-12
    assert abs(least - 2.151e-3) < 1e-6

"""openPangu-Ultra-MoE (latent attention with a compressed query, four
norms a layer, dense then routed expert layers with a shared expert, and
its multi-token-prediction module BUILT as the model's own drafter) at
tiny sizes on the CPU, float32: the program against the benchmark's
plain reference (`benchmarks/reference/pangu_ultra_moe.py`) on seeded
weights, through the model alone and through
`ContinuousBatchingPredictor` with the drafter on and off; the
self-drafting tick's contract (lossless, the drafts the reference's,
accepted and rejected ticks leaving the pool as a fresh prefill would,
budgets and eos inside a two-token commit, cancellation) and its place
in the one-step pipeline (a tick chained to the one in flight: requests
that join and leave between two ticks, the junk row a finished request
leaves behind, the counter); the span kernel in interpret mode against its XLA form; the expert shares against
the uncut layer; what is declared, counted and refused.
"""
import itertools
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.generation.kv_cache import (Drafter,  # noqa: E402
                                            LatentCacheEntry, LayerCache)
from paddle_tpu.inference import ContinuousBatchingPredictor  # noqa: E402
from paddle_tpu.kernels import latent_attention as la  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from paddle_tpu.serving.streaming import ServeRequest  # noqa: E402

from benchmarks.lib import harness  # noqa: E402

SEED = 5_000_000_041

# 3 trunk layers (one dense, two with experts) and the MTP layer, 8 heads
# of [16 | 8] on a 32-wide latent, values 16 wide, a 32-wide compressed
# query, 16 experts top-4 and a shared one; float32 so that the limits
# can be tight
CFG = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=8,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, vocab_size=384,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
    routed_scaling_factor=2.5, norm_topk_prob=True,
    experts_held=list(range(16)), published={"n_routed_experts": 16},
    num_nextn_predict_layers=1, rms_norm_eps=1e-5, rope_theta=10000.0,
    max_position_embeddings=256, initializer_range=0.25, dtype="float32")
# 24 tokens: the drafter agrees with the trunk now and then by chance
SMALL = dict(CFG, vocab_size=24)
GEO = dict(max_batch_size=4, page_size=8, max_seq_len=128)
ON = dict(GEO, spec_draft_tokens=1)
# float32 on both sides: a served token is the reference's argmax but
# for a near-tie at the 6th decimal
TIGHT = {"gap_max": 2e-4, "gap_mean": 2e-5,
         "draft_gap_max": 2e-4, "draft_gap_mean": 2e-5}


@pytest.fixture(scope="module")
def builder():
    return harness.load_module(ROOT, "models", "pangu_ultra_moe")


@pytest.fixture(scope="module")
def reference():
    return harness.load_module(ROOT, "reference", "pangu_ultra_moe")


@pytest.fixture(scope="module")
def check():
    return (harness.load_module(ROOT, "checks", "served_and_drafted"),
            harness.load_module(ROOT, "checks", "served_tokens"))


@pytest.fixture(scope="module")
def model(builder):
    return builder.build(CFG, SEED)[0]


@pytest.fixture(scope="module")
def small(builder):
    return builder.build(SMALL, SEED)[0]


def _prompts(lengths, stream=0, vocab=CFG["vocab_size"]):
    rng = np.random.default_rng([SEED & 0xFFFFFFFF, stream])
    return [rng.integers(2, vocab, n).tolist() for n in lengths]


def _collected(stream, n, each=None):
    """([served tokens], [[(index of the served token, its draft)]],
    [token events]) a request, of `n`; `each(ev)` sees every event as
    the consumer would."""
    served = [[] for _ in range(n)]
    drafted = [[] for _ in range(n)]
    events = [[] for _ in range(n)]
    for ev in stream:
        if each is not None:
            each(ev)
        if ev.kind != "token":
            continue
        r = ev.request
        drafted[r] += [(len(served[r]) + i, d)
                       for i, d in enumerate(ev.drafted)]
        served[r] += list(ev.span)
        events[r].append(ev)
    return served, drafted, events


def _streamed(pred, prompts, max_new=10):
    return _collected(pred.generate_stream(prompts, max_new_tokens=max_new),
                      len(prompts))


def _served_in_waves(pred, waves, each=None):
    """Requests with budgets of their own, handed to the loop a wave at
    a time: `waves` = {pass of the loop: [(prompt, budget)]}."""
    calls = itertools.count()

    def intake():
        at = next(calls)
        if at > max(waves):
            return None
        return [ServeRequest(p, n) for p, n in waves.get(at, [])]

    return _collected(pred.serve_stream(intake),
                      sum(len(w) for w in waves.values()), each)


# ------------------------------------------- model against the reference --

@pytest.mark.parametrize("length", [6, 45])
def test_model_logits_are_the_references(model, reference, length):
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


def test_prefill_gives_the_references_first_token_and_first_draft(
        model, reference):
    """A left-padded batch of unequal lengths: the last position's
    logits, and the MTP module over every position with the token that
    followed it (the argmax itself at the last)."""
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
    assert len(caches) == 4 and caches.hidden is None
    for i, p in enumerate(prompts):
        want = reference.logits_at(CFG, SEED, p, [len(p) - 1])[0]
        got = np.asarray(logits._value)[i, 0]
        assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
        first = int(want.argmax())
        draft = reference.draft_logits_at(CFG, SEED, p + [first],
                                          [len(p) - 1])[0]
        assert int(caches.draft._value[i]) == int(draft.argmax())


def test_logits_are_float32_whatever_the_weights(builder):
    low = builder.build(dict(CFG, dtype="bfloat16"), SEED)[0]
    ids = np.array(_prompts([20])[0], np.int32)
    with paddle.no_grad():
        got = low(paddle.to_tensor(ids[None]))._value
    assert got.dtype == jnp.float32
    rounded = got.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.mean(got != rounded)) > 0.9


# --------------------------------------------- through the serve loop --

def test_served_with_the_drafter_on_agrees_with_the_full_forward(
        model, reference, check):
    """Contexts across page boundaries (a prompt of 8 and one of 16 end
    on a page's last row), more requests than slots: every served token
    and every drafted one against the reference's trunk and MTP module,
    teacher-forced; the 8-bit control fails each half."""
    both, served_tokens = check
    prompts = _prompts([5, 17, 8, 30, 16, 7, 23, 3, 40])
    pred = ContinuousBatchingPredictor(model, **ON)
    served, drafted, _ = _streamed(pred, prompts)
    assert all(len(o) == 10 for o in served)
    rec = both.compare(reference, served_tokens, CFG, SEED,
                       list(zip(prompts, served, drafted)), TIGHT, 9,
                       control=("int8",))
    assert rec["correct"], rec
    assert rec["positions_compared"] == 90
    # one draft a tick: every served token but a request's first had one
    # proposed for its place, or rode in behind an accepted one
    assert rec["drafts_compared"] == 81 - pred.stats["spec_accepted"]
    assert rec["argmax_share"] == rec["draft_argmax_share"] == 1.0
    assert rec["control_fails"] == {"int8": True}, rec["control"]
    ctl = rec["control"]["int8"]
    assert ctl["gap_mean"]["fails"] and ctl["draft_gap_mean"]["fails"]
    assert pred.stats["prefills"] == 9 and pred._prefill_rows == 1
    assert pred.stats["spec_proposed"] >= 72


def test_verify_logits_are_the_references(model, reference, monkeypatch):
    """Not the tokens alone: the float32 logits the verify span gives
    at its first position, every tick, against the full forward."""
    got = []
    real = ContinuousBatchingPredictor._raw_mtp_step

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

    monkeypatch.setattr(ContinuousBatchingPredictor, "_raw_mtp_step", spy)
    prompt = _prompts([21], stream=12)[0]
    pred = ContinuousBatchingPredictor(model, **dict(ON, max_batch_size=1))
    out = pred.generate([prompt], max_new_tokens=8)[0]
    jax.effects_barrier()
    assert pred.stats["spec_accepted"] == 0      # a tick a token
    ids = prompt + out[:-1]
    want = reference.logits_at(CFG, SEED, ids, np.arange(21, len(ids)))
    assert len(got) == 7
    for i in range(7):
        assert np.abs(got[i] - want[i]).max() < 2e-5 * np.abs(want).max()


def test_idle_slots_ride_every_tick_and_are_counted(model, reference, check,
                                                    monkeypatch):
    """One request in a predictor of four slots, the drafter on: what
    is served and drafted is the reference's, `serving.idle_slot_steps`
    counts the three empty slots of every tick, and the span kernel is
    handed, for them, what its contract gives an idle slot: 0 if it
    reads `live`, else their `ctx` of 1 and the span's two rows
    (`IGNORES_LIVE` in tests/test_idle_slot_lengths.py says which)."""
    from tests.test_idle_slot_lengths import IGNORES_LIVE
    idle_len = 3 if "paged_cache_latent_span_update_attend" \
        in IGNORES_LIVE else 0
    both, served_tokens = check
    seen = []
    real = la.paged_latent_attention

    def spy(q, pages, tables, lens, *rest, **kw):
        jax.debug.callback(lambda n: seen.append(np.asarray(n)), lens,
                           ordered=True)
        return real(q, pages, tables, lens, *rest, **kw)

    monkeypatch.setattr(la, "paged_latent_attention", spy)
    prompt = _prompts([21], stream=16)[0]
    pred = ContinuousBatchingPredictor(model, **ON)
    idle0 = metrics.counter("serving.idle_slot_steps").value()
    served, drafted, _ = _streamed(pred, [prompt], max_new=8)
    jax.effects_barrier()
    assert metrics.counter("serving.idle_slot_steps").value() - idle0 \
        == 3 * pred.stats["spec_ticks"]
    rec = both.compare(reference, served_tokens, CFG, SEED,
                       [(prompt, served[0], drafted[0])], TIGHT, 1)
    assert rec["correct"], rec
    calls = CFG["num_hidden_layers"] + 1      # a tick's span kernels
    assert len(seen) == pred.stats["spec_ticks"] * calls >= 7 * calls
    for lens in seen:
        assert sorted(lens.tolist())[:3] == [idle_len] * 3
    # a tick a token (nothing accepted), then one junk tick in flight
    assert pred.stats["spec_accepted"] == 0
    assert [int(lens.max()) for lens in seen[::calls]][:7] == \
        list(range(23, 30))


@pytest.fixture(scope="module")
def small_runs(small):
    """The small-vocabulary model over eight prompts, drafter off and
    on: (prompts, off tokens, on tokens, drafted, on predictor)."""
    prompts = _prompts([5, 17, 8, 30, 16, 7, 23, 12], stream=4,
                       vocab=SMALL["vocab_size"])
    off = ContinuousBatchingPredictor(small, **GEO).generate(
        prompts, max_new_tokens=24)
    pred = ContinuousBatchingPredictor(small, **ON)
    on, drafted, events = _streamed(pred, prompts, max_new=24)
    return prompts, off, on, drafted, events, pred


def test_the_drafter_changes_no_token(small_runs, model):
    """Lossless: drafter on == drafter off, bitwise, where drafts are
    accepted (the small vocabulary) and where none is."""
    prompts, off, on, _, _, pred = small_runs
    assert on == off
    assert 0 < pred.stats["spec_accepted"] < pred.stats["spec_proposed"]
    wide = _prompts([11, 26, 4], stream=5)
    assert ContinuousBatchingPredictor(model, **ON).generate(
        wide, max_new_tokens=12) == ContinuousBatchingPredictor(
        model, **GEO).generate(wide, max_new_tokens=12)


def test_proposed_drafts_are_the_references(small_runs, reference):
    """At every tick, accepted or not: the token the program proposed is
    the argmax of the reference's MTP module at that place."""
    prompts, _, on, drafted, events, _ = small_runs
    for prompt, served, drafts, evs in zip(prompts, on, drafted, events):
        ids = prompt + served[:-1]
        at = np.array([i for i, _ in drafts])
        want = reference.draft_logits_at(SMALL, SEED, ids,
                                         len(prompt) + at - 2).argmax(-1)
        assert [d for _, d in drafts] == want.tolist()
        # an accepted draft IS the token served at its place, and the
        # tick's span goes on to the token after it
        for ev in evs[1:]:
            assert len(ev.drafted) == 1
            assert (len(ev.span) == 2) == (ev.drafted[0] == ev.span[0]) \
                or ev.index == 24
        assert evs[0].drafted == () and evs[0].span == (served[0],)


def test_accepted_and_rejected_ticks_leave_a_fresh_prefills_pool(small):
    """One slot, watched before its first tick and after every resolve
    (the next tick is in flight by then, and writes at the committed
    length and past it): the slot's length, its live rows in every trunk
    layer and in the MTP layer, its last token and its standing draft
    are those of a fresh prefill of the tokens committed so far, after
    an accepted tick and after a rejected one (whose row at the rejected
    position nobody reads)."""
    prompt = _prompts([13], stream=8, vocab=SMALL["vocab_size"])[0]
    pred = ContinuousBatchingPredictor(small, **dict(ON, max_batch_size=1))
    seen, host = [], {}
    dispatch, resolve = pred._dispatch_mtp_step, pred._resolve_spec_step

    def look():
        if host["slot_req"][0] >= 0:
            seen.append((host["tables"][0].copy(), int(host["ctx"][0]),
                         int(host["last"][0]), int(host["draft"][0]),
                         [np.asarray(a) for a in pred.pool.k],
                         pred.stats["spec_accepted"]))

    def watch_dispatch(active, slot_req, tables, ctx, last, draft, inflight):
        if not host:
            host.update(slot_req=slot_req, tables=tables, ctx=ctx,
                        last=last, draft=draft)
            look()
        return dispatch(active, slot_req, tables, ctx, last, draft, inflight)

    def watch_resolve(*args, **kw):
        resolve(*args, **kw)
        look()

    pred._dispatch_mtp_step = watch_dispatch
    pred._resolve_spec_step = watch_resolve
    out = pred.generate([prompt], max_new_tokens=40)[0]
    assert pred.stats["spec_ticks_chained"] >= len(seen) - 2
    accepts = np.diff([s[-1] for s in seen])
    assert accepts.max() == 1 and accepts.min() == 0
    page = GEO["page_size"]
    checked = set()
    for k in range(1, len(seen)):
        if accepts[k - 1] in checked:
            continue
        checked.add(accepts[k - 1])
        table, ctx, last, draft, pools, _ = seen[k]
        n_new = ctx - len(prompt) + 1
        assert n_new == seen[k - 1][1] - len(prompt) + 2 + accepts[k - 1]
        assert last == out[n_new - 1]
        ids = prompt + out[:n_new - 1]
        fresh = ContinuousBatchingPredictor(
            small, **dict(ON, max_batch_size=1))
        plans = []
        keep = fresh._batch_prefill
        fresh._batch_prefill = lambda bucket, group: (
            plans.extend(group), keep(bucket, group))[1]
        assert fresh.generate([ids], max_new_tokens=1) == [[last]]
        assert plans[0]["draft"] == draft
        where = np.arange(ctx)
        for mine, theirs in zip(pools, fresh.pool.k):
            got = mine[table[where // page], where % page]
            want = np.asarray(theirs)[
                np.asarray(plans[0]["pages"])[where // page], where % page]
            assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    assert checked == {0, 1}


def test_a_budget_met_inside_a_two_token_commit(small_runs, small):
    """Whatever `max_new_tokens`, the tokens are a prefix of the longer
    run's: a tick that could commit two commits one where one is left,
    and an accepted draft in a request's last tick is not handed out
    twice."""
    prompts, off, _, _, _, _ = small_runs
    for max_new in (2, 3, 5, 9):
        pred = ContinuousBatchingPredictor(small, **ON)
        got = pred.generate(prompts, max_new_tokens=max_new)
        assert got == [o[:max_new] for o in off]
        assert pred.pool.free_count == pred.capacity


def test_eos_inside_a_two_token_commit(small_runs, small):
    """eos as the FIRST token of a two-token commit (an accepted draft)
    and as its second: stripped, with everything after it, as plain
    decode does."""
    prompts, off, _, _, events, _ = small_runs
    pairs = [(ev.span[0], ev.span[1]) for evs in events for ev in evs
             if len(ev.span) == 2]
    assert pairs
    for eos in {pairs[0][0], pairs[0][1]}:
        plain = ContinuousBatchingPredictor(
            small, eos_token_id=eos, **GEO).generate(
            prompts, max_new_tokens=24)
        pred = ContinuousBatchingPredictor(small, eos_token_id=eos, **ON)
        assert pred.generate(prompts, max_new_tokens=24) == plain
        assert any(len(p) < 24 for p in plain)
        assert all(eos not in p for p in plain)
        assert pred.pool.free_count == pred.capacity


def test_a_cancel_between_verify_and_resolve_returns_every_page(small):
    """Cancelled with a tick in flight: the slot's pages go back to the
    pool, the other request runs on, and what it was served is what it
    is served alone."""
    prompts = _prompts([19, 9], stream=6, vocab=SMALL["vocab_size"])
    pred = ContinuousBatchingPredictor(small, **ON)
    stream = pred.generate_stream(prompts, max_new_tokens=30)
    kept = []
    for ev in stream:
        if ev.kind == "token" and ev.request == 0 and ev.index >= 3:
            stream.cancel(0)         # its next tick is dispatched already
        if ev.kind == "token" and ev.request == 1:
            kept += list(ev.span)
    assert pred.last_status == ["cancelled", "ok"]
    assert pred.pool.free_count == pred.capacity
    alone = ContinuousBatchingPredictor(small, **ON).generate(
        [prompts[1]], max_new_tokens=30)[0]
    assert kept == alone and len(kept) == 30


def test_a_reused_slot_and_page_owe_nothing_to_their_last_tenant(small):
    """One slot: the second request gets the first one's pages back with
    whatever lies on them, a rejected position's stale rows among them."""
    long, short = _prompts([40, 6], stream=3, vocab=SMALL["vocab_size"])
    pred = ContinuousBatchingPredictor(small, **dict(ON, max_batch_size=1))
    first = pred.generate([long], max_new_tokens=12)[0]
    reused = pred.generate([short], max_new_tokens=12)[0]
    fresh = ContinuousBatchingPredictor(
        small, **dict(ON, max_batch_size=1)).generate(
        [short], max_new_tokens=12)[0]
    assert reused == fresh
    junk = ContinuousBatchingPredictor(small, **dict(ON, max_batch_size=1))
    junk.pool.k = [jnp.full_like(a, 37.0) for a in junk.pool.k]
    assert junk.generate([long], max_new_tokens=12)[0] == first


# ------------------------------------- the tick in the one-step pipeline --
# A tick is dispatched while the one before it is in flight: a slot that
# goes on takes its span and position from that tick's outputs on the
# device, any other slot from the host.

@pytest.mark.parametrize("which", ["rejected", "accepted"])
def test_chained_ticks_serve_and_draft_what_the_reference_does(
        model, small, reference, check, which):
    """Requests with budgets of their own that join (three waves) and
    leave between two ticks, where no draft is accepted (384 tokens) and
    where some are (24): every served token and every draft a tick
    verified, as its event names it, against the reference's trunk and
    MTP module; the tokens the plain decode's."""
    both, served_tokens = check
    cfg, net = (CFG, model) if which == "rejected" else (SMALL, small)
    lengths = [5, 17, 8, 30, 16, 7, 23, 12, 3, 21]
    budgets = [9, 3, 24, 6, 1, 16, 2, 12, 18, 5]
    reqs = list(zip(_prompts(lengths, stream=23, vocab=cfg["vocab_size"]),
                    budgets))
    pred = ContinuousBatchingPredictor(net, **ON)
    served, drafted, events = _served_in_waves(
        pred, {0: reqs[:3], 4: reqs[3:7], 9: reqs[7:]})
    assert [len(o) for o in served] == budgets
    plain = ContinuousBatchingPredictor(net, **GEO)
    assert served == [plain.generate([p], max_new_tokens=n)[0]
                      for p, n in reqs]
    rec = both.compare(reference, served_tokens, cfg, SEED,
                       [(p, o, d) for (p, _), o, d in zip(
                           reqs, served, drafted)], TIGHT, len(reqs))
    assert rec["correct"], rec
    assert rec["positions_compared"] == sum(budgets)
    assert rec["argmax_share"] == rec["draft_argmax_share"] == 1.0
    # a draft a tick that committed anything, and nothing after a budget
    ticks = sum(len(evs) - 1 for evs in events)
    assert rec["drafts_compared"] == ticks
    accepted = sum(len(ev.span) == 2 for evs in events for ev in evs)
    assert (accepted > 0) == (which == "accepted")
    assert accepted < ticks
    stats = pred.stats
    assert 0 < stats["spec_ticks_chained"] < stats["spec_ticks"]
    assert pred.pool.free_count == pred.capacity


@pytest.mark.parametrize("ending", ["budget", "eos", "cancel"])
def test_a_request_that_ends_under_its_successor_tick_leaves_a_junk_row(
        small, ending):
    """Two slots. One request ends (its budget met, its eos, a cancel)
    while the next tick, which carries its slot, is in flight; the other
    keeps the pipeline going. Nothing of that row is committed, every
    page returns, and the request that waited for the slot and for the
    pages (the pool holds no others) is served and drafted for as it is
    alone: from its own span and position."""
    goes_on, ends, waits = _prompts([19, 30, 33], stream=16,
                                    vocab=SMALL["vocab_size"])
    kw = dict(ON, max_batch_size=2, num_pages=14)
    solo = lambda prompt, n, **more: _streamed(
        ContinuousBatchingPredictor(small, **dict(kw, **more)), [prompt],
        max_new=n)
    more, cut = {}, 6
    if ending == "eos":
        whole = solo(ends, 10)[0][0]
        at = next(i for i in range(2, 10) if whole[i] not in whole[:i])
        more, cut = {"eos_token_id": whole[at]}, at
    pred = ContinuousBatchingPredictor(small, **dict(kw, **more))
    reqs = [(goes_on, 30), (ends, 6 if ending == "budget" else 10),
            (waits, 6)]
    sent = iter([[ServeRequest(p, n) for p, n in reqs]])
    stream = pred.serve_stream(lambda: next(sent, None))

    def each(ev):
        if ending == "cancel" and ev.kind == "token" and ev.request == 1 \
                and ev.index >= 3:
            stream.cancel(1)         # its next tick is dispatched already

    served, drafted, events = _collected(stream, 3, each)
    assert pred.last_status == ["ok", "cancelled" if ending == "cancel"
                                else "ok", "ok"]
    for r in (0, 2):
        alone = solo(reqs[r][0], reqs[r][1], **more)
        assert (served[r], drafted[r]) == (alone[0][0], alone[1][0])
    alone = solo(ends, reqs[1][1], **more)
    if ending == "cancel":
        assert 3 <= len(served[1]) < 10
        cut = len(served[1])
    assert served[1] == alone[0][0][:cut] and len(served[1]) == cut
    assert drafted[1] == [d for d in alone[1][0] if d[0] < cut]
    # a row of a tick that was dispatched and committed nothing
    committed = sum(len(evs) - 1 for evs in events)
    assert pred.stats["spec_proposed"] > committed
    assert pred.stats["spec_ticks_chained"] > 0
    assert pred.pool.free_count == pred.capacity == 14


def test_a_junk_row_at_the_tables_end_stays_inside_it(small):
    """A request whose prompt and budget fill its table to the last row:
    the row it leaves in the successor tick would lie two positions past
    the table. The other request is served as it is alone and every page
    returns."""
    full, other = _prompts([98, 11], stream=17, vocab=SMALL["vocab_size"])
    pred = ContinuousBatchingPredictor(small, **dict(ON, max_batch_size=2))
    sent = iter([[ServeRequest(full, 30), ServeRequest(other, 50)]])
    served, drafted, _ = _collected(
        pred.serve_stream(lambda: next(sent, None)), 2)
    plain = ContinuousBatchingPredictor(small, **dict(GEO, max_batch_size=2))
    assert served == [plain.generate([full], max_new_tokens=30)[0],
                      plain.generate([other], max_new_tokens=50)[0]]
    assert pred.pool.free_count == pred.capacity


def test_chained_ticks_are_the_ticks_less_those_on_an_empty_pipeline(small):
    """One slot, three requests one after another: the pipeline runs
    empty at each one's end, and the next one's first tick has nothing
    to chain. The counter in `stats` and the ring's `chained`."""
    from paddle_tpu.observability import tracing as tr
    prompts = _prompts([11, 6, 20], stream=18, vocab=SMALL["vocab_size"])
    pred = ContinuousBatchingPredictor(small, **dict(ON, max_batch_size=1))
    empty = []
    real = pred._dispatch_mtp_step

    def watch(*args):
        empty.append(args[-1] is None)
        return real(*args)

    pred._dispatch_mtp_step = watch
    tr.clear_ticks()
    pred.generate(prompts, max_new_tokens=12)
    ticks = [t for t in tr.ticks() if "chained" in t]
    tr.clear_ticks()
    stats = pred.stats
    assert sum(empty) >= 3 and len(empty) == stats["spec_ticks"]
    assert stats["spec_ticks_chained"] == stats["spec_ticks"] - sum(empty)
    assert stats["spec_ticks_chained"] > stats["spec_ticks"] // 2
    assert len(ticks) == stats["spec_ticks"]
    assert sum(t["chained"] for t in ticks) == stats["spec_ticks_chained"]


def test_drafted_tokens_reach_a_routers_client(small):
    from paddle_tpu.serving import Router
    prompt = _prompts([10], stream=7, vocab=SMALL["vocab_size"])[0]
    pred = ContinuousBatchingPredictor(small, **ON)
    router = Router([pred])
    try:
        handle = router.submit(prompt, max_new_tokens=12)
        events = [ev for ev in handle.stream(timeout=120)
                  if ev.kind == "token"]
    finally:
        router.shutdown(timeout=60.0)
    assert [t for ev in events for t in ev.span] == handle.tokens
    assert events[0].drafted == ()
    assert all(len(ev.drafted) == 1 for ev in events[1:])


def test_the_predictor_serves_through_the_span_kernel_in_interpret_mode(
        model, reference, check):
    from paddle_tpu.framework.flags import flag_value, set_flags
    both, served_tokens = check
    before = {k: flag_value(k) for k in ("use_pallas_kernels",
                                         "pallas_interpret")}

    def kernels():
        return {s.labels["kernel"]: s.value for s in
                metrics.counter("kernels.paged_decode").samples()}

    set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
    try:
        n = kernels().get("paged_latent_attention", 0)
        prompts = _prompts([33, 12], stream=14)
        served, drafted, _ = _streamed(
            ContinuousBatchingPredictor(model, **ON), prompts, max_new=6)
        # the tick's program: three trunk layers and the MTP layer
        assert kernels()["paged_latent_attention"] == n + 4
    finally:
        set_flags(before)
    rec = both.compare(reference, served_tokens, CFG, SEED,
                       list(zip(prompts, served, drafted)), TIGHT, 2)
    assert rec["correct"], rec


# ------------------------------- the span kernel against its XLA form --

def _paged_case(rng, span, slots=3, heads=8, page=8, pps=16,
                lens=(100, 37, 9)):
    pool = slots * pps + 1
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    pages = f(pool, page, 128).at[..., 40:].set(0.0)
    tables = jnp.asarray(1 + rng.permutation(pool - 1)[:slots * pps].reshape(
        slots, pps), jnp.int32)
    return (la.latent_rows(f(slots, span * heads, 40), pages), pages, tables,
            jnp.asarray(lens, jnp.int32))


@pytest.mark.parametrize("span", [1, 2, 3])
def test_span_kernel_is_the_xla_form(span):
    q, pages, tables, lens = _paged_case(np.random.default_rng(span), span)
    got = la._latent_attention_pallas(q, pages, tables, lens, 0.2, True,
                                      span=span)
    want = la._latent_attention_xla(q, pages, tables, lens, 0.2, span=span)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_a_span_is_its_queries_one_at_a_time():
    """Query j of a span of s sees the slot's rows less the span's later
    tokens': the single-query form at `lens - (s - 1 - j)`."""
    q, pages, tables, lens = _paged_case(np.random.default_rng(7), 3)
    whole = np.asarray(la._latent_attention_xla(q, pages, tables, lens, 0.2,
                                                span=3))
    for j in range(3):
        one = la._latent_attention_xla(q[:, 8 * j:8 * j + 8], pages, tables,
                                       lens - (2 - j), 0.2)
        assert np.abs(np.asarray(one) - whole[:, 8 * j:8 * j + 8]).max() \
            < 1e-6


def test_a_block_shrinks_with_the_spans_rows():
    # 2048 tokens a block up to 128 query rows, 1024 at a span of two
    # over 128 heads: the float32 scores stay 1 MB
    assert la.latent_pages_per_block(16, 256) == 128
    assert la.latent_pages_per_block(16, 256, 64) == 128
    assert la.latent_pages_per_block(16, 256, 128) == 128
    assert la.latent_pages_per_block(16, 256, 256) == 64
    with pytest.raises(ValueError, match="span under a selection"):
        la.paged_latent_attention(
            jnp.zeros((1, 16, 40)), jnp.zeros((3, 8, 128)),
            jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32),
            keep=jnp.ones((1, 16), bool), span=2)


def test_absorbed_span_is_the_decompressed(model):
    """One layer: the last two positions of a 37-token sequence by the
    prefill's decompressed form, and by one absorbed two-query span over
    pages that hold the 35 rows before them."""
    ids = _prompts([37], stream=13)[0]
    layer = model.model.layers[0]
    with paddle.no_grad():
        x = layer.input_layernorm(model.model.embed_tokens(
            paddle.to_tensor(np.asarray(ids, np.int32)[None])))
    t = len(ids)
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    with paddle.no_grad():
        whole, (rows,) = layer.self_attn(
            x, paddle.to_tensor(pos),
            paddle.to_tensor(jnp.ones((1, t), jnp.bool_)), None)
    page, pps = 8, 8
    table = 1 + np.arange(pps, dtype=np.int32)[None]
    old = np.arange(t - 2)
    pages = jnp.zeros((pps + 1, page, 128), jnp.float32).at[
        (old // page + 1, old % page)].set(la.latent_rows(
            rows._value[0, :t - 2], jnp.zeros((1, 1, 128), jnp.float32)))
    entry = LatentCacheEntry(paddle.to_tensor(pages),
                             paddle.to_tensor(table),
                             paddle.to_tensor(np.array([t - 2], np.int32)))
    with paddle.no_grad():
        step, new = layer.self_attn(x[:, -2:], paddle.to_tensor(pos[:, -2:]),
                                    None, entry)
    want = np.asarray(whole._value)[0, -2:]
    assert np.abs(np.asarray(step._value)[0] - want).max() \
        < 1e-5 * np.abs(want).max()
    # the span wrote both rows where the prefill would have: page 5
    # (positions 35 and 36), rows 3 and 4
    assert np.allclose(np.asarray(new.pages._value)[5, 3:5, :40],
                       np.asarray(rows._value)[0, -2:], atol=1e-6)


# ------------------------------------------------------------- the layers --

def test_expert_shares_add_up_to_the_uncut_layer(reference):
    """16 experts in 16 shares of 1 (16 ranks that share a layer): the
    routed parts of the shares, with the shared expert (which every rank
    computes alike) counted once, add up to the uncut layer, in the
    program and against the reference."""
    from paddle_tpu.incubate.distributed.models.moe.dropless import (
        dropless_moe, group_limited_sigmoid_route)
    pw = reference.pw
    key = pw.base_key(SEED)
    index = jnp.int32(1)
    f32 = lambda tree: {n: a.astype(jnp.float32) for n, a in tree.items()}
    h = jax.random.normal(jax.random.PRNGKey(3), (37, CFG["hidden_size"]),
                          jnp.float32)
    w = f32(pw.moe(CFG, key, 1))
    whole = np.asarray(reference.experts_layer(h, w, key, index, CFG, None))
    shared = np.asarray(reference._swiglu(h, w["shared_in"], w["shared_out"],
                                          None))
    scale = np.abs(whole).max()
    route = lambda logits: group_limited_sigmoid_route(
        logits, jnp.zeros((16,)), 4, 1, 1, 2.5, True)
    parts, ref_parts, local = [], [], 0
    for share in ([r] for r in range(16)):
        banks = f32(pw.experts(CFG, key, 1, share))
        y, counts = dropless_moe(h, None, w["router"], banks["w_in"],
                                 banks["w_out"], held=tuple(share), top_k=4,
                                 route=route)
        parts.append(np.asarray(y))
        local += int(counts[1])
        assert int(counts[0]) == 37 * 4
        ref_parts.append(np.asarray(reference.routed_part(
            h, w, key, index, CFG, None, held=share)))
        assert np.abs(parts[-1] - ref_parts[-1]).max() < 1e-5 * scale
    assert local == 37 * 4              # every assignment on one share
    assert np.abs(sum(parts) + shared - whole).max() < 1e-5 * scale
    assert np.abs(sum(ref_parts) + shared - whole).max() < 1e-5 * scale
    assert np.abs(parts[0]).max() > 1e-3 * scale


def test_sandwich_norms_are_four_a_layer_and_the_mtp_joins_two(model):
    layer = model.model.layers[1]
    norms = [n for n, _ in layer.named_parameters() if "layernorm" in n]
    assert norms == ["input_layernorm.weight",
                     "post_attention_layernorm.weight",
                     "pre_mlp_layernorm.weight", "post_mlp_layernorm.weight"]
    mtp = model.model.mtp
    assert tuple(mtp.eh_proj.weight.shape) == (128, 64)
    assert not mtp.layer.dense and model.model.layers[0].dense
    # the sublayer's output is normed before the residual takes it: a
    # scaled attention output changes nothing downstream
    h = paddle.to_tensor(np.random.default_rng(0).normal(
        size=(1, 5, 64)).astype(np.float32))
    pos = paddle.to_tensor(np.arange(5, dtype=np.int32)[None])
    ok = paddle.to_tensor(np.ones((1, 5), bool))
    with paddle.no_grad():
        base = np.asarray(layer(h, pos, ok, None)[0]._value)
        w = layer.self_attn.o_proj.weight
        w._value = w._value * 3.0
        try:
            scaled = np.asarray(layer(h, pos, ok, None)[0]._value)
        finally:
            w._value = w._value / 3.0
    assert np.abs(scaled - base).max() < 1e-4 * np.abs(base).max()


# ---------------------------------- what is declared, counted and refused --

def test_layout_declares_latent_rows_and_the_drafter(model):
    assert model.cache_layout() == [LayerCache("latent", (40,))] * 4
    assert model.drafter() == Drafter(depth=1, layer=3)
    assert model.long_prefill and model.long_prefill_rows == 1
    pred = ContinuousBatchingPredictor(model, **ON)
    assert pred._drafter == Drafter(1, 3) and not pred.span_ragged
    assert len(pred.pool.k) == 4 and all(v is None for v in pred.pool.v)
    assert pred.prefix_cache is None
    # the drafter is off unless asked for: plain decode, no draft pass
    off = ContinuousBatchingPredictor(model, **GEO)
    assert off._drafter is None
    off.generate(_prompts([9]), max_new_tokens=3)
    assert {s[0] for s in off._traced_sigs} == {"prefill", "decode"}


def test_the_ticks_counters_come_down_with_its_tokens(small):
    def totals():
        return {n: sum(s.value for s in metrics.counter(n).samples())
                for n in ("mtp.drafts_proposed", "mtp.drafts_accepted",
                          "mtp.tokens_committed", "mla.keys_live",
                          "moe.assignments", "moe.assignments_local")}
    before = totals()
    prompt = _prompts([11], stream=9, vocab=SMALL["vocab_size"])[0]
    pred = ContinuousBatchingPredictor(small, **dict(ON, max_batch_size=1))
    out = pred.generate([prompt], max_new_tokens=20)[0]
    got = {n: v - before[n] for n, v in totals().items()}
    ticks, acc = pred.stats["spec_ticks"], pred.stats["spec_accepted"]
    assert got["mtp.drafts_proposed"] == ticks == pred.stats["spec_proposed"]
    assert got["mtp.drafts_accepted"] >= acc >= 1
    assert got["mtp.tokens_committed"] == ticks \
        + got["mtp.drafts_accepted"] >= len(out) - 1
    # a span's two tokens through 3 trunk layers, and the MTP layer over
    # the positions kept; two expert layers and the MTP layer route them
    assert got["moe.assignments"] == 4 * (
        2 * (11 + 2 * ticks) + 11 + ticks + got["mtp.drafts_accepted"])
    assert got["moe.assignments_local"] == got["moe.assignments"]
    assert got["mla.keys_live"] > 3 * 2 * 11 * ticks


def test_the_tick_ring_counts_a_two_token_commit_as_two(small):
    """The ring's `tokens` over a run are the tokens the streams
    received, two for a tick that committed two; `first` one a request."""
    from paddle_tpu.observability import tracing as tr
    tr.clear_ticks()
    prompts = _prompts([11, 6], stream=9, vocab=SMALL["vocab_size"])
    pred = ContinuousBatchingPredictor(small, **ON)
    served, _, events = _streamed(pred, prompts, max_new=24)
    ticks = tr.ticks()
    tr.clear_ticks()
    assert any(len(ev.span) == 2 for evs in events for ev in evs)
    assert sum(t.get("tokens", 0) for t in ticks) == 48 \
        == sum(len(o) for o in served)
    assert sum(t.get("first", 0) for t in ticks) == 2


@pytest.mark.parametrize("kw,name", [
    (dict(prefill_chunk_tokens=16), "prefill_chunk_tokens"),
    (dict(tp_degree=2), "tp_degree"),
    (dict(role="prefill"), "role='prefill'"),
    (dict(spec_draft_tokens=1, sampling_enabled=True), "sampling_enabled"),
    (dict(spec_draft_tokens=2), "spec_draft_tokens=2")])
def test_what_latent_pages_cannot_serve_is_refused_by_name(model, kw, name):
    with pytest.raises(ValueError) as err:
        ContinuousBatchingPredictor(model, **dict(GEO, **kw))
    assert name in str(err.value)
    assert "latent pages" in str(err.value) or "drafter" in str(err.value)


def test_ngram_drafts_over_latent_pages_stay_refused(model, monkeypatch):
    """Without a declared drafter, speculation over latent pages is
    refused as before."""
    monkeypatch.setattr(type(model), "drafter", lambda self: None)
    with pytest.raises(ValueError, match="spec_draft_tokens: not served "
                                         "for a model with latent pages"):
        ContinuousBatchingPredictor(model, **ON)


# ----------------------------------------- the benchmark's kernel count --

def test_verify_span_bytes_against_a_hand_count():
    k = harness.load_module(ROOT, "kernels", "mla_verify")
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    ctx = [1000, 500]
    # rows ONCE for the span's two queries: 1500 x 576 x 2 B; queries 2
    # slots x 256 heads x 576 x 2 B in, 2 x 256 x 512 x 2 B back
    assert k.bytes_per_call(ctx, 2, 128, 512, 64, 2) \
        == 1_728_000 + 589_824 + 524_288
    assert k.flops_per_call(ctx, 2, 128, 512, 64) \
        == 1500 * 256 * (2 * 576 + 2 * 512)
    # 294 operations a byte: bound by the MXU, not by the rows
    assert k.least_seconds(ctx, 2, 128, 512, 64, 2, peaks) \
        == pytest.approx(835_584_000 / 197e12)
    # a span of one at 32 heads is cell 5's decode count, bound by memory
    one = harness.load_module(ROOT, "kernels", "mla_decode")
    assert k.bytes_per_call(ctx, 1, 32, 512, 64, 2) \
        == one.bytes_per_call(ctx, 32, 512, 64, 2)
    assert k.least_seconds(ctx, 1, 32, 512, 64, 2, peaks) \
        == one.least_seconds(ctx, 32, 512, 64, 2, peaks)

"""The kernels of learned sparse attention in interpret mode against XLA
forms and numpy oracles: index scores over a slot's live index pages,
the exact top-k selection (ties to the lower position), attention over
the selected keys through the block-table kernel, and the prefill forms
by query chunks. Contexts under, at and over `topk`, page boundaries,
tied scores, empty slots, a group of 8 query heads a KV head.
"""
import contextlib
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from paddle_tpu.kernels import attention
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import sparse_attention as sa
from paddle_tpu.framework.flags import set_flags

BF16, F32 = jnp.bfloat16, jnp.float32
PAGE, PPS, POOL = 16, 16, 160           # 256 positions a slot
J, DI, TOPK = 4, 64, 64
# a slot's keys: one, a page less one, a page, a page and one, topk - 1,
# topk, topk + 1, well over it and off a page boundary, the whole table
LENS = [1, 15, 16, 17, TOPK - 1, TOPK, TOPK + 1, 203, PAGE * PPS]


def _rng(*stream):
    return np.random.default_rng([20260930, *stream])


@contextlib.contextmanager
def _pallas_interpreted(on):
    """The prefill's Pallas kernels through the interpreter where `on`,
    its XLA forms otherwise."""
    if on:
        set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
    try:
        yield
    finally:
        if on:
            set_flags({"use_pallas_kernels": True, "pallas_interpret": False})


def _pool(rng, lens, hkv=1, d=128, dtype=BF16):
    b = len(lens)
    tables = rng.permutation(POOL - 1)[:b * PPS].reshape(b, PPS) + 1
    for i, n in enumerate(lens):            # page 0 is the trash page
        tables[i, -(-n // PAGE):] = 0
    arr = lambda *s: jnp.asarray(rng.normal(size=s), dtype)
    # index keys lie on whole 128-lane rows, zeros past DI
    index_pages = jnp.pad(arr(POOL, PAGE, DI), [(0, 0), (0, 0), (0, 128 - DI)])
    return (arr(POOL, PAGE, hkv, d), arr(POOL, PAGE, hkv, d), index_pages,
            jnp.asarray(tables, jnp.int32), jnp.asarray(lens, jnp.int32))


def _oracle_keep(scores, lens, k):
    keep = np.zeros(scores.shape, bool)
    for b, n in enumerate(lens):
        order = np.argsort(-scores[b, :n], kind="stable")[:k]
        keep[b, order] = True
    return keep


# ------------------------------------------------------------ selection --

@pytest.mark.parametrize("k", [1, 7, 32, 100, 101, 400])
def test_selection_is_the_stable_sort(k):
    """Scores on a grid of 0.25 (many ties, zeros of both signs) with
    invalid positions strewn in: the kept set is the first k of a stable
    descending sort over the valid ones."""
    rng = _rng(1, k)
    scores = np.round(rng.normal(size=(6, 100)) * 2) / 4
    scores[0, ::3] = -0.0
    scores[1] = 0.5                                     # one value
    valid = rng.random((6, 100)) < 0.85
    valid[2] = True
    keep = np.asarray(sa.select_topk(jnp.asarray(scores, F32),
                                     jnp.asarray(valid), k))
    for b in range(6):
        idx = np.nonzero(valid[b])[0]
        want = idx[np.argsort(-scores[b, idx], kind="stable")][:k]
        assert sorted(want) == np.nonzero(keep[b])[0].tolist(), b


def test_selection_orders_every_float():
    """Infinities, tiny values and both zeros keep their order."""
    vals = np.array([np.inf, 3e38, 1.0, 1e-30, 0.0, -0.0, -1e-30, -1.0,
                     -3e38, -np.inf], np.float32)
    for k in range(1, len(vals) + 1):
        keep = np.asarray(sa.select_topk(jnp.asarray(vals[::-1].copy()),
                                         jnp.ones(len(vals), bool), k))
        # 0.0 and -0.0 tie: the lower position (of the reversed row) first
        want = np.argsort(-vals[::-1], kind="stable")[:k]
        assert sorted(want) == np.nonzero(keep)[0].tolist(), k


# --------------------------------------------------- decode: index scores --

def test_index_scores_over_live_pages():
    rng = _rng(2)
    _, _, index_pages, tables, lens = _pool(rng, LENS)
    qi = jnp.asarray(rng.normal(size=(len(LENS), J, DI)), BF16)
    w = jnp.asarray(rng.normal(size=(len(LENS), J)), F32)
    assert pa.index_scores_gate_reason(128, PAGE, PPS) is None
    got = np.asarray(pa.paged_index_scores(qi, w, index_pages, tables, lens,
                                           interpret=True))
    want = np.asarray(pa._index_scores_xla(
        jnp.pad(qi, [(0, 0), (0, 0), (0, 128 - DI)]), w, index_pages, tables,
        lens))
    live = np.arange(PAGE * PPS)[None, :] < np.asarray(lens)[:, None]
    assert np.array_equal(np.isfinite(got), live)
    assert np.all(got[~live] == -np.inf)
    assert np.abs(got[live] - want[live]).max() < 1e-4 * np.abs(
        want[live]).max()
    # and against the definition, in float64
    ki = np.asarray(index_pages, np.float64)[np.asarray(tables)].reshape(
        len(LENS), -1, 128)[..., :DI]
    dots = np.einsum("bjd,bld->bjl", np.asarray(qi, np.float64), ki)
    plain = (np.maximum(dots, 0) * np.asarray(w, np.float64)[..., None]).sum(1)
    assert np.abs(got[live] - plain[live]).max() < 1e-4 * np.abs(
        plain[live]).max()


@pytest.mark.parametrize("di,page,pps,reason", [
    (128, 16, 12, "table_tiling"), (64, 16, 16, "index_dim_tiling"),
    (128, 4, 16, "table_tiling")])
def test_index_scores_fall_back_by_name(di, page, pps, reason):
    from paddle_tpu.observability import metrics
    assert pa.index_scores_gate_reason(di, page, pps) == reason

    def count():
        return sum(s.value for s in
                   metrics.counter("kernels.pallas_fallbacks").samples()
                   if s.labels == {"kernel": "paged_index_scores",
                                   "reason": reason})
    rng, before = _rng(3, pps), count()
    index_pages = jnp.asarray(rng.normal(size=(8, page, di)), F32)
    tables = jnp.zeros((2, pps), jnp.int32)
    got = pa.paged_index_scores(
        jnp.ones((2, J, di), F32), jnp.ones((2, J), F32), index_pages,
        tables, jnp.asarray([1, page], jnp.int32), interpret=True)
    assert got.shape == (2, pps * page) and count() == before + 1


# -------------------------------------------- decode: select and attend --

@pytest.mark.parametrize("h,hkv", [(8, 1), (16, 2)], ids=["rep8", "rep8x2"])
def test_sparse_decode_attends_to_the_selected_keys_only(h, hkv):
    rng = _rng(4, h)
    k_pages, v_pages, index_pages, tables, lens = _pool(rng, LENS, hkv)
    b = len(LENS)
    q = jnp.asarray(rng.normal(size=(b, h, 128)), BF16)
    qi = jnp.asarray(rng.normal(size=(b, J, DI)), BF16)
    w = jnp.asarray(rng.normal(size=(b, J)), F32)
    out, keep = pa.paged_sparse_attention(
        q, k_pages, v_pages, index_pages, qi, w, tables, lens, TOPK,
        interpret=True)
    keep = np.asarray(keep)
    scores = np.asarray(pa.paged_index_scores(qi, w, index_pages, tables,
                                              lens, interpret=True))
    assert np.array_equal(keep, _oracle_keep(scores, LENS, TOPK))
    assert keep.sum(1).tolist() == [min(n, TOPK) for n in LENS]
    # the softmax over the kept keys alone, in float64
    kk = np.asarray(k_pages, np.float64)[np.asarray(tables)].reshape(
        b, -1, hkv, 128)
    vv = np.asarray(v_pages, np.float64)[np.asarray(tables)].reshape(
        b, -1, hkv, 128)
    qq = np.asarray(q, np.float64).reshape(b, hkv, h // hkv, 128)
    s = np.einsum("bgrd,blgd->bgrl", qq, kk) / np.sqrt(128)
    s = np.where(keep[:, None, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bgrl,blgd->bgrd", p / p.sum(-1, keepdims=True), vv)
    got = np.asarray(out, np.float64).reshape(want.shape)
    assert np.abs(got - want).max() < 2e-2       # bf16 probabilities


def test_at_most_topk_keys_is_plain_paged_attention():
    """Contexts of at most `topk`: every key is selected and the layer
    is the block-table kernel's plain GQA, bit for bit."""
    rng = _rng(5)
    lens = [n for n in LENS if n <= TOPK]
    k_pages, v_pages, index_pages, tables, cl = _pool(rng, lens)
    q = jnp.asarray(rng.normal(size=(len(lens), 8, 128)), BF16)
    qi = jnp.asarray(rng.normal(size=(len(lens), J, DI)), BF16)
    w = jnp.asarray(rng.normal(size=(len(lens), J)), F32)
    out, keep = pa.paged_sparse_attention(
        q, k_pages, v_pages, index_pages, qi, w, tables, cl, TOPK,
        interpret=True)
    plain = pa.paged_attention(q, k_pages, v_pages, tables, cl,
                               interpret=True)
    assert np.asarray(keep).sum(1).tolist() == lens
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(plain, np.float32))


def test_tied_scores_go_to_the_lower_position():
    """Index keys that repeat give equal scores: the set is still the
    stable sort's, and an empty slot (all trash, one key) is served."""
    rng = _rng(6)
    lens = [200, 130, 1]
    k_pages, v_pages, _, tables, cl = _pool(rng, lens)
    tables = tables.at[2].set(0)                       # the idle slot
    few = jnp.pad(jnp.asarray(rng.normal(size=(3, DI)), BF16),
                  [(0, 0), (0, 128 - DI)])
    index_pages = few[jnp.asarray(rng.integers(0, 3, (POOL, PAGE)))]
    qi = jnp.asarray(rng.normal(size=(3, J, DI)), BF16)
    w = jnp.asarray(rng.normal(size=(3, J)), F32)
    q = jnp.asarray(rng.normal(size=(3, 8, 128)), BF16)
    out, keep = pa.paged_sparse_attention(
        q, k_pages, v_pages, index_pages, qi, w, tables, cl, TOPK,
        interpret=True)
    scores = np.asarray(pa.paged_index_scores(qi, w, index_pages, tables,
                                              cl, interpret=True))
    assert len(np.unique(scores[0, :200])) <= 3
    assert np.array_equal(np.asarray(keep), _oracle_keep(scores, lens, TOPK))
    assert np.isfinite(np.asarray(out, np.float32)).all()


# ----------------------------------------------------------------- prefill --

@pytest.fixture
def key_blocks_of_128(monkeypatch):
    monkeypatch.setattr(sa, "_BLOCK_K", 128)


def test_prefill_index_scores_by_chunk(key_blocks_of_128):
    """Rows of different left padding in one batch: each row's scores
    are the XLA form's from the block of its first real key to the block
    of the chunk's last query; the key and the score blocks a step names
    stay inside that span, so a step outside it copies nothing."""
    rng = _rng(7)
    s, c, bk = 512, 64, 128
    first = [0, 50, 130, 300, s]    # none, under a block, over one, into
    n = len(first)                  # the chunk itself, a dummy row
    qi = jnp.asarray(rng.normal(size=(n, c, J, DI)), BF16)
    w = jnp.asarray(rng.normal(size=(n, c, J)), F32)
    ki = jnp.asarray(rng.normal(size=(n, s, DI)), BF16)
    want = np.asarray(sa._index_scores_xla(qi, w, ki))
    # the chunk 256..319 ends in block 2: the block past it is skipped
    valid = jnp.asarray(np.arange(s)[None, :] >= np.asarray(first)[:, None])
    blocks = sa.chunk_key_blocks(valid, c, 1)[4]
    assert blocks.shape == (n, 2, 2, s // bk)   # one tile, then the chunk's
    assert np.asarray(blocks[:, -1, sa._RUNS]).tolist() == [
        [1, 1, 1, 0], [1, 1, 1, 0], [0, 1, 1, 0], [0, 0, 1, 0], [0, 0, 0, 0]]
    assert np.asarray(blocks[:, -1, sa._NAMES]).tolist() == [
        [0, 1, 2, 2], [0, 1, 2, 2], [1, 1, 2, 2], [2, 2, 2, 2], [3, 3, 3, 3]]
    got = np.asarray(sa.prefill_index_scores(qi, w, ki, blocks,
                                             interpret=True))
    for b, runs in enumerate(np.asarray(blocks[:, -1, sa._RUNS])):
        at = np.repeat(runs.astype(bool), bk)
        if at.any():
            assert np.abs(got[b][:, at] - want[b][:, at]).max() \
                < 1e-4 * np.abs(want).max()


def _dense_oracle(q, k, v, qi, w, ki, valid, topk):
    """Row by row in float64: score, stable sort, softmax over the kept."""
    n, s, h, d = q.shape
    hkv = k.shape[2]
    f64 = lambda a: np.asarray(a, np.float64)
    q, k, v, qi, w, ki = map(f64, (q, k, v, qi, w, ki))
    out = np.zeros((n, s, h, d))
    kept = np.zeros((n, s, s), bool)
    for b in range(n):
        scores = (np.maximum(np.einsum("tjd,sd->tjs", qi[b], ki[b]), 0)
                  * w[b][..., None]).sum(1)
        for t in range(s):
            if not valid[b, t]:
                continue
            seen = np.nonzero(valid[b, :t + 1])[0]
            sel = seen[np.argsort(-scores[t, seen], kind="stable")][:topk]
            kept[b, t, sel] = True
            for i in range(h):
                g = i // (h // hkv)
                sc = k[b, sel, g] @ q[b, t, i] / np.sqrt(d)
                p = np.exp(sc - sc.max())
                out[b, t, i] = (p / p.sum()) @ v[b, sel, g]
    return out, kept


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "pallas"])
def test_sparse_prefill_of_a_left_padded_batch(interpret, monkeypatch):
    """Unequal lengths, left-padded, contexts over `topk`, a prompt that
    is not whole chunks: every real position equals the row-by-row
    definition; the padding changes nothing."""
    rng = _rng(8)
    n, s, h, hkv, d, topk, chunk = 2, 160, 8, 2, 64, 24, 32
    if interpret:
        s, d = 256, 128            # whole key blocks, whole lane rows
    lens = [s, s - 59]
    valid = np.arange(s)[None, :] >= (s - np.asarray(lens))[:, None]
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), F32)
    q, k, v = arr(n, s, h, d), arr(n, s, hkv, d), arr(n, s, hkv, d)
    qi, w, ki = arr(n, s, J, DI), arr(n, s, J), arr(n, s, DI)
    with _pallas_interpreted(interpret):
        got = np.asarray(sa.sparse_prefill_attention(
            q, k, v, qi, w, ki, jnp.asarray(valid), topk=topk,
            scale=d ** -0.5, chunk=chunk))
    want, kept = _dense_oracle(q, k, v, qi, w, ki, valid, topk)
    assert kept[0].sum(-1).tolist() == [min(t + 1, topk) for t in range(s)]
    assert np.abs(got[valid] - want[valid]).max() < 2e-4
    # short rows are plain causal attention
    plain = np.asarray(attention._xla_attention(q, k, v, d ** -0.5, True))
    assert np.abs(got[0, :topk] - plain[0, :topk]).max() < 2e-4


# what a chunk may skip, at chunk 32 and topk 24: (lens, None the whole
# bucket s and a negative one counted back from it; distinct index keys, 0
# every key its own; positions cut off the bucket's end; chunks of padding,
# without a selection, with one: None is the rest)
_VISIBLE = {
    # a dummy row (lens == 0) beside a whole one
    "dummy_row": ((None, 0), 0, 0, (0, 0, None)),
    # nothing over topk: the one real chunk keeps all it sees
    "under_topk": ((20, 24), 0, 0, (None, 1, 0)),
    # 40 keys: 8 in one chunk, then 9..40 visible in the next, which
    # crosses topk at its 16th query
    "crosses_mid_chunk": ((40,), 0, 0, (None, 1, 1)),
    # the short row never needs a selection, the long one does
    "one_row_needs_it": ((None, 20), 0, 0, (0, 0, None)),
    # three distinct index keys: a third of what a query sees ties at
    # the threshold, from its first key to its last
    "ties_at_both_ends": ((None, -59), 3, 0, (0, 0, None)),
    # the bucket is not whole chunks
    "ragged_tail": ((None, -59), 0, 10, (0, 0, None)),
}


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(_VISIBLE))
def test_sparse_prefill_does_what_a_chunk_sees(case, interpret):
    """Chunks of padding that run nothing and chunks under `topk` that
    keep what they see leave every real position at the row-by-row
    definition, and the keep-mask of every chunk that runs is
    `select_topk`'s over the whole bucket, bit for bit: a chunk the plan
    lets off the selection would have got back what it sees."""
    offsets, distinct, cut_off, chunks = _VISIBLE[case]
    rng = _rng(9, sorted(_VISIBLE).index(case))
    h, hkv, topk, chunk = 8, 2, 24, 32
    # interpreted kernels want whole key blocks and whole lane rows
    s, d = ((256, 128) if interpret else (160, 64))
    s -= cut_off
    lens = [s if o is None else o % s for o in offsets]
    n = len(lens)
    valid = np.arange(s)[None, :] >= (s - np.asarray(lens))[:, None]
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), F32)
    q, k, v = arr(n, s, h, d), arr(n, s, hkv, d), arr(n, s, hkv, d)
    qi, w, ki = arr(n, s, J, DI), arr(n, s, J), arr(n, s, DI)
    if distinct:
        ki = arr(distinct, DI)[jnp.asarray(rng.integers(0, distinct, (n, s)))]
    with _pallas_interpreted(interpret):
        got = sa.sparse_prefill_attention(
            q, k, v, qi, w, ki, jnp.asarray(valid), topk=topk,
            scale=d ** -0.5, chunk=chunk)
        plan = sa.chunk_plan(jnp.asarray(valid), chunk, topk)
        blocks = sa.chunk_key_blocks(jnp.asarray(valid), chunk, h // hkv)
        # scores and the whole-bucket selection, a chunk at a time
        pad = lambda a: jnp.pad(a, [(0, 0), (0, -s % chunk)]
                                + [(0, 0)] * (a.ndim - 2))
        kv, qi_, w_, ki_ = map(pad, (jnp.asarray(valid), qi, w, ki))
        kinds = np.asarray(plan).tolist()
        assert len(kinds) == kv.shape[1] // chunk
        for i, kind in enumerate(kinds):
            start = i * chunk
            real = np.asarray(kv)[:, start:start + chunk].any()
            assert real == (kind != sa.PADDING)
            if not real:
                continue
            scores = sa.prefill_index_scores(
                qi_[:, start:start + chunk], w_[:, start:start + chunk],
                ki_, blocks[i])
            seen = kv[:, None, :] & (np.arange(kv.shape[1])[None, None, :]
                                     <= np.arange(start, start + chunk)[
                                         None, :, None])
            keep = np.asarray(sa.select_topk(scores, seen, topk))
            assert np.array_equal(keep, np.asarray(seen)) \
                == (kind == sa.DENSE), (i, kind)
            if distinct:
                assert len(np.unique(np.asarray(scores)[
                    0, -1, :start + chunk])) <= distinct
    count = [kinds.count(kind)
             for kind in (sa.PADDING, sa.DENSE, sa.SELECTED)]
    assert count == [len(kinds) - sum(filter(None, chunks)) if c is None
                     else c for c in chunks], kinds
    assert np.asarray(sa.plan_counts(plan, blocks, chunk, s)
                      ).tolist()[:5] == count + [
        count[2] * n * s, len(kinds) * n * s]
    want, kept = _dense_oracle(q, k, v, qi, w, ki, valid, topk)
    assert kept.sum(-1).max() == min(topk, max(lens))
    assert np.abs(np.asarray(got)[valid] - want[valid]).max() < 2e-4


@pytest.mark.parametrize("bucket,prompts,c,topk,want", [
    # the cell's buckets, chunks of 512, top-2048: a prompt of three
    # quarters of the bucket (0 padding, 1 no selection, 2 a selection)
    (4096, [3072], 512, 2048, [0] * 2 + [1] * 4 + [2] * 2),
    (8192, [6144], 512, 2048, [0] * 4 + [1] * 4 + [2] * 8),
    (16384, [12288], 512, 2048, [0] * 8 + [1] * 4 + [2] * 20),
    # the longest row decides, a dummy row nothing
    (8192, [3000, 8192, 0], 512, 2048, [1] * 4 + [2] * 12),
    # a chunk selects once its LAST query sees more than topk
    (160, [160], 32, 70, [1, 1, 2, 2, 2]),
    # a bucket with nothing to leave out, a bucket of dummy rows
    (1024, [1024], 512, 2048, [1, 1]),
    (1024, [0], 512, 2048, [0, 0])])
def test_plan_of_a_bucket(bucket, prompts, c, topk, want):
    valid = np.arange(bucket)[None, :] >= (bucket - np.asarray(prompts))[:, None]
    assert (sa.PADDING, sa.DENSE, sa.SELECTED) == (0, 1, 2)
    assert np.asarray(sa.chunk_plan(jnp.asarray(valid), c, topk)).tolist() \
        == want


def test_a_plan_handed_in_is_the_one_computed():
    """A model computes the plan once for its layers: the call that is
    handed it answers as the call that computes it, to the bit."""
    rng = _rng(10)
    n, s, h, hkv, d, topk, chunk = 2, 160, 8, 2, 64, 24, 32
    valid = jnp.asarray(np.arange(s)[None, :] >= np.asarray([[110], [150]]))
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), F32)
    args = (arr(n, s, h, d), arr(n, s, hkv, d), arr(n, s, hkv, d),
            arr(n, s, J, DI), arr(n, s, J), arr(n, s, DI), valid)
    call = functools.partial(sa.sparse_prefill_attention, *args, topk=topk,
                             scale=d ** -0.5, chunk=chunk)
    plan = sa.chunk_plan(valid, chunk, topk)
    assert np.asarray(plan).tolist() == [0, 0, 0, 1, 2]
    plan = (plan, sa.chunk_key_blocks(valid, chunk, h // hkv))
    assert np.array_equal(np.asarray(call(plan=plan)), np.asarray(call()))


# ------------------------------------------- a tile's live key blocks --------

# (query heads, KV heads, queries of the chunk): 8 heads a group in two
# tiles of 128 queries; a head of its own keys, the chunk in one tile
_TILE_FORMS = {"grouped": (16, 2, 256), "one_tile": (2, 2, 256)}
_S, _BK, _START = 1024, 128, 512    # 8 key blocks; the chunk's first query
# left padding of the rows of ONE batch: none, under a block, several
# blocks and a bit, up into the chunk's first tile, the whole chunk
_PADS = [0, 50, 421, 600, 824]


def _chunk_case(form, seed):
    """q, k, v, keep of the chunk 512..767 under a selection (a third of
    what a query sees, its own key among them), its table of key
    blocks, and which of its queries are real."""
    h, hkv, c = _TILE_FORMS[form]
    rng = _rng(11, seed)
    n, d = len(_PADS), 128
    valid = np.arange(_S)[None, :] >= np.asarray(_PADS)[:, None]
    qpos = _START + np.arange(c)
    own = np.arange(_S)[None, None, :] == qpos[None, :, None]
    keep = valid[:, None, :] & (np.arange(_S)[None, None, :]
                                <= qpos[None, :, None]) \
        & ((rng.random((n, c, _S)) < 0.3) | own)
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), F32)
    blocks = sa.chunk_key_blocks(jnp.asarray(valid), c, h // hkv)[_START // c]
    return (arr(n, c, h, d), arr(n, hkv, _S, d), arr(n, hkv, _S, d),
            jnp.asarray(keep), blocks, valid[:, qpos])


@pytest.mark.parametrize("form", sorted(_TILE_FORMS))
def test_selected_attention_over_rows_of_different_padding(
        form, key_blocks_of_128):
    """Every real query of every row equals the XLA attention under
    `keep`, and, bit for bit, the kernel made to visit every key block:
    a block of a row's padding adds nothing. A row whose chunk is all
    padding runs no block and is left at zeros."""
    q, k, v, keep, blocks, real = _chunk_case(form, 0)
    n, c, h, d = q.shape
    bq, bk = sa.attend_tiles(c, h // k.shape[1], _S)
    assert (bq, bk) == ((128, _BK) if form == "grouped" else (c, _BK))
    got = np.asarray(sa.selected_attention(q, k, v, keep, blocks, d ** -0.5,
                                           interpret=True))
    want = np.asarray(sa.selected_attention(q, k, v, keep, blocks, d ** -0.5))
    assert np.abs(got[real] - want[real]).max() < 1e-5
    j = jnp.arange(_S // bk, dtype=jnp.int32)
    whole = jnp.broadcast_to(jnp.stack([j, jnp.ones_like(j)]), blocks.shape)
    everywhere = np.asarray(sa._attend_pallas(
        q, k, v, keep, whole, d ** -0.5, bq, bk, True))
    assert np.array_equal(got[real], everywhere[real])
    assert not real[-1].any() and not got[-1].any()


@pytest.mark.parametrize("form", sorted(_TILE_FORMS))
def test_key_blocks_of_a_chunk_by_hand(form, key_blocks_of_128):
    """What a chunk at 512..767 sees of keys in blocks of 128: from the
    block of the row's first real key to the block of the tile's last
    query; nothing where the tile's queries are all padding. The last
    row of a table is the whole chunk's."""
    _, _, _, _, blocks, _ = _chunk_case(form, 1)
    runs = np.asarray(blocks[:, :, sa._RUNS])
    span = [[[int(np.flatnonzero(t)[0]), int(np.flatnonzero(t)[-1])]
             if t.any() else None for t in row] for row in runs]
    if form == "grouped":       # tiles end at 639 and 767: blocks 4 and 5
        assert span == [[[0, 4], [0, 5], [0, 5]], [[0, 4], [0, 5], [0, 5]],
                        [[3, 4], [3, 5], [3, 5]], [[4, 4], [4, 5], [4, 5]],
                        [None, None, None]]
    else:
        assert span == [[[0, 5]] * 2, [[0, 5]] * 2, [[3, 5]] * 2,
                        [[4, 5]] * 2, [None] * 2]
    # a range has no hole: what runs is what lies between its ends
    assert all(t[lo:hi + 1].all() and t.sum() == hi + 1 - lo
               for row, at in zip(runs, span)
               for t, (lo, hi) in zip(row, (a for a in at if a)))


@pytest.mark.parametrize("form", sorted(_TILE_FORMS))
def test_named_key_blocks_are_live_and_resident(form, key_blocks_of_128):
    """The block each grid step names, walked over the grid: a step that
    runs names its own block; one that does not names the nearer end of
    the tile's range (the block resident before or after it: nothing is
    copied) and never an index outside the array, the tile of padding
    included, which names one block throughout."""
    _, _, _, _, blocks, _ = _chunk_case(form, 2)
    tab = np.asarray(blocks)
    n, rows, _, nb = tab.shape
    copies = 0
    for b in range(n):
        for i in range(rows):
            named, runs = tab[b, i, sa._NAMES], tab[b, i, sa._RUNS]
            assert ((0 <= named) & (named < nb)).all()
            if not runs.any():
                assert len(set(named.tolist())) == 1
                continue
            lo, hi = np.flatnonzero(runs)[[0, -1]]
            assert named.tolist() == [min(max(j, lo), hi) for j in range(nb)]
            copies += len(set(named.tolist()))
    assert copies == tab[:, :, sa._RUNS].sum() < n * rows * nb


@pytest.mark.parametrize("bucket,prompts,c,rep,want", [
    # the issue's example: 10240 tokens in 16384, one tile a chunk; 20
    # chunks run, the i-th over blocks 0..i of which 12 are padding
    (16384, [10240], 512, 1, (210, 450)),
    # four tiles of 128 queries a chunk, two rows: the short row's
    # chunks of padding run no block while the long row's chunks run
    (16384, [12288, 3072], 512, 8,
     (4 * (sum(range(9, 33)) - 8 * 24) + 4 * (sum(range(27, 33)) - 26 * 6),
      2 * 4 * sum(range(9, 33)))),
    # a full bucket: nothing to leave out
    (4096, [4096], 512, 8, (4 * 36, 4 * 36)),
    # the first real key in the middle of a block, of a tile, of a chunk
    (2048, [700], 512, 8, (1 + 1 + 4 * 2, 4 * 3 + 4 * 4)),
    # dummy rows only: no chunk runs
    (1024, [0, 0], 512, 1, (0, 0))])
def test_key_block_counts_of_a_bucket(bucket, prompts, c, rep, want):
    valid = jnp.asarray(
        np.arange(bucket)[None, :] >= (bucket - np.asarray(prompts))[:, None])
    plan = sa.chunk_plan(valid, c, 2048)
    blocks = sa.chunk_key_blocks(valid, c, rep)
    assert tuple(np.asarray(sa.plan_counts(plan, blocks, c, bucket)
                            ).tolist()[5:]) == want

"""A slot that carries no request attends over nothing: three of the five
decode contracts of the paged caches (generation/kv_cache.py) give the
slot-walk kernels a length of 0 for it where the entry says which slots
are `live`, and their own `context_lens + n` where it does not; K/V pages
without an indexer and the span over latent pages do not read `live` yet
and are held to that. Interpret mode, a
batch of live and idle slots (idle: a table that is all trash and a
position of 1, as the serve loop keeps it): a live slot's output and the
written pages do not change by a bit, an idle slot's output is zeros, its
row of index scores -inf, its selection empty, and its token still lands
on the trash page.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from paddle_tpu.framework.flags import flag_value, set_flags
from paddle_tpu.generation import kv_cache as kc
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.observability import metrics

F32 = jnp.float32
PAGE, PPS, POOL, TRASH = 16, 16, 96, 0
H, D, LANES = 8, 128, 128
J, DI, TOPK = 4, 64, 16
# (position, carries a request): contexts under and over `TOPK`, one that
# ends a page, and two idle slots between them
SLOTS = [(37, True), (1, False), (2, True), (1, False), (PAGE - 1, True)]
CL = np.array([n for n, _ in SLOTS], np.int32)
LIVE = np.array([on for _, on in SLOTS])
B = len(SLOTS)


@pytest.fixture(autouse=True)
def _interpreted():
    before = {k: flag_value(k) for k in ("use_pallas_kernels",
                                         "pallas_interpret")}
    set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
    yield
    set_flags(before)


def _arr(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), F32)


def _tables(rng):
    tables = rng.permutation(np.arange(1, POOL))[:B * PPS].reshape(B, PPS)
    tables[~LIVE] = TRASH
    return jnp.asarray(tables, jnp.int32)


def _index_rows(rng, *shape):
    """Index keys on whole 128-lane rows, zeros past DI."""
    return jnp.pad(_arr(rng, *shape, DI),
                   [(0, 0)] * len(shape) + [(0, LANES - DI)])


def _kv(rng, hkv=1):
    entry = kc.PagedCacheEntry(_arr(rng, POOL, PAGE, hkv, D),
                               _arr(rng, POOL, PAGE, hkv, D), _tables(rng),
                               jnp.asarray(CL))
    return entry, (_arr(rng, B, 1, H, D), _arr(rng, B, 1, hkv, D),
                   _arr(rng, B, 1, hkv, D))


def _kv_sparse(rng):
    entry, qkv = _kv(rng)
    entry = entry._replace(index_pages=_index_rows(rng, POOL, PAGE))
    return entry, qkv + (_arr(rng, B, 1, J, DI), _arr(rng, B, 1, J),
                         _arr(rng, B, 1, DI), TOPK)


def _latent(rng, span=1):
    entry = kc.LatentCacheEntry(_arr(rng, POOL, PAGE, LANES), _tables(rng),
                                jnp.asarray(CL))
    return entry, (_arr(rng, B, span, H, LANES), _arr(rng, B, span, LANES))


def _latent_sparse(rng):
    entry, qrow = _latent(rng)
    entry = entry._replace(index_pages=_index_rows(rng, POOL, PAGE))
    return entry, qrow + (_arr(rng, B, 1, J, DI), _arr(rng, B, 1, J),
                          _arr(rng, B, 1, DI), TOPK)


# contract -> (its case, the kernel it has to have gone through, the
# tokens a slot writes, the entry's page arrays)
# the two that do not read `live` yet (PERF.md sections 6 and 7, PR 43)
IGNORES_LIVE = {"paged_cache_update_attend",
                "paged_cache_latent_span_update_attend"}
CONTRACTS = {
    "paged_cache_update_attend":
        (_kv, "paged_attention", 1, ("k_pages", "v_pages")),
    "paged_cache_sparse_update_attend":
        (_kv_sparse, "paged_sparse_attention", 1,
         ("k_pages", "v_pages", "index_pages")),
    "paged_cache_latent_update_attend":
        (_latent, "paged_latent_attention", 1, ("pages",)),
    "paged_cache_latent_span_update_attend":
        (lambda rng: _latent(rng, span=2), "paged_latent_attention", 2,
         ("pages",)),
    "paged_cache_sparse_latent_update_attend":
        (_latent_sparse, "paged_sparse_latent_attention", 1,
         ("pages", "index_pages")),
}


def _value(x):
    return np.asarray(getattr(x, "_value", x))


def _stepped(name, entry, args):
    out, new, *n_sel = getattr(kc, name)(entry, *args)
    return _value(out), new, [_value(n) for n in n_sel]


@pytest.mark.parametrize("name", sorted(CONTRACTS))
def test_an_idle_slot_attends_over_nothing_and_a_live_one_as_before(
        name, monkeypatch):
    case, kernel, n, arrays = CONTRACTS[name]
    entry, args = case(np.random.default_rng([20261004, sorted(
        CONTRACTS).index(name)]))
    scored = []
    real = pa.paged_index_scores

    def scores(*args, **kw):
        scored.append(real(*args, **kw))
        return scored[-1]

    monkeypatch.setattr(pa, "paged_index_scores", scores)
    through = lambda: metrics.counter("kernels.paged_decode").value(
        kernel=kernel)
    before = through()
    out0, new0, sel0 = _stepped(name, entry, args)
    out1, new1, sel1 = _stepped(
        name, entry._replace(live=jnp.asarray(LIVE)), args)
    assert through() == before + 2          # the kernel, not the gather

    assert np.array_equal(out1[LIVE], out0[LIVE])
    assert np.abs(out0[LIVE]).min(axis=(1, 2, 3)).max() > 0
    assert np.abs(out0[~LIVE]).max() > 0    # it did attend: two trash rows
    if name not in IGNORES_LIVE:
        assert not out1[~LIVE].any()
        assert new1.live is not None and new0.live is None
    else:
        assert np.array_equal(out1, out0)
    for field in arrays:
        a0, a1 = _value(getattr(new0, field)), _value(getattr(new1, field))
        assert np.array_equal(a1, a0), field
        before_write = _value(getattr(entry, field))
        # the idle slots' tokens landed on the trash page, at `cl`
        assert not np.array_equal(a1[TRASH, 1:1 + n],
                                  before_write[TRASH, 1:1 + n]), field
        assert np.array_equal(a1[TRASH, 1 + n:], before_write[TRASH, 1 + n:])
    if sel0:                                # keys selected a slot
        assert np.array_equal(sel1[0][LIVE], sel0[0][LIVE])
        assert np.array_equal(sel0[0], np.minimum(CL + 1, TOPK))
        assert not sel1[0][~LIVE].any()
        without, with_live = (_value(s) for s in scored)
        assert np.array_equal(with_live[LIVE], without[LIVE])
        assert np.isneginf(with_live[~LIVE]).all()
        assert np.isfinite(without[~LIVE][:, :2]).all()


@pytest.mark.parametrize("n", [1, 2])
def test_attend_lens(n):
    cl = jnp.asarray(CL)
    assert np.array_equal(kc.attend_lens(cl, n), CL + n)
    got = kc.attend_lens(cl, n, jnp.asarray(LIVE))
    assert got.dtype == jnp.int32
    assert np.array_equal(got, np.where(LIVE, CL + n, 0))


def test_the_hand_set_ragged_form_goes_by_its_metadata():
    """Under host metadata an idle slot is one valid entry of the grid:
    `live` is not read (the ragged kernel is MHA's)."""
    rng = np.random.default_rng(20261005)
    entry, args = _kv(rng, hkv=H)
    meta = pa.build_ragged_meta(np.asarray(entry.block_table), CL + 1, PAGE)
    entry = entry._replace(
        ragged_meta={k: jnp.asarray(v) for k, v in meta.items()})
    out0, _, _ = _stepped("paged_cache_update_attend", entry, args)
    out1, new, _ = _stepped("paged_cache_update_attend",
                            entry._replace(live=jnp.asarray(LIVE)), args)
    assert np.array_equal(out1, out0) and np.abs(out1[~LIVE]).max() > 0
    assert new.ragged_meta is entry.ragged_meta

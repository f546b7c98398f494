"""Learned sparse attention: an indexer scores every earlier key for a
query, the `topk` best are selected, and attention runs over those alone
(the DeepSeek-Sparse-Attention form; models/keye_vl2.py).

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        s <= t
    S_t     = the min(topk, t + 1) keys of largest I[t, s], ties to the
              lower s; one set a token, shared by every head

This module holds what prefill and decode share and the prefill form:

- `select_topk`: the exact selection, as a keep-mask. The k-th largest
  score is found by bisection over the float32 bit pattern (32 counting
  passes over the row, no sort: `jax.lax.top_k` is a full sort on the
  TPU and `approx_max_k` is not exact), then ties at that value are
  admitted from the lowest position up by a prefix count.
- `prefill_index_scores`: I for one chunk of queries against a whole
  prompt's keys (a Pallas kernel that loops the index heads in VMEM; the
  XLA form writes a [chunk, heads, keys] float32 array first).
- `sparse_prefill_attention`: scores, selection and masked attention in
  query chunks, so that nothing of [prompt, prompt] extent outlives a
  chunk. The attention of a chunk is a flash kernel of its own
  (`selected_attention`, `_attend_kernel`): the query heads that share
  a KV head are rows of one matmul against a key block, and the chunk's
  selection comes in once a group as an additive tile. Without Pallas
  it is the XLA attention under the mask.
  A chunk does what its queries can see (`chunk_plan`): a chunk of
  padding runs nothing, and one whose queries see at most `topk` keys
  attends to all they see, without scores or a selection. And both
  kernels visit the key blocks its queries can see
  (`chunk_key_blocks`): a (row, tile of queries) computes the blocks
  from the row's first real key to the tile's last query, a block of
  the row's left padding or past that query is skipped, and a skipped
  grid step names the block that is resident before or after it, so
  it copies nothing either. Plan and table follow from the keys'
  validity alone, the same for every layer: a model computes them once
  a program and the kernels read the table as prefetched scalars.

The decode forms read the paged pool and live in
kernels/paged_attention.py. Scores, selection and softmax are float32;
the operands keep the cache's dtype.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (_Z, _NEG_INF, use_pallas as _use_pallas,
                      pallas_interpret, note_fallback, mxu_precision)

F32 = jnp.float32
U32 = jnp.uint32
_LANES = 128
_BLOCK_K = 512                 # keys a block, of both prefill kernels
# the rows of a tile's table of key blocks (`chunk_key_blocks`)
_NAMES, _RUNS = np.int32(0), np.int32(1)


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------

def _sortable(scores):
    """float32 -> uint32 whose unsigned order is the floats' order
    (-0.0 counted as 0.0, so that equal scores are equal keys)."""
    s = scores.astype(F32)
    s = jnp.where(s == F32(0), F32(0), s)
    b = jax.lax.bitcast_convert_type(s, jnp.int32)
    key = b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(key, U32) ^ U32(0x80000000)


def select_topk(scores, valid, k):
    """keep [..., L] bool: the min(k, number valid) positions of each
    row with the largest `scores` among `valid`, ties to the lower
    position. Exact: the threshold is the k-th largest value itself,
    found a bit of its pattern a counting pass (two or four bits a pass,
    three or fifteen candidates counted at once, were slower on the
    chip: PERF.md)."""
    u = jnp.where(valid, _sortable(scores), U32(0))
    k = jnp.int32(k)

    def bit(i, t):
        cand = t | (U32(1) << (U32(31) - i.astype(U32)))
        n = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, t)

    # t = the k-th largest key, or 0 where fewer than k are valid
    t = jax.lax.fori_loop(jnp.int32(0), jnp.int32(32), bit,
                          jnp.zeros(u.shape[:-1], U32))[..., None]
    above = u > t
    tied = (u == t) & valid
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32, keepdims=True)
    surplus = jnp.sum(tied, axis=-1, dtype=jnp.int32, keepdims=True) > room

    def by_position(_):
        rank = jnp.cumsum(tied.astype(jnp.int32), axis=-1, dtype=jnp.int32)
        return above | (tied & (rank <= room))

    # more ties at the threshold than places left is rare: the prefix
    # count over the row is paid only then
    return jax.lax.cond(jnp.any(surplus), by_position,
                        lambda _: above | tied, None)


# ---------------------------------------------------------------------------
# index scores of a chunk of queries against a prompt's keys
# ---------------------------------------------------------------------------

def _index_scores_xla(qi, w, ki):
    """qi [N, C, J, Di], w [N, C, J] float32, ki [N, S, Di] ->
    [N, C, S] float32."""
    s = jnp.einsum("ncjd,nsd->ncjs", qi, ki, preferred_element_type=F32)
    return jnp.sum(jax.nn.relu(s) * w.astype(F32)[..., None], axis=2)


def _prefill_scores_kernel(blocks_ref, q_ref, w_ref, k_ref, o_ref, *, heads,
                           row):
    b, j = pl.program_id(0), pl.program_id(1)

    # a key block before the row's first real key or past the chunk's
    # last query holds no key a query of the row may see: left as it
    # lies, the caller masks it
    @pl.when(blocks_ref[b, row, _RUNS, j] != 0)
    def _():
        k = k_ref[0]                                       # (bk, Di)
        w = w_ref[0]                                       # (C, J)
        acc = jnp.zeros(o_ref.shape[1:], F32)
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=F32,
                precision=mxu_precision(k))                # (C, bk)
            acc = acc + jnp.maximum(s, F32(0)) * w[:, h:h + 1]
        o_ref[0] = acc


def _index_scores_pallas(qi, w, ki, blocks, block_k, interpret):
    n, c, heads, di = qi.shape
    s = ki.shape[1]
    row = np.int32(blocks.shape[1] - 1)     # the whole chunk's
    # keys in and scores out by the block the step NAMES: a step that
    # does not run names the block resident before or after it, copies
    # nothing in and writes nothing new back
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, s // block_k),
        in_specs=[
            pl.BlockSpec((1, heads, c, di),
                         lambda b, j, t: (b, _Z, _Z, _Z)),
            pl.BlockSpec((1, c, heads), lambda b, j, t: (b, _Z, _Z)),
            pl.BlockSpec((1, block_k, di),
                         lambda b, j, t: (b, t[b, row, _NAMES, j], _Z))],
        out_specs=pl.BlockSpec(
            (1, c, block_k), lambda b, j, t: (b, _Z, t[b, row, _NAMES, j])))
    return pl.pallas_call(
        functools.partial(_prefill_scores_kernel, heads=heads, row=row),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, c, s), F32),
        interpret=interpret,
    )(blocks, qi.transpose(0, 2, 1, 3), w.astype(F32), ki)


def prefill_index_scores(qi, w, ki, blocks, interpret=False):
    """I of one chunk: qi [N, C, J, Di] (the chunk's index queries), w
    [N, C, J] float32, ki [N, S, Di] (every key of the prompt),
    `blocks` the chunk's table of key blocks (`chunk_key_blocks`: its
    last row is the whole chunk's) -> [N, C, S] float32. Entries of
    keys in blocks the table does not run (before a row's first real
    key, past the chunk's last query) are undefined: the caller's mask
    of what a query sees drops them."""
    interpret = interpret or pallas_interpret()
    c, s = qi.shape[1], ki.shape[1]
    block_k = min(_BLOCK_K, s)
    if interpret or _use_pallas():
        if c % 8 == 0 and s % block_k == 0 and block_k % _LANES == 0:
            return _index_scores_pallas(qi, w, ki, blocks, block_k,
                                        interpret)
        note_fallback("prefill_index_scores", "chunk_tiling")
    return _index_scores_xla(qi, w, ki)


# ---------------------------------------------------------------------------
# attention of a chunk of queries over its selected keys
# ---------------------------------------------------------------------------

_ATTEND_BLOCK_Q = 128
_ATTEND_TILE_ROWS = 1024      # 8 heads of a group x 128 queries


def attend_tiles(c, rep, s):
    """(queries a tile, keys a block) of `selected_attention` for a
    chunk of `c` queries of `rep` heads a KV head over `s` keys. A tile
    holds `_ATTEND_TILE_ROWS` rows of queries x heads of a group at
    most, and `_ATTEND_BLOCK_Q` queries at least: every tile reads the
    chunk's key blocks again, so a head that shares its keys with no
    other (`rep` 1) takes the whole chunk in one."""
    return (min(max(_ATTEND_BLOCK_Q, _ATTEND_TILE_ROWS // rep), c),
            min(_BLOCK_K, s))


def _attend_kernel(blocks_ref, q_ref, bias_ref, k_ref, v_ref, o_ref, m_scr,
                   l_scr, acc_scr, *, scale, rep, block_k):
    """One (row, KV head, query tile) over the key blocks: q_ref holds
    the tile's queries of the `rep` heads of the group, head-major
    [rep * bq, D]; bias_ref the selection of the tile's queries [bq, bk]
    (0 kept, _NEG_INF not), shared by the heads; blocks_ref the chunk's
    table of key blocks (`chunk_key_blocks`)."""
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # a key block before the row's first real key (its left padding) or
    # past the tile's last query adds nothing to any query of the tile
    @pl.when(blocks_ref[pl.program_id(0), pl.program_id(2), _RUNS, j] != 0)
    def _compute():
        q, k, v = q_ref[0, 0, 0], k_ref[0, 0], v_ref[0, 0]
        bq = bias_ref.shape[1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32,
            precision=mxu_precision(q, k)) * np.float32(scale)
        s = (s.reshape(rep, bq, block_k)
             + bias_ref[0].astype(F32)[None]).reshape(rep * bq, block_k)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=F32, precision=mxu_precision(v))
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        o_ref[0, 0, 0] = (acc_scr[:] / jnp.where(l == F32(0), F32(1), l)
                          ).astype(o_ref.dtype)


def _attend_pallas(q, k, v, keep, blocks, scale, bq, bk, interpret):
    """q [N, C, H, D]; k, v [N, Hkv, S, D]; keep [N, C, S] bool; blocks
    [N, C // bq (+ 1), 2, S // bk] int32: the key blocks each tile's
    steps name and run (`chunk_key_blocks`)."""
    n, c, h, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    rep, tiles = h // hkv, c // bq
    # [N, Hkv, tile, head of the group x query of the tile, D]
    fold = lambda a: a.reshape(n, tiles, bq, hkv, rep, d).transpose(
        0, 3, 1, 4, 2, 5).reshape(n, hkv, tiles, rep * bq, d)
    bias = jnp.where(keep, F32(0), _NEG_INF).astype(jnp.bfloat16)
    q_spec = pl.BlockSpec((1, 1, 1, rep * bq, d),
                          lambda b, g, i, j, t: (b, g, i, _Z, _Z))
    # K, V and the bias tile by the block the step NAMES: a step that
    # does not run names the block resident before or after it, so the
    # pipeline copies nothing for it
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d), lambda b, g, i, j, t: (b, g, t[b, i, _NAMES, j], _Z))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, hkv, tiles, s // bk),
        in_specs=[q_spec,
                  pl.BlockSpec(
                      (1, bq, bk),
                      lambda b, g, i, j, t: (b, i, t[b, i, _NAMES, j])),
                  kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((rep * bq, _LANES), F32),
                        pltpu.VMEM((rep * bq, _LANES), F32),
                        pltpu.VMEM((rep * bq, d), F32)])
    out = pl.pallas_call(
        functools.partial(_attend_kernel, scale=scale, rep=rep, block_k=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, hkv, tiles, rep * bq, d), q.dtype),
        interpret=interpret,
    )(blocks, fold(q), bias, k, v)
    return out.reshape(n, hkv, tiles, rep, bq, d).transpose(
        0, 2, 4, 1, 3, 5).reshape(n, c, h, d)


def selected_attention(q, k, v, keep, blocks, scale, interpret=False):
    """Attention of a chunk of queries over the keys `keep` marks. q
    [N, C, H, D]; k, v [N, Hkv, S, D] (head-major: the whole prompt's);
    keep [N, C, S] bool, causality and the padding in it; `blocks` the
    chunk's table of key blocks (`chunk_key_blocks`: each tile of
    `attend_tiles` queries visits the blocks between the row's first
    real key and the tile's last query, a row at a time) -> [N, C, H,
    D]. A query that keeps no key is don't-care."""
    from .attention import _xla_attention
    interpret = interpret or pallas_interpret()
    c, d, s = q.shape[1], q.shape[3], k.shape[2]
    bq, bk = attend_tiles(c, q.shape[2] // k.shape[1], s)
    if interpret or _use_pallas():
        if c % bq == 0 and bq % 8 == 0 and s % bk == 0 \
                and bk % _LANES == 0 and d % _LANES == 0:
            return _attend_pallas(q, k, v, keep, blocks, scale, bq, bk,
                                  interpret)
        note_fallback("sparse_prefill_attention", "chunk_tiling")
    return _xla_attention(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                          scale, False, mask=keep[:, None])


# ---------------------------------------------------------------------------
# prefill: score, select, attend, a chunk of queries at a time
# ---------------------------------------------------------------------------

PADDING, DENSE, SELECTED = 0, 1, 2     # what a chunk of queries has to do


def chunk_plan(key_valid, chunk, topk):
    """What each chunk of `chunk` queries of a left-padded batch has to
    do, from the keys' validity [N, S] alone -> [chunks] int32: PADDING
    where no row has a real query in it, DENSE where no query of it sees
    more than `topk` keys (it attends to all it sees, unselected), else
    SELECTED. The same for every layer of a program: a model computes
    it once and hands it to `sparse_prefill_attention`."""
    n, s = key_valid.shape
    c = min(int(chunk), s)
    key_valid = jnp.pad(key_valid, [(0, 0), (0, -s % c)])
    # the most keys a query of the chunk sees: the count at its last one
    seen = jnp.cumsum(key_valid, axis=1, dtype=jnp.int32)[:, c - 1::c]
    kind = jnp.where(jnp.max(seen, axis=0) > topk, SELECTED, DENSE)
    has_query = jnp.any(key_valid.reshape(n, -1, c), axis=(0, 2))
    return jnp.where(has_query, kind, PADDING).astype(jnp.int32)


def chunk_key_blocks(key_valid, chunk, rep):
    """Which key blocks each chunk of `chunk` queries of a left-padded
    batch visits, from the keys' validity [N, S] alone -> [chunks, N,
    tiles + 1, 2, blocks] int32, a table a (chunk, row of the batch,
    tile of `attend_tiles` queries at `rep` heads a KV head; the last
    "tile" is the whole chunk, for the index scores): [..., _RUNS, j] is
    1 where grid step j computes, from the block of the row's first
    real key to the block of the tile's last query (what a query of the
    tile may see: a selection keeps keys among those), and [..., _NAMES,
    j] is the block step j names: j where it runs, else the nearer end
    of that range, which is resident before or after it; a tile of
    padding runs nothing and names one block throughout. The same for
    every layer of a program, like `chunk_plan`: a model computes both
    once. The kernels read it as prefetched scalars, so a step costs no
    index arithmetic, traced or run."""
    n, s_real = key_valid.shape
    c = min(int(chunk), s_real)
    s = s_real + -s_real % c
    bq, bk = attend_tiles(c, rep, s)
    tiles, blocks = -(-c // bq), -(-s // bk)
    at = jnp.arange(blocks * bk, dtype=jnp.int32)
    first = jnp.min(
        jnp.where(jnp.pad(key_valid, [(0, 0), (0, at.size - s_real)]), at,
                  jnp.int32(at.size)).reshape(n, blocks, bk),
        axis=-1)                                # a block's first real key
    ends = jnp.minimum((jnp.arange(tiles + 1, dtype=jnp.int32) + 1)
                       * jnp.int32(bq), c) - 1  # a tile's last query
    last = jnp.arange(0, s, c, dtype=jnp.int32)[:, None] + ends
    live = first[None, :, None, :] <= last[:, None, :, None]
    j = jnp.arange(blocks, dtype=jnp.int32)
    lo = jnp.min(jnp.where(live, j, jnp.int32(blocks - 1)), axis=-1,
                 keepdims=True)
    hi = jnp.max(jnp.where(live, j, jnp.int32(-1)), axis=-1, keepdims=True)
    return jnp.stack([jnp.maximum(lo, jnp.minimum(j, hi)),
                      ((j >= lo) & (j <= hi)).astype(jnp.int32)], axis=-2)


def plan_counts(plan, blocks, chunk, s):
    """[7] int32 of a layer's prefill of N rows of `s` keys under
    `plan` and `blocks` (`chunk_plan`'s and `chunk_key_blocks`'s):
    chunks of padding, chunks without a selection, chunks with one; the
    keys a counting pass ran over (a selection counts over the bucket)
    and the keys of every chunk's bucket; then the (row, query tile, key
    block) triples `selected_attention` computes, and those from block
    0 to the block of the chunk's last query, the whole bucket's."""
    n, tiles = blocks.shape[1], blocks.shape[2] - 1
    c = min(int(chunk), s)
    bk = min(_BLOCK_K, s + -s % c)
    kinds = [jnp.sum(plan == kind, dtype=jnp.int32)
             for kind in (PADDING, DENSE, SELECTED)]
    runs = plan != PADDING
    attended = jnp.sum(blocks[:, :, :tiles, _RUNS], axis=(1, 2, 3),
                       dtype=jnp.int32)
    ends = jnp.arange(c - 1, plan.shape[0] * c, c, dtype=jnp.int32)
    bucket = jnp.int32(n * tiles) * (ends // jnp.int32(bk) + 1)
    return jnp.stack(kinds + [
        kinds[2] * jnp.int32(n * s), jnp.int32(plan.shape[0] * n * s),
        jnp.sum(jnp.where(runs, attended, 0), dtype=jnp.int32),
        jnp.sum(jnp.where(runs, bucket, 0), dtype=jnp.int32)])


def sparse_prefill_attention(q, k, v, qi, w, ki, key_valid, *, topk, scale,
                             chunk, plan=None):
    """Causal attention of a left-padded batch over each query's
    selected keys. q [N, S, H, D]; k, v [N, S, Hkv, D]; qi [N, S, J,
    Di]; w [N, S, J]; ki [N, S, Di]; key_valid [N, S] bool (False on the
    padding) -> out [N, S, H, D]. A query with at most `topk` visible
    keys attends to all of them (plain causal attention); a padding
    query sees no key and its row is don't-care. Scores, selection and
    the masked attention run `chunk` queries at a time, and a chunk does
    what `plan` says its queries need: the pair (`chunk_plan(key_valid,
    chunk, topk)`, `chunk_key_blocks(key_valid, chunk, H // Hkv)`),
    computed here where the caller has none: nothing where all of them
    are padding, no selection where none sees more than `topk` keys,
    and of the key blocks those that hold a key they may see."""
    n, s_real, h, d = q.shape
    c = min(int(chunk), s_real)
    if plan is None:
        plan = (chunk_plan(key_valid, c, topk),
                chunk_key_blocks(key_valid, c, h // k.shape[2]))
    tail = -s_real % c
    if tail:        # whole chunks: the tail's keys are seen by no query
        q, k, v, qi, w, ki, key_valid = (
            jnp.pad(a, [(0, 0), (0, tail)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, qi, w, ki, key_valid))
    s = s_real + tail
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)   # head-major
    kpos = jnp.arange(s, dtype=jnp.int32)

    def one(at):
        start, kind, blocks = at
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, c, axis=1)
        qpos = start + jnp.arange(c, dtype=jnp.int32)

        def real(_):
            seen = key_valid[:, None, :] \
                & (kpos[None, None, :] <= qpos[None, :, None])

            def selected(_):
                with jax.named_scope("dsa.indexer"):
                    scores = prefill_index_scores(cut(qi), cut(w), ki, blocks)
                with jax.named_scope("dsa.select"):
                    return select_topk(scores, seen, topk)

            keep = jax.lax.cond(kind == SELECTED, selected,
                                lambda _: seen, None)
            with jax.named_scope("dsa.attend"):
                return selected_attention(cut(q), k, v, keep, blocks, scale)

        return jax.lax.cond(kind != PADDING, real,
                            lambda _: jnp.zeros((n, c, h, d), q.dtype), None)

    out = jax.lax.map(one, (jnp.arange(0, s, c, dtype=jnp.int32), *plan))
    return jnp.moveaxis(out, 0, 1).reshape(n, s, h, d)[:, :s_real]

"""Decode attention over latent pages (multi-head latent attention in
absorbed form, DeepSeek-V2, arXiv:2405.04434; models/ling_hybrid.py).

A latent layer keeps ONE row a token for all heads, `[c | k_r]`: the
normed compressed latent (512 numbers) and the rotated shared key part
(64), on whole 128-lane rows of a page array [num_pages, page, lanes]
under the pool's page ids. With the up-projection absorbed into the
query (`q^_h = W_bK,h^T q_nope,h`, then `[q^_h | q_rope,h]`), every head
contracts against the same row, and the row's first numbers are also the
value: `o^_h = sum_s p_hs c_s`, up-projected after (`W_bV,h o^_h`).

So the H query heads are the rows of one [H, lanes] x [lanes, tokens]
matmul a block of pages and P.V is one [H, tokens] x [tokens, lanes]
matmul against the SAME block: one array, read once. `_paged_kernel`
(kernels/paged_attention.py) takes a K and a V array of one head size
and would read the rows twice; this kernel is its plan (grid over slots,
a slot's LIVE pages by its own DMAs, two buffers deep, loops rolled)
with one buffer. It returns the weighted sum of whole rows [B, H,
lanes]: the caller keeps the latent's part. Operands stay in the pool's
dtype; scores, softmax and accumulation are float32.

A query SPAN (`span` > 1: a speculative verify of a drafted token, the
draft pass after it) folds its queries into the row axis: `span` x H
rows, the oldest query's heads first, against the same blocks, so a
slot's live rows are still read once. The span's own rows are in the
pages already; query j of the span does not see the rows of the span's
later tokens (the last `span - 1 - j` of the slot's rows).
"""
from __future__ import annotations

import functools
import math as pymath

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (_Z, _NEG_INF, use_pallas as _use_pallas,
                      pallas_dtype_ok, pallas_interpret, note_fallback,
                      mxu_precision)
# rows or queries on a page array's lanes: its dtype, zeros past the width
from .paged_attention import (_note_decode_kernel,
                              index_key_rows as latent_rows)

# Tokens of one block: as the block-table kernel's `_BLOCK_KEY_COLUMNS`
_BLOCK_TOKENS = 2048
# ... at up to this many query rows (heads, or a span's heads)
_BLOCK_ROWS = 128


def _latent_attention_xla(q, pages, block_tables, lens, scale, keep=None,
                          span=1):
    """q [B, span x H, lanes]; pages [P, page, lanes]; lens [B] rows a
    slot holds -> [B, span x H, lanes]. Gathers each slot's whole table:
    the CPU's route and the kernel's oracle. `keep` [B, L] bool
    restricts each slot to its selected rows; under a `span` the rows
    of q are its queries' heads, the oldest query first, and query j
    sees `lens - (span - 1 - j)` rows."""
    b = q.shape[0]
    rows = pages[block_tables].reshape(b, -1, pages.shape[2])
    s = jnp.einsum("bhd,bld->bhl", q, rows,
                   preferred_element_type=jnp.float32) * np.float32(scale)
    live = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :] < lens[:, None]
    if keep is not None:
        live = live & keep
    live = live[:, None, :]
    if span > 1:
        later = np.int32(span - 1) - jnp.arange(
            q.shape[1], dtype=jnp.int32) // np.int32(q.shape[1] // span)
        live = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, None, :] \
            < (lens[:, None, None] - later[None, :, None])
    p = jax.nn.softmax(jnp.where(live, s, _NEG_INF), axis=-1)
    return jnp.einsum("bhl,bld->bhd", p.astype(rows.dtype), rows,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _latent_kernel(tables_ref, lens_ref, q_ref, *refs, scale, page_size, ppb,
                   selected=False, span=1):
    # with `selected`, a float32 row a slot (0 on a selected row's
    # column, _NEG_INF on the others') comes after q: the kernel still
    # reads every live page and the selection is a mask on the scores
    # (as `_paged_kernel`'s)
    bias_ref = refs[0] if selected else None
    rows_hbm, o_ref, buf_ref, sem = refs[1:] if selected else refs
    b = pl.program_id(0)
    i32 = np.int32
    n = ppb * page_size                    # tokens a block
    h, d = q_ref.shape[1:]
    ctx = lens_ref[b]
    n_pages = jax.lax.div(ctx + i32(page_size - 1), i32(page_size))
    n_blocks = jax.lax.div(n_pages + i32(ppb - 1), i32(ppb))

    def page_copy(blk, buf, i):
        # an ordinal past the slot's last live page re-reads that page
        # (its tokens are masked): every block is `ppb` pages
        o = jnp.minimum(blk * i32(ppb) + i, n_pages - i32(1))
        src = pl.ds(pl.multiple_of(tables_ref[b, o] * i32(page_size),
                                   page_size), page_size)
        dst = pl.ds(pl.multiple_of(i * i32(page_size), page_size), page_size)
        return pltpu.make_async_copy(rows_hbm.at[src], buf_ref.at[buf, dst],
                                     sem.at[buf])

    def each_page(do):      # rolled, and counted in int32 (see _paged_kernel)
        def body(i):
            do(i)
            return i + i32(1)
        jax.lax.while_loop(lambda i: i < i32(ppb), body, i32(0))

    def start_block(blk, buf):
        each_page(lambda i: page_copy(blk, buf, i).start())

    @pl.when(n_blocks > i32(0))
    def _first():
        start_block(i32(0), i32(0))

    q = q_ref[0]                                           # (H, lanes)
    seen = ctx
    if span > 1:        # (span x H, 1): the rows query j of the span sees
        row = jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0)
        seen = ctx - (i32(span - 1) - jax.lax.div(row, i32(h // span)))

    def block(blk, carry):
        m_prev, l_prev, acc = carry
        buf = jax.lax.rem(blk, i32(2))

        @pl.when(blk + i32(1) < n_blocks)
        def _prefetch():
            start_block(blk + i32(1), i32(1) - buf)

        each_page(lambda i: page_copy(blk, buf, i).wait())
        rows = buf_ref[buf]                                # (n, lanes)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=mxu_precision(q, rows)) * np.float32(scale)
        if selected:
            s = s + bias_ref[0, :, pl.ds(pl.multiple_of(blk * i32(n), n), n)]
        tok = blk * i32(n) + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(tok < seen, s, _NEG_INF)             # (H, n)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=mxu_precision(rows))
        return m_new, l_new, acc * alpha + pv

    _, l, acc = jax.lax.fori_loop(
        i32(0), n_blocks, block,
        (jnp.full((h, 1), _NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, d), jnp.float32)))
    safe_l = jnp.where(l == np.float32(0.0), np.float32(1.0), l)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)


def latent_pages_per_block(page, pages_per_seq, rows=0):
    """Pages of one block. Past `_BLOCK_ROWS` query rows (a span of two
    at 128 heads) a block holds fewer tokens, so that its float32
    scores [rows, tokens] stay the size they are at `_BLOCK_ROWS`."""
    tokens = _BLOCK_TOKENS * _BLOCK_ROWS // max(rows, _BLOCK_ROWS)
    ppb = 1
    while 2 * ppb <= pages_per_seq and 2 * ppb * page <= tokens:
        ppb *= 2
    return ppb


def _latent_attention_pallas(q, pages, block_tables, lens, scale, interpret,
                             keep=None, span=1):
    b, h, d = q.shape
    _, page, _ = pages.shape
    ppb = latent_pages_per_block(page, block_tables.shape[1], h)
    q_spec = pl.BlockSpec((1, h, d), lambda b_, tr, lr: (b_, _Z, _Z))
    selection, selection_specs = (), []
    if keep is not None:
        bias = jnp.where(keep, np.float32(0), _NEG_INF)[:, None, :]
        selection = (bias,)
        selection_specs = [pl.BlockSpec((1, 1, bias.shape[2]),
                                        lambda b_, tr, lr: (b_, _Z, _Z))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[q_spec, *selection_specs,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((2, ppb * page, d), pages.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, page_size=page,
                          ppb=ppb, selected=keep is not None, span=span),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lens.astype(jnp.int32), q, *selection,
      pages.reshape(-1, d))


def latent_gate_reason(h, lanes, page):
    """Why the Pallas kernel cannot take this geometry (a reason label
    of ``kernels.pallas_fallbacks``), or None: rows on whole 128-lane
    rows, whole sublane tiles of heads and of a page's tokens."""
    if lanes % 128:
        return "latent_dim_tiling"
    if h % 8:
        return "head_count_tiling"
    if page % 8:
        return "page_tiling"
    return None


def sparse_latent_gate_reason(h, lanes, page, pages_per_seq):
    """As `latent_gate_reason`, for the kernel under a selection: the
    slot's row of verdicts is read a block's columns at a time, so a
    table is whole blocks and a block whole 128-lane stretches."""
    ppb = latent_pages_per_block(page, pages_per_seq)
    if pages_per_seq % ppb or (ppb * page) % 128:
        return "table_tiling"
    return latent_gate_reason(h, lanes, page)


def paged_latent_attention(q, pages, block_tables, lens, scale=None,
                           interpret=False, keep=None, span=1):
    """One decode token a slot over the slot's live latent rows. q [B,
    H, width <= lanes] (the absorbed query); pages [num_pages, page,
    lanes]; block_tables [B, pages_per_seq]; lens [B] rows held, the new
    token's included -> [B, H, lanes], the softmax-weighted sum of whole
    rows (float32 accumulation, q's dtype). `keep` [B, pages_per_seq *
    page] bool restricts each slot to its selected rows (the selection
    is a mask on the scores: every live page is still read). With a
    `span` of s tokens a slot (their rows written, `lens` counting them
    all), q is [B, s x H, width], the oldest token's heads first, and
    token j attends to the first `lens - (s - 1 - j)` rows: causal
    inside the span, the live rows read once for all s."""
    sc = scale if scale is not None else 1.0 / pymath.sqrt(q.shape[-1])
    if span > 1 and keep is not None:
        raise ValueError(
            "a query span under a selection: one set of rows a slot cannot "
            "serve the span's several positions")
    interpret = interpret or pallas_interpret()
    q = latent_rows(q, pages)
    kernel = "paged_latent_attention" if keep is None \
        else "paged_sparse_latent_attention"
    with jax.named_scope("mla.attend"):
        if interpret or _use_pallas():
            h, page, lanes = q.shape[1], pages.shape[1], pages.shape[2]
            reason = latent_gate_reason(h // span, lanes, page) \
                if keep is None \
                else sparse_latent_gate_reason(h, lanes, page,
                                               block_tables.shape[1])
            if reason is None and not interpret \
                    and not pallas_dtype_ok(q, pages):
                reason = "dtype"
            if reason is None:
                _note_decode_kernel(kernel)
                return _latent_attention_pallas(q, pages, block_tables, lens,
                                                sc, interpret, keep=keep,
                                                span=span)
            note_fallback(kernel, reason)
        _note_decode_kernel("xla")
        return _latent_attention_xla(q, pages, block_tables, lens, sc, keep,
                                     span)


def paged_sparse_latent_attention(q, pages, index_pages, qi, w, block_tables,
                                  lens, topk, scale=None, interpret=False):
    """One decode token a slot over the `topk` latent rows its indexer
    selects (every row at a context of at most `topk`): the index
    scores and the exact selection of kernels/paged_attention.py and
    kernels/sparse_attention.py, then `paged_latent_attention` under the
    selection. It reads every live page, as the K/V form does
    (`paged_sparse_attention`). q [B, H, width <= lanes] (absorbed); qi
    [B, J, Di] and w [B, J] the token's index queries and their weights;
    lens [B] rows held, the new token's included -> (out [B, H, lanes],
    keep [B, L] bool: the selection)."""
    from .paged_attention import paged_index_scores
    from .sparse_attention import select_topk
    with jax.named_scope("dsa.indexer"):
        scores = paged_index_scores(qi, w, index_pages, block_tables, lens,
                                    interpret)
    with jax.named_scope("dsa.select"):
        live = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :] \
            < lens[:, None]
        keep = select_topk(scores, live, topk)
    return paged_latent_attention(q, pages, block_tables, lens, scale,
                                  interpret, keep=keep), keep

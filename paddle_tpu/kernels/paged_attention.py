"""Paged (block) attention for serving decode.

Reference parity: paddle/phi/kernels/fusion/gpu block_multihead_attention
(the paged KV-cache attention behind paddle.incubate.nn.functional.
block_multihead_attention, used by PaddleNLP's inference server) and the
vLLM-style PagedAttention it mirrors.

TPU-native design: the KV cache lives in HBM as fixed-size pages
[num_pages, page_size, n_kv_heads, head_dim]; each sequence owns a block
table of page indices. One decode step attends a single query token per
sequence against its pages. The Pallas kernel streams pages through VMEM
with the block table supplied via *scalar prefetch* (the table is read on
the scalar core BEFORE the grid runs, so page fetches become plain block
DMAs — the canonical TPU paged-attention pattern; cf. PAPERS.md "Ragged
Paged Attention" and jax.experimental.pallas.ops.tpu.paged_attention).
Online softmax accumulates across pages in VMEM scratch.
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
                      tp_shard_degree, partitioned, mxu_precision)

# How a tensor-parallel replica splits the paged kernels' operands
# (_common.partitioned): heads over 'model'; block tables, lengths and
# ragged metadata whole on every shard.
_Q_HEADS = (None, "heads", None)              # q/out [B, H, D]
_SPAN_HEADS = (None, None, "heads", None)     # q/out [B, Qb, H, D]
_PAGE_HEADS = (None, None, "heads", None)     # pages [P, page, Hkv, D]
_META_FIELDS = ("seq", "page", "ordinal", "first", "last", "valid")
# Scoped VMEM the TPU compiler grants a kernel by default on v5e.
_VMEM_SCOPED_BYTES = 16 * 1024 * 1024
# The kernels that take a group of query heads a KV head (GQA); the
# others contract head against head and need H == Hkv.
_GROUP_KERNELS = ("paged_attention", "paged_sparse_attention")


def paged_gate_reason(kernel, h, hkv, d, tp=1):
    """Why `kernel` cannot take this head geometry, as the reason label
    of ``kernels.pallas_fallbacks``, or None when it can. The query
    heads must be whole groups of the KV heads (H % Hkv == 0) for a
    kernel in `_GROUP_KERNELS` and equal to them for the others
    (``gqa_ratio`` either way). Under tensor-parallel serving the head
    axes are sharded over 'model', so the tiling constraints must hold
    for the PER-SHARD head count H / tp: a global H that tiles but a
    shard that doesn't is ``tp_head_shard``. Query heads are KV-major,
    so a shard's query heads are the groups of its own KV heads when
    both counts divide."""
    if h % hkv != 0 or (h != hkv and kernel not in _GROUP_KERNELS):
        return "gqa_ratio"
    if d % 128 != 0:
        return "head_dim_tiling"
    if h % 8 != 0:
        return "head_count_tiling"
    if tp > 1 and (h % tp != 0 or hkv % tp != 0 or (h // tp) % 8 != 0):
        return "tp_head_shard"
    return None


def _paged_gate(kernel, q, k_pages, v_pages, interpret, tp_degree=None):
    """Shared Pallas-vs-XLA gate for the paged kernels: returns True
    when the Pallas path runs; a wanted-but-lost fast path is recorded
    via ``kernels.pallas_fallbacks{kernel,reason}`` (docs/
    OBSERVABILITY.md) so production silently dropping to plain XLA is
    observable. The geometry is judged by `paged_gate_reason`, at
    ``tp_degree`` if given, else the declared mesh's
    (``_common.tp_shard_degree()``)."""
    if not (interpret or _use_pallas()):
        return False
    tp = int(tp_degree) if tp_degree is not None else tp_shard_degree()
    reason = paged_gate_reason(kernel, q.shape[-2], k_pages.shape[2],
                               q.shape[-1], tp)
    if reason is None and not interpret \
            and not pallas_dtype_ok(q, k_pages, v_pages):
        reason = "dtype"
    if reason is not None:
        note_fallback(kernel, reason)
    return reason is None


def _note_decode_kernel(kernel):
    """Count which kernel a single-token decode attention was traced
    with: ``kernels.paged_decode{kernel}``, `kernel` the Pallas
    kernel's own name ("paged_attention", or a masked or latent one's)
    or "xla". Like `note_fallback` it runs at trace time only, once a
    layer of each compiled decode program, and adds nothing to the
    program."""
    from ..observability import metrics as _obsm
    _obsm.counter("kernels.paged_decode").inc(kernel=kernel)


# ---------------------------------------------------------------------------
# XLA block-table path (any GQA ratio): the route of every geometry that
# fails `_paged_gate`, of the CPU, and the numeric oracle of the kernels.
# It gathers each slot's whole block table and attends per KV head
# GROUP: the `rep = H // Hkv` query heads that share a KV head are rows
# of one [rep, D] x [D, L] contraction against that head's keys, so the
# gathered table is read as it lies, [B, L, Hkv, D] in the pool's dtype,
# and never repeated to H heads or widened to float32. MHA is rep = 1.
# The cost is slots x table length whatever is cached.
# ---------------------------------------------------------------------------

def _gathered_group_attention(q, k_pages, v_pages, block_tables, ok, scale):
    """The one body of both XLA paths. q: [B, Q, H, D] (Q query
    positions a slot); pages: [P, page, Hkv, D]; block_tables:
    [B, pages_per_seq]; ok: [B, Q, L] bool, the keys each query may
    see (L = pages_per_seq * page) → [B, Q, H, D] in q's dtype.

    Scores and P.V are `dot_general`s batched on (B, Hkv) whose
    operands stay in the pool's dtype and accumulate in float32
    (products of bf16 operands are exact there); the softmax is
    float32. A query with no visible key attends uniformly (`_NEG_INF`
    is finite), so nothing is NaN; the varq path zeroes such rows."""
    b, nq, h, d = q.shape
    hkv = k_pages.shape[2]
    rep = h // hkv
    k = k_pages[block_tables].reshape(b, -1, hkv, d)        # [B, L, Hkv, D]
    v = v_pages[block_tables].reshape(b, -1, hkv, d)
    qg = q.reshape(b, nq, hkv, rep, d)
    s = jnp.einsum("bqgrd,blgd->bgqrl", qg, k,
                   preferred_element_type=jnp.float32) * np.float32(scale)
    s = jnp.where(ok[:, None, :, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgqrl,blgd->bqgrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, nq, h, d).astype(q.dtype)


def _paged_attention_xla(q, k_pages, v_pages, block_tables, context_lens,
                         scale):
    """One decode token a slot through the block tables. q: [B, H, D];
    pages: [P, page, Hkv, D]; tables: [B, pages_per_seq];
    context_lens: [B] → out [B, H, D]. The Q = 1 case of
    `_gathered_group_attention`."""
    n_keys = block_tables.shape[1] * k_pages.shape[1]
    ok = jnp.arange(n_keys)[None, :] < context_lens[:, None]
    return _gathered_group_attention(q[:, None], k_pages, v_pages,
                                     block_tables, ok[:, None, :],
                                     scale)[:, 0]


# ---------------------------------------------------------------------------
# Pallas kernel, block table via scalar prefetch: any group ratio
# rep = H // Hkv (MHA is rep = 1). It reads a slot's LIVE pages only,
# `ppb` pages a block, and never writes a gathered table.
#
# The pool is taken as it lies, viewed [num_pages * page * Hkv, D] (a
# bitcast: the trailing (Hkv, D) tiles of a page are contiguous), so a
# block of pages is a [columns, D] matrix whose key axis interleaves
# tokens and KV heads: column c is token c // Hkv under KV head c % Hkv.
# All H query heads contract against it in ONE MXU matmul
# [H, D] x [D, columns]; a query head keeps the columns of its own KV
# head (the rep heads of a group are rows over the same columns) and the
# mask sends the others to -inf before the softmax, so P.V is again one
# matmul [H, columns] x [columns, D] with no per-head slicing. Operands
# stay in the pool's dtype, accumulation and the online softmax across
# blocks are float32: the precision of `_gathered_group_attention`.
#
# Pages move by DMAs the kernel issues itself, two buffers deep: block
# j + 1 is in flight while block j is contracted. The grid runs over
# slots and the loop over a slot's live blocks, so bytes and time follow
# the cached length rounded up to a block, not the table's length.
# ---------------------------------------------------------------------------

# Key columns (tokens x KV heads) of one block. The scores of a block
# are a float32 [H, columns] value; on a v5e at Mistral-7B's decode
# shape (16 slots of 1.1k-2.6k tokens, 32 / 8 heads of 128, page 16) the
# kernel took 0.348 / 0.256 / 0.239 ms at 512 / 1024 / 2048 columns.
_BLOCK_KEY_COLUMNS = 2048


def paged_block_vmem_bytes(ppb, h, hkv, d, page, itemsize):
    """VMEM the block-table decode kernel needs at `ppb` pages a block,
    from its shapes: K and V blocks in two buffers each, the float32
    scores, probabilities and mask of one block with the probabilities'
    copy in the pool's dtype, the double-buffered q/out blocks and the
    float32 accumulator."""
    columns = ppb * page * hkv
    buffers = 2 * 2 * columns * d * itemsize
    products = h * columns * (3 * 4 + itemsize)
    q_out = 2 * 2 * h * d * itemsize + h * d * 4
    return buffers + products + q_out


def paged_pages_per_block(h, hkv, d, page, itemsize, pages_per_seq):
    """Pages a block of the block-table decode kernel: the largest power
    of two, at most a slot's table, whose block stays within
    `_BLOCK_KEY_COLUMNS` and the scoped VMEM limit."""
    ppb = 1
    while (2 * ppb <= pages_per_seq
           and 2 * ppb * page * hkv <= _BLOCK_KEY_COLUMNS
           and paged_block_vmem_bytes(2 * ppb, h, hkv, d, page, itemsize)
           <= _VMEM_SCOPED_BYTES):
        ppb *= 2
    return ppb


def _paged_kernel(tables_ref, lens_ref, q_ref, *refs, scale, page_size, hkv,
                  ppb, selected=False):
    # with `selected`, a float32 row a slot (0 on a selected key's
    # columns, _NEG_INF on the others') comes after q: the kernel still
    # reads every live page and the selection is a mask on the scores
    bias_ref = refs[0] if selected else None
    k_hbm, v_hbm, o_ref, k_buf, v_buf, sem = refs[1:] if selected else refs
    b = pl.program_id(0)
    h, d = q_ref.shape[1:]
    rep = h // hkv
    rows = page_size * hkv                 # key columns of one page
    i32 = np.int32
    ctx = lens_ref[b]
    n_pages = jax.lax.div(ctx + i32(page_size - 1), i32(page_size))
    n_blocks = jax.lax.div(n_pages + i32(ppb - 1), i32(ppb))

    def page_copy(blk, buf, i):
        """The K and V DMAs of page `i` of block `blk` into buffer
        `buf`. An ordinal past the slot's last live page re-reads that
        page (its columns are masked), so every block is `ppb` pages to
        start and to wait for."""
        o = jnp.minimum(blk * i32(ppb) + i, n_pages - i32(1))
        src = pl.ds(pl.multiple_of(tables_ref[b, o] * i32(rows), rows), rows)
        dst = pl.ds(pl.multiple_of(i * i32(rows), rows), rows)
        return (pltpu.make_async_copy(k_hbm.at[src], k_buf.at[buf, dst],
                                      sem.at[buf, i32(0)]),
                pltpu.make_async_copy(v_hbm.at[src], v_buf.at[buf, dst],
                                      sem.at[buf, i32(1)]))

    def each_page(do):
        # A rolled loop: the kernel is traced and lowered once a layer,
        # and 2 * ppb descriptors unrolled at each of three sites cost a
        # 16-layer decode program 22 s of set-up on the chip's host.
        # `while_loop`, because with x64 on a `fori_loop` over constant
        # bounds counts in int64, which Mosaic does not lower.
        def body(i):
            do(i)
            return i + i32(1)
        jax.lax.while_loop(lambda i: i < i32(ppb), body, i32(0))

    def start_block(blk, buf):
        each_page(lambda i: [c.start() for c in page_copy(blk, buf, i)])

    @pl.when(n_blocks > i32(0))
    def _first():
        start_block(i32(0), i32(0))

    q = q_ref[0]                                           # (H, D)

    def block(blk, carry):
        m_prev, l_prev, acc = carry                        # (H,1) (H,1) (H,D)
        buf = jax.lax.rem(blk, i32(2))

        @pl.when(blk + i32(1) < n_blocks)
        def _prefetch():
            start_block(blk + i32(1), i32(1) - buf)

        each_page(lambda i: [c.wait() for c in page_copy(blk, buf, i)])
        k = k_buf[buf]                                     # (columns, D)
        v = v_buf[buf]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=mxu_precision(q, k)) * np.float32(scale)
        if selected:
            cols = ppb * rows
            s = s + bias_ref[0, :, pl.ds(pl.multiple_of(blk * i32(cols),
                                                        cols), cols)]
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        tok = blk * i32(ppb * page_size) + jax.lax.div(col, i32(hkv))
        mine = jax.lax.rem(col, i32(hkv)) == jax.lax.div(row, i32(rep))
        s = jnp.where(mine & (tok < ctx), s, _NEG_INF)     # (H, columns)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=mxu_precision(v))
        return m_new, l_new, acc * alpha + pv

    _, l, acc = jax.lax.fori_loop(
        i32(0), n_blocks, block,
        (jnp.full((h, 1), _NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, d), jnp.float32)))
    safe_l = jnp.where(l == np.float32(0.0), np.float32(1.0), l)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)


def _paged_attention_pallas(q, k_pages, v_pages, block_tables, context_lens,
                            scale, interpret=False, keep=None):
    """q: [B, H, D] → [B, H, D]; H a multiple of the pool's Hkv. A slot
    with no cached token gives zeros. `keep` [B, pages_per_seq * page]
    bool restricts each slot to its selected keys."""
    b, h, d = q.shape
    _, page, hkv, _ = k_pages.shape
    ppb = paged_pages_per_block(h, hkv, d, page, k_pages.dtype.itemsize,
                                block_tables.shape[1])
    columns = ppb * page * hkv
    q_spec = pl.BlockSpec((1, h, d), lambda b_, tr, lr: (b_, _Z, _Z))
    selection, selection_specs = (), []
    if keep is not None:
        # a key's verdict on each of its Hkv columns, as the blocks lie
        bias = jnp.repeat(jnp.where(keep, np.float32(0), _NEG_INF), hkv,
                          axis=1)[:, None, :]
        selection = (bias,)
        selection_specs = [pl.BlockSpec((1, 1, bias.shape[2]),
                                        lambda b_, tr, lr: (b_, _Z, _Z))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[q_spec, *selection_specs,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, columns, d), k_pages.dtype),
            pltpu.VMEM((2, columns, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=scale, page_size=page,
                               hkv=hkv, ppb=ppb, selected=keep is not None)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      q, *selection, k_pages.reshape(-1, d), v_pages.reshape(-1, d))


def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    scale=None, interpret=False):
    """Single-step decode attention over a paged KV cache.

    q: [B, H, D] (one query token per sequence)
    k_pages/v_pages: [num_pages, page_size, n_kv_heads, D]
    block_tables: [B, pages_per_seq] int32 page ids per sequence
    context_lens: [B] int32 valid token counts
    Returns [B, H, D]. The Pallas kernel takes any whole group ratio
    H // n_kv_heads at D % 128 == 0, H % 8 == 0 (`paged_gate_reason`);
    the rest goes through the XLA block-table path.
    """
    d = q.shape[-1]
    sc = scale if scale is not None else 1.0 / pymath.sqrt(d)
    interpret = interpret or pallas_interpret()
    if _paged_gate("paged_attention", q, k_pages, v_pages,
                   interpret):
        _note_decode_kernel("paged_attention")
        return partitioned(
            lambda q_, k_, v_, bt, cl: _paged_attention_pallas(
                q_, k_, v_, bt, cl, sc, interpret=interpret),
            [_Q_HEADS, _PAGE_HEADS, _PAGE_HEADS, None, None], _Q_HEADS,
            q, k_pages, v_pages, block_tables, context_lens)
    _note_decode_kernel("xla")
    return _paged_attention_xla(q, k_pages, v_pages, block_tables,
                                context_lens, sc)


# ---------------------------------------------------------------------------
# Learned sparse attention, decode: score, select, attend (the prefill
# form and the selection itself are in kernels/sparse_attention.py).
#
# A layer with an indexer keeps one index key a token in a third paged
# array [num_pages, page, lanes] under the pool's page ids (a key's Di
# values, then zeros to whole 128-lane rows). A decode step scores the
# slot's live index keys for its one query token,
#   I[s] = sum_j w[j] * relu(qI[j] . kI[s]),
# selects the `topk` best exactly (all of them at a context of at most
# `topk`), and attends over the selected keys alone.
#
# Scores: a Pallas kernel on the block-table kernel's plan (grid over
# slots, a slot's live pages by its own DMAs, two buffers deep, loops
# rolled). The index pool is taken as it lies, viewed [num_pages * page,
# lanes] (a bitcast); the J index heads, zero-padded like the keys, are
# the rows of one [J, lanes] x [lanes, tokens] contraction a block, and
# the weighted sum over them is the block's row of scores, written into
# the slot's whole row in VMEM.
#
# Attention: the block-table kernel above with the selection as a mask
# on its scores (`keep`). It reads every live page; at contexts of a few
# thousand keys and pages of 16 nearly every page holds a selected key,
# so gathering rows would read as many pages by many more descriptors
# (measured both ways: PERF.md).
# ---------------------------------------------------------------------------

_INDEX_PAGES_PER_BLOCK = 32


def index_key_rows(keys, index_pages):
    """Index keys or queries [..., Di] as rows of `index_pages`: its
    dtype, zeros on the lanes past Di."""
    pad = index_pages.shape[-1] - keys.shape[-1]
    return jnp.pad(keys.astype(index_pages.dtype),
                   [(0, 0)] * (keys.ndim - 1) + [(0, pad)])


def _index_scores_xla(qi, w, index_pages, block_tables, lens):
    """qi [B, J, lanes]; w [B, J] float32; index_pages [P, page, lanes];
    lens [B] keys a slot holds -> [B, L] float32, -inf past `lens`."""
    b = qi.shape[0]
    ki = index_pages[block_tables].reshape(b, -1, index_pages.shape[2])
    s = jnp.einsum("bjd,bld->bjl", qi, ki,
                   preferred_element_type=jnp.float32)
    out = jnp.sum(jax.nn.relu(s) * w.astype(jnp.float32)[..., None], axis=1)
    live = jnp.arange(ki.shape[1], dtype=jnp.int32)[None, :] < lens[:, None]
    return jnp.where(live, out, -jnp.inf)


def _index_scores_kernel(tables_ref, lens_ref, q_ref, w_ref, k_hbm, o_ref,
                         k_buf, sem, *, page_size, ppb):
    b = pl.program_id(0)
    i32 = np.int32
    n = ppb * page_size                    # tokens a block
    ctx = lens_ref[b]
    n_pages = jax.lax.div(ctx + i32(page_size - 1), i32(page_size))
    n_blocks = jax.lax.div(n_pages + i32(ppb - 1), i32(ppb))
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)

    def page_copy(blk, buf, i):
        o = jnp.minimum(blk * i32(ppb) + i, n_pages - i32(1))
        src = pl.ds(pl.multiple_of(tables_ref[b, o] * i32(page_size),
                                   page_size), page_size)
        dst = pl.ds(pl.multiple_of(i * i32(page_size), page_size), page_size)
        return pltpu.make_async_copy(k_hbm.at[src], k_buf.at[buf, dst],
                                     sem.at[buf])

    def each_page(do):
        def body(i):
            do(i)
            return i + i32(1)
        jax.lax.while_loop(lambda i: i < i32(ppb), body, i32(0))

    def start_block(blk, buf):
        each_page(lambda i: page_copy(blk, buf, i).start())

    @pl.when(n_blocks > i32(0))
    def _first():
        start_block(i32(0), i32(0))

    q = q_ref[0]                                           # (J, Di)
    w = w_ref[0]                                           # (J, 1)

    def block(blk, carry):
        buf = jax.lax.rem(blk, i32(2))

        @pl.when(blk + i32(1) < n_blocks)
        def _prefetch():
            start_block(blk + i32(1), i32(1) - buf)

        each_page(lambda i: page_copy(blk, buf, i).wait())
        k = k_buf[buf]                                     # (n, Di)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=mxu_precision(q, k))                 # (J, n)
        row = jnp.sum(jnp.maximum(s, np.float32(0)) * w, axis=0,
                      keepdims=True)                       # (1, n)
        tok = blk * i32(n) + jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
        o_ref[0, :, pl.ds(pl.multiple_of(blk * i32(n), n), n)] = jnp.where(
            tok < ctx, row, -jnp.inf)
        return carry

    jax.lax.fori_loop(i32(0), n_blocks, block, i32(0))


def _index_scores_pallas(qi, w, index_pages, block_tables, lens, ppb,
                         interpret):
    b, heads, di = qi.shape
    _, page, _ = index_pages.shape
    n_keys = block_tables.shape[1] * page
    spec = lambda shape: pl.BlockSpec((1,) + shape,
                                      lambda b_, tr, lr: (b_, _Z, _Z))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[spec((heads, di)), spec((heads, 1)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=spec((1, n_keys)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb * page, di), index_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_index_scores_kernel, page_size=page, ppb=ppb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, n_keys), jnp.float32),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lens.astype(jnp.int32), qi,
      w.astype(jnp.float32)[..., None],
      index_pages.reshape(-1, di))[:, 0]


def index_scores_gate_reason(lanes, page, pages_per_seq):
    """Why the Pallas index-score kernel cannot take this geometry (a
    reason label of ``kernels.pallas_fallbacks``), or None: keys on
    whole 128-lane rows, a block of 16 pages whole 128-lane stretches
    of a slot's row of scores, and a slot's table whole blocks."""
    if lanes % 128:
        return "index_dim_tiling"
    if pages_per_seq % 16 or (16 * page) % 128:
        return "table_tiling"
    return None


def paged_index_scores(qi, w, index_pages, block_tables, lens,
                       interpret=False):
    """Index scores of one query token a slot over the slot's live
    index keys. qi [B, J, Di]; w [B, J]; index_pages [num_pages, page,
    lanes >= Di]; block_tables [B, pages_per_seq]; lens [B] keys held
    (the new token's included) -> [B, pages_per_seq * page] float32,
    -inf at the positions past `lens`."""
    interpret = interpret or pallas_interpret()
    _, page, di = index_pages.shape
    qi = index_key_rows(qi, index_pages)
    pps = block_tables.shape[1]
    if interpret or _use_pallas():
        reason = index_scores_gate_reason(di, page, pps)
        if reason is None and not interpret \
                and not pallas_dtype_ok(qi, index_pages):
            reason = "dtype"
        if reason is None:
            ppb = _INDEX_PAGES_PER_BLOCK if pps % _INDEX_PAGES_PER_BLOCK == 0 \
                else 16
            return _index_scores_pallas(qi, w, index_pages, block_tables,
                                        lens, ppb, interpret)
        note_fallback("paged_index_scores", reason)
    return _index_scores_xla(qi, w, index_pages, block_tables, lens)


def paged_sparse_attention(q, k_pages, v_pages, index_pages, qi, w,
                           block_tables, context_lens, topk, scale=None,
                           interpret=False):
    """One decode token a slot over the `topk` keys its indexer selects
    (every key at a context of at most `topk`). q [B, H, D]; qi [B, J,
    Di] and w [B, J] the token's index queries and their weights;
    context_lens [B] the keys a slot holds, the new token's included.
    Returns (out [B, H, D], keep [B, L] bool: the selection)."""
    from .sparse_attention import select_topk
    d = q.shape[-1]
    sc = scale if scale is not None else 1.0 / pymath.sqrt(d)
    interpret = interpret or pallas_interpret()
    with jax.named_scope("dsa.indexer"):
        scores = paged_index_scores(qi, w, index_pages, block_tables,
                                    context_lens, interpret)
    with jax.named_scope("dsa.select"):
        live = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :] \
            < context_lens[:, None]
        keep = select_topk(scores, live, topk)
    with jax.named_scope("dsa.attend"):
        if _paged_gate("paged_sparse_attention", q, k_pages, v_pages,
                       interpret):
            _note_decode_kernel("paged_sparse_attention")
            out = _paged_attention_pallas(q, k_pages, v_pages, block_tables,
                                          context_lens, sc,
                                          interpret=interpret, keep=keep)
        else:
            _note_decode_kernel("xla")
            out = _gathered_group_attention(
                q[:, None], k_pages, v_pages, block_tables, keep[:, None, :],
                sc)[:, 0]
    return out, keep


# ---------------------------------------------------------------------------
# Ragged metadata: the (sequence, page) pairs a grid runs over, built on
# the host and scalar-prefetched by `paged_attention_ragged_varq` (the
# mixed and verify steps), which has no block-table form.
# ---------------------------------------------------------------------------

def build_ragged_meta(block_tables, context_lens, page_size, bucket_to=None):
    """Flatten per-sequence page lists into kernel metadata.

    block_tables: [B, pages_per_seq] int (host); context_lens: [B] int
    (host). Returns dict of int32 arrays of length G (bucketed):
    seq (owning sequence), page (physical page id), ordinal (page index
    within its sequence), first/last (1 at a sequence's first/last
    page), valid (0 on padding entries). Padding entries sit at the
    end and are fully skipped by the kernel."""
    bt = np.asarray(block_tables)
    cl = np.asarray(context_lens)
    # vectorized flatten (this runs on the host before EVERY decode
    # step in the serving loop — no per-page python iteration)
    n_pages = np.where(cl > 0, -(-cl // page_size), 0).astype(np.int64)
    seqs_a = np.repeat(np.arange(bt.shape[0]), n_pages)
    ords_a = np.concatenate([np.arange(n) for n in n_pages]) \
        if len(n_pages) else np.zeros(0, np.int64)
    pages_a = bt[seqs_a, ords_a] if seqs_a.size else seqs_a
    firsts_a = (ords_a == 0).astype(np.int64)
    lasts_a = (ords_a == n_pages[seqs_a] - 1).astype(np.int64) \
        if seqs_a.size else seqs_a
    seqs, pages = seqs_a.tolist(), pages_a.tolist()
    ords, firsts, lasts = (ords_a.tolist(), firsts_a.tolist(),
                           lasts_a.tolist())
    g = len(seqs)
    if bucket_to is None:
        bucket_to = 8
        while bucket_to < g:
            bucket_to *= 2
    if g > bucket_to:
        raise ValueError(f"{g} page entries exceed bucket {bucket_to}")
    pad = bucket_to - g
    # padding entries alias the LAST real entry's seq/page: their output
    # window then never moves after the final real flush, so the
    # end-of-grid writeback re-emits that row's already-correct block
    # (a fill of 0 would drag stale buffer contents into row 0)
    fill_seq = seqs[-1] if seqs else 0
    fill_page = pages[-1] if pages else 0
    mk = lambda xs, fill: np.asarray(xs + [fill] * pad, np.int32)
    return {
        "seq": mk(seqs, fill_seq), "page": mk(pages, fill_page),
        "ordinal": mk(ords, 0),
        "first": mk(firsts, 0), "last": mk(lasts, 0),
        "valid": np.asarray([1] * g + [0] * pad, np.int32),
    }


class RaggedMetaBuilder:
    """Incrementally maintained ragged-grid metadata for the serving
    decode loop.

    `build_ragged_meta` re-flattens every (slot, page) pair from scratch
    before each decode step — O(B * pages_per_seq) host work per token.
    The builder instead gives each slot a FIXED row segment
    [b*pages_per_seq, (b+1)*pages_per_seq) of the flat arrays, so the
    per-step delta is O(1): a slot acquires at most one new page per
    step (when its context length crosses a page boundary), and only
    admission/eviction rewrite a whole segment.

    Segment layout keeps each sequence's pages contiguous and in
    ordinal order (the kernel's online-softmax accumulation contract);
    a segment's padding rows alias the slot's last valid page with
    valid=0, so the kernel's output window never moves off the row
    between its final flush and the next slot's first page — identical
    to build_ragged_meta's end-padding trick, applied per segment. The
    grid size is the constant B*pages_per_seq, so every decode step
    reuses one compiled kernel.
    """

    FIELDS = _META_FIELDS

    def __init__(self, n_slots, pages_per_seq, page_size, trash_page=0):
        self.B = int(n_slots)
        self.pps = int(pages_per_seq)
        self.page = int(page_size)
        self.trash = int(trash_page)
        G = self.B * self.pps
        self.seq = np.repeat(np.arange(self.B), self.pps).astype(np.int32)
        self.page_ids = np.full(G, trash_page, np.int32)
        self.ordinal = np.tile(np.arange(self.pps), self.B).astype(np.int32)
        self.first = np.zeros(G, np.int32)
        self.last = np.zeros(G, np.int32)
        self.valid = np.zeros(G, np.int32)
        self._n = np.zeros(self.B, np.int64)      # valid pages per slot
        self._tables = np.full((self.B, self.pps), trash_page, np.int32)

    def _npages(self, post_len):
        return max(1, -(-int(post_len) // self.page))

    def set_slot(self, b, table_row, post_len):
        """(Re)build slot b's segment: `table_row` is its block-table
        row (page ids, trash-padded), `post_len` the POST-write context
        length the next decode step will attend (ctx + 1)."""
        n = self._npages(post_len)
        lo = b * self.pps
        self._tables[b, :] = table_row[:self.pps]
        seg = slice(lo, lo + self.pps)
        self.page_ids[seg] = self._tables[b, min(n, self.pps) - 1]
        self.page_ids[lo:lo + n] = self._tables[b, :n]
        self.first[seg] = 0
        self.last[seg] = 0
        self.valid[seg] = 0
        self.first[lo] = 1
        self.last[lo + n - 1] = 1
        self.valid[lo:lo + n] = 1
        self._n[b] = n

    def clear_slot(self, b):
        """Slot went inactive: one valid entry over the trash page (the
        decode step still writes the slot's dummy token somewhere)."""
        row = np.full(self.pps, self.trash, np.int32)
        self.set_slot(b, row, 1)

    def rollback_slot(self, b, post_len):
        """Speculative-verify rewind: the dispatch advanced the segment
        optimistically to cover the whole drafted span; after the
        on-device verify resolves, rejected positions may leave the
        slot shorter than advertised. Shrink the segment back to cover
        exactly `post_len` written tokens (the kept prefix) — the
        inverse of `advance_slot`, rebuilt from the stored table row so
        first/last/valid return to what a never-speculated slot of
        that length would carry."""
        self.set_slot(b, self._tables[b], post_len)

    def advance_slot(self, b, post_len):
        """ctx grew by one: extend the segment only when the new length
        crosses into a fresh page — O(1) host work per decode step."""
        n = self._npages(post_len)
        cur = int(self._n[b])
        if n == cur:
            return
        lo = b * self.pps
        for j in range(cur, min(n, self.pps)):
            self.page_ids[lo + j] = self._tables[b, j]
            self.valid[lo + j] = 1
        self.last[lo + cur - 1] = 0
        self.last[lo + n - 1] = 1
        # re-point the segment's padding alias at the new last page
        self.page_ids[lo + n:lo + self.pps] = self._tables[b, n - 1]
        self._n[b] = n

    def meta(self):
        return {"seq": self.seq, "page": self.page_ids,
                "ordinal": self.ordinal, "first": self.first,
                "last": self.last, "valid": self.valid}


# ---------------------------------------------------------------------------
# Variable-query-length ("varq") variant — the MIXED prefill+decode
# kernel (cf. PAPERS.md "Ragged Paged Attention"): each batch slot
# carries a query span of length q_lens[b] >= 1 — a prefill CHUNK or a
# single decode token — attending causally over its paged KV pool
# pages. One compiled step therefore serves a batch mixing mid-prefill
# and mid-decode requests; chunked prefill (inference.
# ContinuousBatchingPredictor) and speculative verify both ride it.
#
# Span geometry: query i of slot b sits at absolute position
# kv_lens[b] - q_lens[b] + i (its K/V is already written at that
# position — the caller scatters the span's K/V into the pages first,
# see generation/kv_cache.paged_cache_mixed_update_attend). Queries
# are padded to the compile-time span bucket Qb; padding rows (i >=
# q_lens[b]) are zeroed in the output. For q_lens == 1 everywhere the
# math degenerates to exactly the decode kernels above.
# ---------------------------------------------------------------------------

def _paged_attention_varq_xla(q, k_pages, v_pages, block_tables, kv_lens,
                              q_lens, scale):
    """XLA block-table path of the mixed step and the speculative
    verify (any GQA ratio; `_gathered_group_attention` with a causal
    span mask). q: [B, Qb, H, D]; pages [P, page, Hkv, D];
    block_tables [B, pages_per_seq]; kv_lens [B] total keys per slot
    (span included); q_lens [B] span lengths. Returns [B, Qb, H, D]
    with padding query rows zeroed."""
    qb = q.shape[1]
    kl = jnp.asarray(kv_lens, jnp.int32)[:, None, None]
    ql = jnp.asarray(q_lens, jnp.int32)[:, None, None]
    tok = jnp.arange(block_tables.shape[1] * k_pages.shape[1],
                     dtype=jnp.int32)[None, None, :]
    qi = jnp.arange(qb, dtype=jnp.int32)[None, :, None]
    ok = (tok <= (kl - ql) + qi) & (tok < kl)                # [B, Qb, L]
    out = _gathered_group_attention(q, k_pages, v_pages, block_tables, ok,
                                    scale)
    return jnp.where((qi < ql)[..., None], out, 0)


def paged_attention_varq(q, k_pages, v_pages, block_tables, kv_lens,
                         q_lens, scale=None):
    """Mixed-step attention via block tables (XLA path — the numeric
    oracle and the route for geometries the Pallas kernel rejects).
    See `paged_attention_ragged_varq` for the ragged-grid kernel."""
    d = q.shape[-1]
    sc = scale if scale is not None else 1.0 / pymath.sqrt(d)
    return _paged_attention_varq_xla(q, k_pages, v_pages, block_tables,
                                     kv_lens, q_lens, sc)


# The span is processed VARQ_Q_CHUNK queries at a time: the per-page
# score and P.V products are (page, chunk, H, D) f32 intermediates, and
# at Llama-2-7B widths (32 x 128) a whole 64-query span makes them
# 16 MiB each — the TPU compiler refused the kernel for exceeding the
# 16 MiB scoped VMEM limit (jax 0.9.0 / libtpu 0.0.34, described
# v5e:2x2). Eight queries keep them at 2 MiB.
VARQ_Q_CHUNK = 8


def varq_vmem_bytes(qb, h, d, page, itemsize):
    """VMEM the ragged varq kernel needs for a `qb`-query span bucket,
    from its shapes: the three f32 accumulators (qb, h, 128|128|d), the
    double-buffered q/out blocks (qb, h, d) and k/v pages, and the two
    (page, chunk, h, d) f32 products of one chunk."""
    qc = min(qb, VARQ_Q_CHUNK)
    scratch = qb * h * (128 + 128 + d) * 4
    blocks = 2 * 2 * (qb + page) * h * d * itemsize
    products = 2 * page * qc * h * d * 4
    return scratch + blocks + products


def max_varq_span(h, d, page, itemsize):
    """Largest power-of-two span bucket whose varq kernel fits the
    scoped VMEM limit at this head geometry (0 = not even one query).
    The predictor bounds its chunk and speculative-verify buckets with
    this at construction; above it `paged_attention_ragged_varq`
    raises instead of compiling a kernel the chip would refuse."""
    qb = 0
    nxt = 1
    while varq_vmem_bytes(nxt, h, d, page, itemsize) <= _VMEM_SCOPED_BYTES:
        qb, nxt = nxt, nxt * 2
    return qb


def _ragged_varq_kernel(seq_ref, page_ref, ord_ref, first_ref, last_ref,
                        valid_ref, kvlen_ref, qlen_ref, q_ref, k_ref,
                        v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale,
                        page_size, q_chunk):
    g = pl.program_id(0)
    n_chunks = q_ref.shape[1] // q_chunk

    @pl.when(first_ref[g] == 1)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(valid_ref[g] == 1)
    def _compute():
        b = seq_ref[g]
        kl = kvlen_ref[b]
        ql = qlen_ref[b]
        k = k_ref[0].astype(jnp.float32)   # (page, H, D)
        v = v_ref[0].astype(jnp.float32)

        def chunk(c, carry):
            rows = pl.ds(pl.multiple_of(c * q_chunk, q_chunk), q_chunk)
            q = q_ref[0, rows].astype(jnp.float32)        # (Qc, H, D)
            s = jnp.sum(q[None, :, :, :] * k[:, None, :, :],
                        axis=-1) * np.float32(scale)      # (page, Qc, H)
            tok = ord_ref[g] * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            qpos = (kl - ql) + c * q_chunk + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            # keys causal to each span query AND inside the written
            # context; a span's ordinal-0 page always holds key 0, so
            # every real query row sees >= 1 valid key on its first
            # page (no exp(0) pollution of the online softmax)
            s = jnp.where((tok <= qpos) & (tok < kl), s, _NEG_INF)
            m_prev = m_scr[rows][:, :, 0]                 # (Qc, H)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new[None, :, :])            # (page, Qc, H)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_scr[rows][:, :, 0] * alpha + jnp.sum(p, axis=0)
            pv = jnp.sum(p[:, :, :, None] * v[:, None, :, :],
                         axis=0)                          # (Qc, H, D)
            acc_scr[rows] = acc_scr[rows] * alpha[:, :, None] + pv
            m_scr[rows] = jnp.broadcast_to(
                m_new[:, :, None], (q_chunk,) + m_scr.shape[1:])
            l_scr[rows] = jnp.broadcast_to(
                l_new[:, :, None], (q_chunk,) + l_scr.shape[1:])
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_chunks), chunk,
                          jnp.int32(0))

    @pl.when(last_ref[g] == 1)
    def _finalize():
        l = l_scr[:, :, 0]
        safe_l = jnp.where(l == np.float32(0.0), np.float32(1.0), l)
        o_ref[0] = (acc_scr[:] / safe_l[:, :, None]).astype(o_ref.dtype)


def _paged_attention_ragged_varq_pallas(q, k_pages, v_pages, kv_lens,
                                        q_lens, meta, scale,
                                        interpret=False):
    """`meta`: the six ragged arrays in _META_FIELDS order."""
    b, qb, h, d = q.shape
    page = k_pages.shape[1]
    G = int(meta[0].shape[0])
    q_chunk = next(c for c in range(min(qb, VARQ_Q_CHUNK), 0, -1)
                   if qb % c == 0)
    fit = max_varq_span(h, d, page, q.dtype.itemsize)
    if not interpret and qb > fit:
        raise ValueError(
            f"varq span bucket {qb} at {h} heads x {d} needs "
            f"{varq_vmem_bytes(qb, h, d, page, q.dtype.itemsize)} bytes "
            f"of VMEM, over the {_VMEM_SCOPED_BYTES} the TPU compiler "
            f"grants a kernel; the largest bucket that fits is {fit}")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, qb, h, d),
                         lambda g, sq, pg, od, fr, ls, va, kn, qn:
                         (sq[g], _Z, _Z, _Z)),
            pl.BlockSpec((1, page, h, d),
                         lambda g, sq, pg, od, fr, ls, va, kn, qn:
                         (pg[g], _Z, _Z, _Z)),
            pl.BlockSpec((1, page, h, d),
                         lambda g, sq, pg, od, fr, ls, va, kn, qn:
                         (pg[g], _Z, _Z, _Z)),
        ],
        out_specs=pl.BlockSpec(
            (1, qb, h, d),
            lambda g, sq, pg, od, fr, ls, va, kn, qn: (sq[g], _Z, _Z, _Z)),
        scratch_shapes=[
            pltpu.VMEM((qb, h, 128), jnp.float32),
            pltpu.VMEM((qb, h, 128), jnp.float32),
            pltpu.VMEM((qb, h, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_ragged_varq_kernel, scale=scale,
                               page_size=page, q_chunk=q_chunk)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, qb, h, d), q.dtype),
        interpret=interpret,
    )(*meta, kv_lens, q_lens, q, k_pages, v_pages)


def paged_attention_ragged_varq(q, k_pages, v_pages, kv_lens, q_lens,
                                meta, scale=None, interpret=False,
                                block_tables=None):
    """Ragged-grid mixed prefill+decode attention. q: [B, Qb, H, D];
    `meta` is the 6-array ragged metadata (build_ragged_meta /
    RaggedMetaBuilder) built for the POST-write kv_lens; kv_lens [B] =
    q_start + q_lens. Padding query rows and kv_lens == 0 slots produce
    zeros.

    Runs the Pallas kernel under the shared `_paged_gate` (H == Hkv,
    D % 128 == 0, H % 8 == 0, Mosaic dtype); a lost fast path falls
    back to the XLA reference — which needs `block_tables` — and is
    counted in ``kernels.pallas_fallbacks``."""
    d = q.shape[-1]
    sc = scale if scale is not None else 1.0 / pymath.sqrt(d)
    interpret = interpret or pallas_interpret()
    if _paged_gate("paged_attention_ragged_varq", q, k_pages,
                   v_pages, interpret):
        out = partitioned(
            lambda q_, k_, v_, kl, ql, *m:
            _paged_attention_ragged_varq_pallas(
                q_, k_, v_, kl, ql, m, sc, interpret=interpret),
            [_SPAN_HEADS, _PAGE_HEADS, _PAGE_HEADS] + [None] * 8,
            _SPAN_HEADS, q, k_pages, v_pages,
            jnp.asarray(kv_lens, jnp.int32), jnp.asarray(q_lens, jnp.int32),
            *[jnp.asarray(meta[f], jnp.int32) for f in _META_FIELDS])
        qb = q.shape[1]
        qvalid = jnp.arange(qb, dtype=jnp.int32)[None, :] \
            < jnp.asarray(q_lens, jnp.int32)[:, None]
        has = jnp.asarray(kv_lens, jnp.int32) > 0
        return jnp.where((qvalid & has[:, None])[:, :, None, None], out, 0)
    if block_tables is None:
        raise ValueError(
            "paged_attention_ragged_varq needs block_tables for the XLA "
            "fallback path (Pallas gate rejected this geometry)")
    return _paged_attention_varq_xla(q, k_pages, v_pages, block_tables,
                                     kv_lens, q_lens, sc)

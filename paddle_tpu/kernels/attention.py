"""Flash attention for TPU.

Reference parity: paddle/phi/kernels/gpu/flash_attn_kernel.cu (the
FlashAttention-2 CUDA binding used by paddle.nn.functional.
scaled_dot_product_attention / flash_attention). TPU-native design: a
Pallas kernel implementing blockwise online-softmax attention (the
flash-attention recurrence) tiled for the MXU: Q blocks stay resident in
VMEM while K/V blocks stream through; running max `m`, normalizer `l`
and the f32 accumulator live in VMEM scratch across the KV grid axis.

The backward pass recomputes attention blockwise (flash-style: no S×S
materialization) using the saved `lse` — expressed in XLA ops, which the
compiler fuses per-block; a dedicated Pallas backward kernel is a later
optimization.

Gradient plumbing goes through jax.custom_vjp so the kernel composes with
the eager tape AND jax.grad under jit.
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
                      pallas_dtype_ok, pallas_interpret, mxu_precision,
                      partitioned, shard_index)

# How a multi-device step splits the flash kernels' operands
# (_common.partitioned): batch over 'data', heads over 'model'.
_BSHD = ("batch", None, "heads", None)        # q/k/v/out/do [B, S, H, D]
_BHS = ("batch", "heads", None)               # lse [B, H, S]


def _zero_tail_rows(arr, blk_idx, block, limit):
    """Zero the rows of a loaded block that lie beyond `limit` (the array's
    true extent). Out-of-bounds block reads return unspecified padding —
    possibly NaN — and 0 * NaN = NaN inside a dot contraction, so masking
    the downstream math is NOT sufficient: the operand rows themselves must
    be zeroed."""
    if limit % block == 0:
        return arr
    ids = blk_idx * block + jax.lax.broadcasted_iota(
        jnp.int32, arr.shape, 0)
    return jnp.where(ids < limit, arr, 0)


def _lens_rows(kv_lens, bh):
    """Per-row (B*H) kv lengths as a [BH, 1, 128] i32 array. The singleton
    middle axis keeps the BLOCK's trailing two dims at (1, 128) — equal to
    the array dim / lane-divisible, which Mosaic's tiling check requires
    (a [BH, 128] layout with block (1, 128) fails it: 1 is neither a
    multiple of 8 nor equal to BH). The kernel reads lane 0."""
    per_b = jnp.asarray(kv_lens, jnp.int32)
    reps = bh // per_b.shape[0]
    per_row = jnp.repeat(per_b, reps)
    return jnp.broadcast_to(per_row[:, None, None], (bh, 1, 128))


def _gqa_kv_row(h, H, Hkv):
    """Map a flattened [B*H] query-head row index onto its [B*Hkv] kv row
    (GQA group folding). The fwd and bwd BlockSpec index maps MUST agree
    on this formula — single definition, used by both.

    Uses lax.div/rem with explicit i32 constants rather than `//`/`%`:
    with jax_enable_x64 on, jnp.floor_divide(tracer, python_int) bakes an
    int64->int32 convert_element_type into the index-map jaxpr, and
    Mosaic's scalar convert lowering recurses forever on it (observed on
    v5e). h is a non-negative grid index, so truncating div == floor."""
    if H == Hkv:
        return h
    if isinstance(h, (int, np.integer)):
        return (h // H) * Hkv + (h % H) // (H // Hkv)
    i32 = lambda n: jnp.asarray(n, jnp.int32)
    return (jax.lax.div(h, i32(H)) * i32(Hkv)
            + jax.lax.div(jax.lax.rem(h, i32(H)), i32(H // Hkv)))


def _pad_d_for_dtype(dtype, d):
    """Head-dim padding target: bf16/f16 operands must fill the 128-wide
    MXU lane dim for Mosaic's matmul legalization; f32 handles d=64 via
    implicit lane padding."""
    if dtype in (jnp.bfloat16, jnp.float16) and d % 128:
        return ((d + 127) // 128) * 128
    return d


def _fmix32(x):
    """murmur3 finalizer: avalanche mix of an i32 lane. Pure vector int
    ops (mul wraps two's-complement, logical shifts) — identical
    semantics under Mosaic, the Pallas interpreter, and plain XLA, so
    forward, backward and host-side tests regenerate the same bits."""
    m1 = jnp.int32(np.int32(np.uint32(0x85EBCA6B)))
    m2 = jnp.int32(np.int32(np.uint32(0xC2B2AE35)))
    # explicit i32 shift amounts: with jax_enable_x64 on, a bare python
    # literal traces as i64 and lax.shift_right_logical rejects the mix
    s16, s13 = jnp.int32(16), jnp.int32(13)
    x = x ^ jax.lax.shift_right_logical(x, s16)
    x = x * m1
    x = x ^ jax.lax.shift_right_logical(x, s13)
    x = x * m2
    x = x ^ jax.lax.shift_right_logical(x, s16)
    return x


def dropout_keep_mask(q_ids, k_ids, row, seed0, seed1, seq_q, seq_k,
                      dropout_p):
    """Counter-based attention-dropout keep mask (reference parity: the
    philox counter RNG of flash_attn_kernel.cu — same idea, cheaper
    hash). Element (row, q, k) is kept iff
    fmix32(fmix32(fmix32(row ^ s0) ^ q) ^ k ^ s1) >= p·2^32 in uint32
    order. The three coordinates are mixed as SEPARATE words (each
    < 2^31 on its own) rather than as one linearized counter, so the
    pattern never wraps/collides however large B·H·Sq·Sk gets, and it
    is independent of block sizes and grid iteration order — the
    backward kernels (and tests, on the host) regenerate the exact
    forward pattern. The uint32 compare is done in the signed domain
    (x ^ 0x80000000 preserves order) to avoid unsigned vector compares
    in Mosaic. seq_q/seq_k are unused (kept for call-site symmetry)."""
    del seq_q, seq_k
    i32 = lambda n: jnp.asarray(n, jnp.int32)
    x = _fmix32(i32(row) ^ i32(seed0))
    x = _fmix32(x ^ q_ids)
    x = _fmix32(x ^ k_ids ^ i32(seed1))
    thresh = np.uint32(min(0xFFFFFFFF, int(round(dropout_p * 4294967296.0))))
    sign = jnp.int32(np.int32(np.uint32(0x80000000)))
    t_signed = jnp.int32(np.int32(thresh ^ np.uint32(0x80000000)))
    return (x ^ sign) >= t_signed


def dropout_seeds(dropout_key):
    """Derive the (1, 1, 128) i32 seed array the kernels read (lanes
    0/1) from a jax PRNG key — the ONE definition shared by
    flash_attention_jax, the validator and the tests, so the in-kernel
    pattern and every oracle stay in lockstep."""
    s01 = jax.random.randint(
        dropout_key, (2,), jnp.iinfo(jnp.int32).min,
        jnp.iinfo(jnp.int32).max, dtype=jnp.int32)
    return (jnp.zeros((1, 1, 128), jnp.int32)
            .at[0, 0, 0].set(s01[0]).at[0, 0, 1].set(s01[1]))


def _mask_row(h, H, Bm, Hm):
    """Map a flattened [B*H] row index onto its row of the [Bm*Hm, Sq,
    Sk] attention-mask array (Bm ∈ {1, B}, Hm ∈ {1, H}): batch- and/or
    head-broadcast masks are tiled straight from HBM, never repeated.
    lax.div/rem with explicit i32 — see _gqa_kv_row for why."""
    if Bm == 1 and Hm == 1:
        return _Z
    if isinstance(h, (int, np.integer)):
        b, hh = h // H, h % H
        return (b if Bm > 1 else 0) * Hm + (hh if Hm > 1 else 0)
    i32 = lambda n: jnp.asarray(n, jnp.int32)
    b = jax.lax.div(h, i32(H))
    hh = jax.lax.rem(h, i32(H))
    row = b * i32(Hm) if Bm > 1 else i32(0)
    return row + hh if Hm > 1 else row


# ---------------------------------------------------------------------------
# Pallas forward kernel: works on [BH, S, D]
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, block_q, block_k, seq_q, seq_k,
                has_lens, has_mask=False, dropout_p=0.0):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    mask_ref = next(it) if has_mask else None
    lens_ref = next(it) if has_lens else None
    seed_ref = next(it) if dropout_p else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = it
    hrow = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    i = pl.program_id(1)

    def _compute():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bk, d)
        v = _zero_tail_rows(v_ref[0], j, block_k, seq_k)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=mxu_precision(q, k)) * np.float32(scale)
        if has_mask:
            # additive mask tile (bool masks are converted to additive
            # _NEG_INF outside); applied BEFORE the -inf clamp below so
            # NaN padding in tail mask blocks can't survive it
            s = s + mask_ref[0].astype(jnp.float32)

        q_ids = k_ids = None
        if causal or seq_k % block_k or has_lens or dropout_p:
            q_ids = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
        if causal or seq_k % block_k or has_lens:
            keep = k_ids < seq_k  # kv tail: padded columns must not
            if causal:           # enter the softmax denominator
                keep = jnp.logical_and(keep, q_ids >= k_ids)
            if has_lens:
                # varlen: this sequence's real kv length (padding tokens
                # beyond it are finite garbage — mask them out)
                keep = jnp.logical_and(keep, k_ids < lens_ref[0, 0, 0])
            s = jnp.where(keep, s, _NEG_INF)

        m_prev = m_scr[:, 0]  # (bq,)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        # the normalizer uses pre-dropout p: dropout applies to
        # softmax(S), i.e. AFTER normalization (flash_attn semantics)
        l_new = l_scr[:, 0] * alpha + jnp.sum(p, axis=1)
        if dropout_p:
            keep_d = dropout_keep_mask(
                q_ids, k_ids, hrow, seed_ref[0, 0, 0],
                seed_ref[0, 0, 1], seq_q, seq_k, dropout_p)
            p_acc = jnp.where(keep_d, p, 0.0) * np.float32(
                1.0 / (1.0 - dropout_p))
        else:
            p_acc = p
        acc_scr[:] = (acc_scr[:] * alpha[:, None] +
                      jax.lax.dot_general(
                          p_acc.astype(v.dtype), v,
                          (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32,
                          precision=mxu_precision(v)))
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    if causal:
        # skip fully-masked KV blocks (block start beyond the last q row)
        @pl.when(j * block_k <= (i + 1) * block_q - 1)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_scr[:, 0]
        safe_l = jnp.where(l == np.float32(0.0), np.float32(1.0), l)
        o_ref[0] = (acc_scr[:] / safe_l[:, None]).astype(o_ref.dtype)
        # lse is materialized with a 128-wide lane dim (TPU tiling needs
        # the last two block dims ≥ (8, 128)); caller slices lane 0.
        lse_ref[0] = (m_scr[:] + jnp.log(safe_l)[:, None]
                      ).astype(lse_ref.dtype)


def _flash_fwd_pallas(q, k, v, scale, causal, block_q=128, block_k=128,
                      n_heads=None, n_kv_heads=None, kv_lens=None,
                      mask3=None, mask_dims=(1, 1), seeds=None,
                      dropout_p=0.0):
    """q: [B*H, S, D]; k,v: [B*Hkv, S, D] → (out [B*H,S,D], lse [B*H,S]).

    Native GQA/MQA (reference: flash_attn_kernel.cu's num_heads_k <
    num_heads path): when Hkv < H the kv BlockSpec index maps fold the
    query head onto its kv group — kv shards are NEVER repeated in HBM.

    mask3 ([Bm*Hm, Sq, Sk] additive float, Bm/Hm given by mask_dims):
    broadcast masks are tiled from HBM without repetition. seeds
    ((1,1,128) i32, lanes 0/1) + dropout_p: in-kernel counter-hash
    attention dropout (see dropout_keep_mask).

    bf16/f16 with d % 128 != 0: Mosaic rejects the sub-lane-width bf16
    matmul operand ("Bad lhs type"), so D is zero-padded to the 128-lane
    boundary — the MXU processes 128 lanes either way, and zero K/Q
    columns do not change Q.Kt; padded V columns are sliced off."""
    bh, sq, d = q.shape
    d_pad = _pad_d_for_dtype(q.dtype, d)
    if d_pad != d:
        pad = [(0, 0), (0, 0), (0, d_pad - d)]
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
        out, lse = _flash_fwd_pallas(q, k, v, scale, causal, block_q,
                                     block_k, n_heads, n_kv_heads,
                                     kv_lens=kv_lens, mask3=mask3,
                                     mask_dims=mask_dims, seeds=seeds,
                                     dropout_p=dropout_p)
        return out[..., :d], lse
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    grid = (bh, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k))

    H = n_heads or 1
    Hkv = n_kv_heads or H

    def kv_index(h, i, j):
        return (_gqa_kv_row(h, H, Hkv), j, _Z)

    has_lens = kv_lens is not None
    has_mask = mask3 is not None
    Bm, Hm = mask_dims
    # masks broadcast over the query axis ([.., 1, Sk], e.g. key-padding
    # masks) are tiled as (1, 1, block_k) rows — never expanded to S×S
    # in HBM; the kernel's `s + mask` broadcasts the row
    mask_q1 = has_mask and mask3.shape[1] == 1 and sq > 1
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_q=sq, seq_k=sk, has_lens=has_lens,
        has_mask=has_mask, dropout_p=dropout_p)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, _Z)),
        pl.BlockSpec((1, block_k, d), kv_index),
        pl.BlockSpec((1, block_k, d), kv_index),
    ]
    args = [q, k, v]
    if has_mask:
        args.append(mask3)
        in_specs.append(pl.BlockSpec(
            (1, 1 if mask_q1 else block_q, block_k),
            lambda h, i, j: (_mask_row(h, H, Bm, Hm),
                             _Z if mask_q1 else i, j)))
    if has_lens:
        args.append(_lens_rows(kv_lens, bh))
        in_specs.append(
            pl.BlockSpec((1, 1, 128), lambda h, i, j: (h, _Z, _Z)))
    if dropout_p:
        args.append(seeds)
        in_specs.append(
            pl.BlockSpec((1, 1, 128), lambda h, i, j: (_Z, _Z, _Z)))

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, _Z)),
            pl.BlockSpec((1, block_q, 128), lambda h, i, j: (h, i, _Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
            pltpu.VMEM((block_q, d), jnp.float32),    # accumulator
        ],
        interpret=pallas_interpret(),
    )(*args)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# Pallas backward kernels (flash-attention-2 style: recompute P blockwise
# from the saved lse — no S×S tensor ever materializes in HBM).
# Reference parity: the bwd kernels of phi/kernels/gpu/flash_attn_kernel.cu
# (flash_attn_bwd); dk/dv accumulate over the q-block axis, dq over the
# kv-block axis, each in f32 VMEM scratch.
# ---------------------------------------------------------------------------

def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     *refs, scale, causal, block_q, block_k, seq_q, seq_k,
                     has_lens=False, has_mask=False, dropout_p=0.0):
    it = iter(refs)
    mask_ref = next(it) if has_mask else None
    lens_ref = next(it) if has_lens else None
    seed_ref = next(it) if dropout_p else None
    dk_ref, dv_ref, dk_scr, dv_scr = it
    hrow = pl.program_id(0)
    j = pl.program_id(1)   # kv block
    i = pl.program_id(2)   # q block (innermost: accumulation axis)
    ni = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        # tail blocks: out-of-bounds rows must be ZEROED, not just masked
        # downstream (0 * NaN-padding = NaN inside the dots)
        q = _zero_tail_rows(q_ref[0], i, block_q, seq_q
                            ).astype(jnp.float32)        # (bq, d)
        k = k_ref[0].astype(jnp.float32)                 # (bk, d)
        v = _zero_tail_rows(v_ref[0], j, block_k, seq_k
                            ).astype(jnp.float32)
        do = _zero_tail_rows(do_ref[0], i, block_q, seq_q
                             ).astype(jnp.float32)       # (bq, d)
        lse = lse_ref[0, 0]                  # (bq,)
        delta = delta_ref[0, 0]              # (bq,)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ) * np.float32(scale)
        if has_mask:
            s = s + mask_ref[0].astype(jnp.float32)
        q_ids = k_ids = None
        if (causal or seq_q % block_q or seq_k % block_k or has_lens
                or dropout_p):
            q_ids = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
        if causal or seq_q % block_q or seq_k % block_k or has_lens:
            # padded q rows (garbage lse/delta) and padded kv columns
            # must contribute nothing to dk/dv
            keep = jnp.logical_and(q_ids < seq_q, k_ids < seq_k)
            if causal:
                keep = jnp.logical_and(keep, q_ids >= k_ids)
            if has_lens:
                keep = jnp.logical_and(keep, k_ids < lens_ref[0, 0, 0])
            s = jnp.where(keep, s, _NEG_INF)
            p = jnp.where(keep, jnp.exp(s - lse[:, None]), 0.0)
        else:
            keep = None
            p = jnp.exp(s - lse[:, None])    # (bq, bk)
        if dropout_p:
            # regenerate the forward's exact keep pattern; with
            # O = (P∘D)V and D = keep/(1-p):
            #   dV = (P∘D)^T dO,  dS = P ∘ (dP_d∘D − delta)
            # (delta = rowsum(dO∘O) stays valid: it equals
            # rowsum((P∘D) ∘ dP_d))
            keep_d = dropout_keep_mask(
                q_ids, k_ids, hrow, seed_ref[0, 0, 0],
                seed_ref[0, 0, 1], seq_q, seq_k, dropout_p)
            dmul = jnp.where(keep_d, np.float32(1.0 / (1.0 - dropout_p)),
                             np.float32(0.0))
            pd = p * dmul
        else:
            dmul = None
            pd = p
        # dv += (p∘D)^T do
        dv_scr[:] += jax.lax.dot_general(
            pd, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dmul is not None:
            dp = dp * dmul
        ds = p * (dp - delta[:, None]) * np.float32(scale)
        if keep is not None:
            # guard against NaN/Inf garbage in out-of-bounds lse/delta
            # tail reads: 0 * inf would poison the accumulators
            ds = jnp.where(keep, ds, 0.0)
        # dk += ds^T q
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # q block overlaps the causal triangle of this kv block
        @pl.when((i + 1) * block_q - 1 >= j * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(i == ni - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *refs, scale, causal, block_q, block_k,
                   seq_q, seq_k, has_lens=False, has_mask=False,
                   dropout_p=0.0):
    it = iter(refs)
    mask_ref = next(it) if has_mask else None
    lens_ref = next(it) if has_lens else None
    seed_ref = next(it) if dropout_p else None
    dq_ref, dq_scr = it
    hrow = pl.program_id(0)
    i = pl.program_id(1)   # q block
    j = pl.program_id(2)   # kv block (innermost: accumulation axis)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = _zero_tail_rows(k_ref[0], j, block_k, seq_k
                            ).astype(jnp.float32)
        v = _zero_tail_rows(v_ref[0], j, block_k, seq_k
                            ).astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ) * np.float32(scale)
        if has_mask:
            s = s + mask_ref[0].astype(jnp.float32)
        keep = q_ids = k_ids = None
        if causal or seq_k % block_k or has_lens or dropout_p:
            q_ids = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
        if causal or seq_k % block_k or has_lens:
            # kv-tail columns must not contribute to dq; q-tail rows
            # compute garbage but their dq writes land out of bounds
            # and are dropped
            keep = k_ids < seq_k
            if causal:
                keep = jnp.logical_and(keep, q_ids >= k_ids)
            if has_lens:
                keep = jnp.logical_and(keep, k_ids < lens_ref[0, 0, 0])
            s = jnp.where(keep, s, _NEG_INF)
        p = (jnp.where(keep, jnp.exp(s - lse[:, None]), 0.0)
             if keep is not None else jnp.exp(s - lse[:, None]))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_p:
            # dS = P ∘ (dP_d∘D − delta); see _bwd_dkdv_kernel
            keep_d = dropout_keep_mask(
                q_ids, k_ids, hrow, seed_ref[0, 0, 0],
                seed_ref[0, 0, 1], seq_q, seq_k, dropout_p)
            dp = dp * jnp.where(keep_d,
                                np.float32(1.0 / (1.0 - dropout_p)),
                                np.float32(0.0))
        ds = p * (dp - delta[:, None]) * np.float32(scale)
        if keep is not None:
            ds = jnp.where(keep, ds, 0.0)
        # dq += ds k
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(j * block_k <= (i + 1) * block_q - 1)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(j == nj - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, scale, causal,
                      block_q=128, block_k=128, n_heads=None,
                      n_kv_heads=None, kv_lens=None, mask3=None,
                      mask_dims=(1, 1), seeds=None, dropout_p=0.0):
    """q,o,do: [B*H, S, D]; k,v: [B*Hkv, S, D]; lse: [B*H, S] (f32).
    Returns dq [B*H,...], dk/dv [B*H,...] (per query head — group-sum for
    GQA). mask3/seeds/dropout_p as in _flash_fwd_pallas — the dropout
    keep pattern is regenerated in-kernel from the same seeds."""
    bh, sq, d = q.shape
    d_pad = _pad_d_for_dtype(q.dtype, d)
    if d_pad != d:
        pad = [(0, 0), (0, 0), (0, d_pad - d)]
        q, k, v, o, do = (jnp.pad(a, pad) for a in (q, k, v, o, do))
        dq, dk, dv = _flash_bwd_pallas(q, k, v, o, lse, do, scale, causal,
                                       block_q, block_k, n_heads,
                                       n_kv_heads, kv_lens=kv_lens,
                                       mask3=mask3, mask_dims=mask_dims,
                                       seeds=seeds, dropout_p=dropout_p)
        return dq[..., :d], dk[..., :d], dv[..., :d]
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    # delta_i = rowsum(do * o): tiny elementwise+reduce, XLA fuses it.
    # lse/delta are carried as [BH, 1, S]: the singleton middle axis puts
    # the block's trailing dims at (1, block_q) with 1 == the array dim,
    # which Mosaic's (8, 128)-tiling check accepts ([BH, S] with block
    # (1, block_q) does not: 1 is neither 8-divisible nor equal to BH).
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]
    lse = lse[:, None, :]

    H = n_heads or 1
    Hkv = n_kv_heads or H

    def kv_in(h, a, b, kv_block):
        return (_gqa_kv_row(h, H, Hkv), kv_block, _Z)

    q_spec_i = pl.BlockSpec((1, block_q, d), lambda h, a, b: (h, b, _Z))
    k_in_j = pl.BlockSpec((1, block_k, d), lambda h, a, b: kv_in(h, a, b, a))
    k_out_j = pl.BlockSpec((1, block_k, d), lambda h, a, b: (h, a, _Z))
    row_i = pl.BlockSpec((1, 1, block_q), lambda h, a, b: (h, _Z, b))
    # GQA: dk/dv come out PER QUERY HEAD ([B*H, Sk, D]); the wrapper
    # group-sums them down to [B*Hkv, ...] — kv inputs are still never
    # repeated in HBM.
    has_lens = kv_lens is not None
    has_mask = mask3 is not None
    Bm, Hm = mask_dims
    mask_q1 = has_mask and mask3.shape[1] == 1 and sq > 1
    extra_args = []
    if has_mask:
        extra_args.append(mask3)
    if has_lens:
        extra_args.append(_lens_rows(kv_lens, bh))
    if dropout_p:
        extra_args.append(seeds)

    def extra_specs(q_blk, kv_blk):
        # q_blk/kv_blk pick which grid axis is the q/kv block index for
        # the mask tile ((h, a, b) -> logical (q block, kv block))
        sp = []
        if has_mask:
            sp.append(pl.BlockSpec(
                (1, 1 if mask_q1 else block_q, block_k),
                lambda h, a, b: (_mask_row(h, H, Bm, Hm),
                                 _Z if mask_q1 else (a, b)[q_blk],
                                 (a, b)[kv_blk])))
        if has_lens:
            sp.append(pl.BlockSpec((1, 1, 128),
                                   lambda h, a, b: (h, _Z, _Z)))
        if dropout_p:
            sp.append(pl.BlockSpec((1, 1, 128),
                                   lambda h, a, b: (_Z, _Z, _Z)))
        return sp

    dkdv_in = [q_spec_i, k_in_j, k_in_j, q_spec_i, row_i, row_i]
    # dkdv grid is (bh, kv block, q block): mask tile q index is axis b
    dkdv_in.extend(extra_specs(q_blk=1, kv_blk=0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          seq_q=sq, seq_k=sk, has_lens=has_lens,
                          has_mask=has_mask, dropout_p=dropout_p),
        grid=(bh, nk, nq),
        in_specs=dkdv_in,
        out_specs=[k_out_j, k_out_j],
        out_shape=[jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=pallas_interpret(),
    )(q, k, v, do, lse, delta, *extra_args)

    q_spec = pl.BlockSpec((1, block_q, d), lambda h, a, b: (h, a, _Z))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda h, a, b: kv_in(h, a, b, b))
    row_q = pl.BlockSpec((1, 1, block_q), lambda h, a, b: (h, _Z, a))
    dq_in = [q_spec, kv_spec, kv_spec, q_spec, row_q, row_q]
    # dq grid is (bh, q block, kv block)
    dq_in.extend(extra_specs(q_blk=0, kv_blk=1))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          seq_q=sq, seq_k=sk, has_lens=has_lens,
                          has_mask=has_mask, dropout_p=dropout_p),
        grid=(bh, nq, nk),
        in_specs=dq_in,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=pallas_interpret(),
    )(q, k, v, do, lse, delta, *extra_args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# XLA reference path (used on CPU, with masks/dropout, and as bwd recompute)
# ---------------------------------------------------------------------------

def _xla_attention(q, k, v, scale, causal, mask=None, dropout_p=0.0,
                   dropout_key=None):
    """q,k,v: [B, S, H, D] (paddle flash layout). GQA (fewer kv heads)
    handled by repeating kv — the Pallas path avoids the repeat."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    cdt = jnp.promote_types(q.dtype, jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=cdt) * jnp.asarray(scale, cdt)
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
        s = jnp.where(qi >= ki, s, _NEG_INF)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            s = jnp.where(mask, s, _NEG_INF)
        else:
            s = s + mask.astype(s.dtype)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=cdt).astype(q.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wrapper (pure jax level, [B,S,H,D] layout)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_core(q, k, v, scale, causal):
    return _flash_fwd(q, k, v, scale, causal)[0]


def _flash_blocks():
    """Autotune knobs (FLAGS_flash_block_q/_k) — static at trace time."""
    from ..framework.flags import flag_value
    return int(flag_value("flash_block_q")), \
        int(flag_value("flash_block_k"))


def _extras(b, h, kv_lens, mask3, mask_dims, seeds):
    """The optional kernel operands as (arrays, roles) for
    `partitioned`. The flattened [Bm*Hm, Sq', Sk] mask travels as 4-D so
    that a per-batch / per-head mask splits with q; it is flattened
    again inside the shard."""
    arrays, roles = [], []
    if kv_lens is not None:
        arrays.append(kv_lens)
        roles.append(("batch",))
    if mask3 is not None:
        Bm, Hm = mask_dims
        arrays.append(mask3.reshape(Bm, Hm, *mask3.shape[1:]))
        roles.append(("batch" if Bm == b else None,
                      "heads" if Hm == h else None, None, None))
    if seeds is not None:
        arrays.append(seeds)
        roles.append(None)
    return arrays, roles


def _take_extras(rest, has_lens, has_mask, has_seeds):
    """Inverse of `_extras` inside the shard: (kv_lens, mask3,
    mask_dims, seeds)."""
    it = iter(rest)
    lens = next(it) if has_lens else None
    m3, dims = None, (1, 1)
    if has_mask:
        m4 = next(it)
        dims = (m4.shape[0], m4.shape[1])
        m3 = m4.reshape(dims[0] * dims[1], *m4.shape[2:])
    seeds = next(it) if has_seeds else None
    if seeds is not None:
        idx = shard_index()
        if not isinstance(idx, int):     # python 0: the call is not split
            # every shard draws its own dropout stream (the in-kernel
            # hash keys on the LOCAL row); fwd and bwd shift alike
            seeds = seeds.at[0, 0, 0].add(idx.astype(seeds.dtype))
    return lens, m3, dims, seeds


def _fwd_pallas_bshd(q, k, v, scale, causal, kv_lens=None, mask3=None,
                     mask_dims=(1, 1), seeds=None, dropout_p=0.0):
    """[B,S,H,D]-layout marshalling around _flash_fwd_pallas, shared by
    every custom_vjp fwd: flatten heads, run the kernel, unflatten.
    Returns (out [B,S,H,D], lse [B,H,S])."""
    flags = (kv_lens is not None, mask3 is not None, seeds is not None)

    def local(q, k, v, *rest):
        lens, m3, dims, sd = _take_extras(rest, *flags)
        b, sq, h, d = q.shape
        hkv = k.shape[2]
        bq, bk = _flash_blocks()

        def to3(x, nh):
            return x.transpose(0, 2, 1, 3).reshape(b * nh, x.shape[1], d)
        out, lse = _flash_fwd_pallas(
            to3(q, h), to3(k, hkv), to3(v, hkv), scale, causal,
            block_q=bq, block_k=bk, n_heads=h, n_kv_heads=hkv,
            kv_lens=lens, mask3=m3, mask_dims=dims, seeds=sd,
            dropout_p=dropout_p)
        return (out.reshape(b, h, sq, d).transpose(0, 2, 1, 3),
                lse.reshape(b, h, sq))

    extra, roles = _extras(q.shape[0], q.shape[2], kv_lens, mask3,
                           mask_dims, seeds)
    return partitioned(local, [_BSHD, _BSHD, _BSHD] + roles,
                       [_BSHD, _BHS], q, k, v, *extra)


def _flash_fwd(q, k, v, scale, causal):
    out, lse = _fwd_pallas_bshd(q, k, v, scale, causal)
    return out, (q, k, v, out, lse)


def _bwd_pallas_bshd(q, k, v, out, lse, g, scale, causal, kv_lens=None,
                     mask3=None, mask_dims=(1, 1), seeds=None,
                     dropout_p=0.0):
    """[B,S,H,D]-layout marshalling around _flash_bwd_pallas, shared by
    every custom_vjp bwd: flatten heads, run the kernels, unflatten and
    group-sum dk/dv down to the kv heads (GQA)."""
    flags = (kv_lens is not None, mask3 is not None, seeds is not None)

    def local(q, k, v, out, lse, g, *rest):
        lens, m3, dims, sd = _take_extras(rest, *flags)
        b, sq, h, d = q.shape
        sk = k.shape[1]
        hkv = k.shape[2]

        def to3(x, s, nh):
            return x.transpose(0, 2, 1, 3).reshape(b * nh, s, d)
        bq, bk = _flash_blocks()
        dq3, dk3, dv3 = _flash_bwd_pallas(
            to3(q, sq, h), to3(k, sk, hkv), to3(v, sk, hkv),
            to3(out, sq, h), lse.reshape(b * h, sq),
            to3(g.astype(q.dtype), sq, h), scale, causal,
            block_q=bq, block_k=bk, n_heads=h, n_kv_heads=hkv,
            kv_lens=lens, mask3=m3, mask_dims=dims,
            seeds=sd, dropout_p=dropout_p)
        dq = dq3.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
        dk = dk3.reshape(b, hkv, h // hkv, sk, d).sum(2) \
            .transpose(0, 2, 1, 3)
        dv = dv3.reshape(b, hkv, h // hkv, sk, d).sum(2) \
            .transpose(0, 2, 1, 3)
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))

    extra, roles = _extras(q.shape[0], q.shape[2], kv_lens, mask3,
                           mask_dims, seeds)
    return partitioned(
        local, [_BSHD, _BSHD, _BSHD, _BSHD, _BHS, _BSHD] + roles,
        [_BSHD, _BSHD, _BSHD], q, k, v, out, lse, g, *extra)


def _flash_bwd(scale, causal, res, g):
    """Backward: Pallas flash-2 kernels when available (dk/dv and dq
    accumulated blockwise from the saved lse — no S×S materialization),
    else the XLA einsum recompute below."""
    q, k, v, out, lse = res
    d = q.shape[-1]
    if (_use_pallas() and pallas_dtype_ok(q, k, v, g)
            and q.shape[1] >= 8 and d % 64 == 0):
        return _bwd_pallas_bshd(q, k, v, out, lse, g, scale, causal)
    if k.shape[2] != q.shape[2]:
        # GQA fallback: repeat kv, compute per-q-head, group-sum at the end
        rep = q.shape[2] // k.shape[2]
        dq_, dk_, dv_ = _flash_bwd(
            scale, causal, (q, jnp.repeat(k, rep, axis=2),
                            jnp.repeat(v, rep, axis=2), out, lse), g)
        b_, sk_, h_, d_ = dk_.shape
        dk_ = dk_.reshape(b_, sk_, h_ // rep, rep, d_).sum(3)
        dv_ = dv_.reshape(b_, sk_, h_ // rep, rep, d_).sum(3)
        return dq_, dk_.astype(k.dtype), dv_.astype(v.dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * np.float32(scale)
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
        s = jnp.where(qi >= ki, s, _NEG_INF)
    p = jnp.exp(s - lse[..., None])  # recomputed softmax via saved lse
    gf = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, gf,
                    preferred_element_type=jnp.float32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", gf,
                    v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)  # (b, sq, h)
    ds = p * (dp - delta.transpose(0, 2, 1)[..., None]) * np.float32(scale)
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_core.defvjp(lambda q, k, v, scale, causal: _flash_fwd(q, k, v, scale, causal),
                   _flash_bwd)


# varlen core: per-sequence kv lengths ([B] i32) masked IN-KERNEL
# (reference parity: flash_attn varlen/cu_seqlens path for padded
# batches). kv_lens is a traced array arg; its cotangent is float0.

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_core_varlen(q, k, v, kv_lens, scale, causal):
    return _flash_fwd_varlen(q, k, v, kv_lens, scale, causal)[0]


def _flash_fwd_varlen(q, k, v, kv_lens, scale, causal):
    out, lse = _fwd_pallas_bshd(q, k, v, scale, causal, kv_lens=kv_lens)
    return out, (q, k, v, kv_lens, out, lse)


def _flash_bwd_varlen(scale, causal, res, g):
    q, k, v, kv_lens, out, lse = res
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    if (_use_pallas() and pallas_dtype_ok(q, k, v, g)
            and sq >= 8 and d % 64 == 0):
        dq, dk, dv = _bwd_pallas_bshd(q, k, v, out, lse, g, scale,
                                      causal, kv_lens=kv_lens)
    else:
        lens_mask = (jnp.arange(sk)[None, None, None, :]
                     < kv_lens[:, None, None, None])

        def ref(q, k, v):
            return _xla_attention(q, k, v, scale, causal, mask=lens_mask)

        _, pull = jax.vjp(ref, q, k, v)
        dq, dk, dv = pull(g.astype(q.dtype))
    z = np.zeros(kv_lens.shape, float0_dtype())
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), z)


def float0_dtype():
    return jax.dtypes.float0


_flash_core_varlen.defvjp(
    lambda q, k, v, kv_lens, scale, causal: _flash_fwd_varlen(
        q, k, v, kv_lens, scale, causal),
    _flash_bwd_varlen)


# general core: additive mask and/or in-kernel dropout (and optionally
# varlen lens) on the Pallas fast path (reference parity: the
# attn_mask + dropout arguments of flash_attn_kernel.cu, which upstream
# keeps on the fused kernel). NOTE mask gradients: like upstream's
# flash binding, this path does NOT produce a mask cotangent (zeros are
# returned) — flash_attention_bshd routes masks that require grad to
# the XLA path, where autodiff handles them.

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_core_gen(q, k, v, mask3, extras, scale, cfg):
    return _flash_fwd_gen(q, k, v, mask3, extras, scale, cfg)[0]


def _flash_fwd_gen(q, k, v, mask3, extras, scale, cfg):
    causal, dropout_p, Bm, Hm = cfg
    out, lse = _fwd_pallas_bshd(
        q, k, v, scale, causal, kv_lens=extras.get("kv_lens"),
        mask3=mask3, mask_dims=(Bm, Hm), seeds=extras.get("seeds"),
        dropout_p=dropout_p)
    return out, (q, k, v, mask3, extras, out, lse)


def _gen_reference(q, k, v, mask3, kv_lens, seeds, scale, causal,
                   dropout_p, Bm, Hm):
    """XLA reference with the general core's EXACT semantics, including
    the counter-hash dropout pattern — used as the non-Pallas bwd
    fallback and by tests as the parity oracle."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if k.shape[2] != h:
        rep = h // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * np.float32(scale)
    if mask3 is not None:
        # mask3's q axis may be a broadcast singleton (key-padding masks)
        s = s + mask3.reshape(Bm, Hm, mask3.shape[1],
                              mask3.shape[2]).astype(jnp.float32)
    qi = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    if causal:
        s = jnp.where(qi >= ki, s, _NEG_INF)
    if kv_lens is not None:
        lens_keep = (jnp.arange(sk)[None, None, None, :]
                     < kv_lens[:, None, None, None])
        s = jnp.where(lens_keep, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_p:
        rows = jnp.arange(b * h, dtype=jnp.int32).reshape(b, h, 1, 1)
        keep = dropout_keep_mask(qi[None, None], ki[None, None], rows,
                                 seeds[0, 0, 0], seeds[0, 0, 1],
                                 sq, sk, dropout_p)
        p = jnp.where(keep, p, 0.0) * np.float32(1.0 / (1.0 - dropout_p))
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _flash_bwd_gen(scale, cfg, res, g):
    causal, dropout_p, Bm, Hm = cfg
    q, k, v, mask3, extras, out, lse = res
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    kv_lens = extras.get("kv_lens")
    seeds = extras.get("seeds")
    if (_use_pallas() and pallas_dtype_ok(q, k, v, g)
            and sq >= 8 and d % 64 == 0):
        dq, dk, dv = _bwd_pallas_bshd(q, k, v, out, lse, g, scale,
                                      causal, kv_lens=kv_lens,
                                      mask3=mask3, mask_dims=(Bm, Hm),
                                      seeds=seeds, dropout_p=dropout_p)
    else:
        def ref(q_, k_, v_):
            return _gen_reference(q_, k_, v_, mask3, kv_lens, seeds,
                                  scale, causal, dropout_p, Bm, Hm)
        _, pull = jax.vjp(ref, q, k, v)
        dq, dk, dv = pull(g.astype(q.dtype))
    dmask = None if mask3 is None else jnp.zeros_like(mask3)
    dex = {}
    if kv_lens is not None:
        dex["kv_lens"] = np.zeros(kv_lens.shape, float0_dtype())
    if seeds is not None:
        dex["seeds"] = np.zeros(seeds.shape, float0_dtype())
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dmask, dex)


_flash_core_gen.defvjp(_flash_fwd_gen, _flash_bwd_gen)


def flash_attention_jax(query, key, value, *, causal=False, scale=None,
                        mask=None, dropout_p=0.0, dropout_key=None,
                        kv_lens=None, allow_pallas_mask=True):
    """Pure-jax entry ([B,S,H,D] arrays). Chooses Pallas vs XLA.

    kv_lens ([B] i32): per-sequence valid kv length for padded batches —
    masked inside the Pallas kernels (varlen parity, no S x S mask
    tensor).

    Masks (bool or additive float, [Bm, Hm, Sq', Sk'] with Bm∈{1,B},
    Hm∈{1,H}, singleton Sq'/Sk' broadcast) and dropout stay on the
    Pallas fast path: masks as blockwise additive tiles, dropout via the
    in-kernel counter hash. allow_pallas_mask=False forces masked calls
    to the XLA path (used when the mask itself needs gradients — the
    fast path, like upstream's flash binding, doesn't produce them)."""
    d = query.shape[-1]
    sc = scale if scale is not None else 1.0 / pymath.sqrt(d)
    b, sq = query.shape[0], query.shape[1]
    h = query.shape[2]
    sk = key.shape[1]
    # d only needs to be a multiple of 64: the kernel's block last-dim
    # equals the full array dim, which TPU tiling always accepts (lanes
    # are padded to 128 internally for d=64 — still beats XLA attention)
    base = (_use_pallas() and pallas_dtype_ok(query, key, value)
            and sq >= 8 and d % 64 == 0 and h % key.shape[2] == 0)
    if kv_lens is not None:
        kv_lens = jnp.asarray(kv_lens, jnp.int32)
    # dropout is active only when a key was supplied (training mode)
    eff_drop = float(dropout_p) if dropout_key is not None else 0.0
    mask_fast_ok = (
        mask is None
        or (allow_pallas_mask and mask.ndim == 4
            and mask.shape[0] in (1, b) and mask.shape[1] in (1, h)
            and mask.shape[2] in (1, sq) and mask.shape[3] in (1, sk)))

    if base and mask is None and eff_drop == 0.0:
        if kv_lens is not None:
            return _flash_core_varlen(query, key, value, kv_lens, sc,
                                      causal)
        return _flash_core(query, key, value, sc, causal)

    if base and mask_fast_ok and eff_drop < 1.0:
        mask3, dims = None, (1, 1)
        if mask is not None:
            m = mask
            if m.dtype == jnp.bool_:
                m = jnp.where(m, np.float32(0.0), _NEG_INF)
            if m.shape[3] != sk:
                m = jnp.broadcast_to(m, m.shape[:3] + (sk,))
            # a singleton q axis stays singleton: the kernels tile it as
            # (1, block_k) rows instead of materializing S×S in HBM
            dims = (m.shape[0], m.shape[1])
            mask3 = m.reshape(dims[0] * dims[1], m.shape[2], sk)
        extras = {}
        if kv_lens is not None:
            extras["kv_lens"] = kv_lens
        if eff_drop > 0.0:
            extras["seeds"] = dropout_seeds(dropout_key)
        cfg = (bool(causal), float(eff_drop), dims[0], dims[1])
        return _flash_core_gen(query, key, value, mask3, extras, sc, cfg)

    if kv_lens is not None:
        lens_mask = (jnp.arange(sk)[None, None, None, :]
                     < kv_lens[:, None, None, None])
        m2 = lens_mask if mask is None else (
            jnp.logical_and(lens_mask, mask) if mask.dtype == jnp.bool_
            else mask + jnp.where(lens_mask, np.float32(0.0), _NEG_INF))
        return _xla_attention(query, key, value, sc, causal, mask=m2,
                              dropout_p=dropout_p, dropout_key=dropout_key)
    return _xla_attention(query, key, value, sc, causal, mask=mask,
                          dropout_p=dropout_p, dropout_key=dropout_key)


# ---------------------------------------------------------------------------
# Tensor-level API (tape-aware)
# ---------------------------------------------------------------------------

def flash_attention_bshd(query, key, value, attn_mask=None, dropout_p=0.0,
                         is_causal=False, training=True, scale=None,
                         kv_lens=None):
    """paddle scaled_dot_product_attention parity: [B, S, H, D] in/out.
    kv_lens ([B] ints): varlen padded-batch support, masked in-kernel."""
    from ..ops._dispatch import apply
    from ..ops.creation import _coerce
    from ..framework.random import next_key

    args = [_coerce(query), _coerce(key), _coerce(value)]
    has_mask = attn_mask is not None
    # the Pallas fast path doesn't produce mask gradients (upstream
    # flash_attn parity) — a mask that REQUIRES grad (e.g. a learned
    # relative-position bias) goes to the XLA path where autodiff
    # differentiates it
    mask_no_grad = True
    if has_mask:
        args.append(_coerce(attn_mask))
        mask_no_grad = bool(getattr(attn_mask, "stop_gradient", True))
    has_lens = kv_lens is not None
    if has_lens:
        args.append(_coerce(kv_lens))
    key_drop = next_key() if (dropout_p > 0.0 and training) else None

    def fn(q, k, v, *rest):
        it = iter(rest)
        m = next(it) if has_mask else None
        lens = next(it) if has_lens else None
        return flash_attention_jax(
            q, k, v, causal=is_causal, scale=scale,
            mask=m, kv_lens=lens,
            dropout_p=dropout_p if training else 0.0,
            dropout_key=key_drop,
            allow_pallas_mask=mask_no_grad)
    return apply(fn, *args, _name="flash_attention")


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """paddle.nn.functional.flash_attention.flash_attention parity."""
    out = flash_attention_bshd(query, key, value, dropout_p=dropout,
                               is_causal=causal, training=training)
    return out, None

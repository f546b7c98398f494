"""Ring attention (context parallelism) + Ulysses sequence parallelism.

Reference parity: the "sep" (segment parallel) mesh dimension in
fleet/base/topology.py plus the PaddleNLP ecosystem implementations
(llm ring_flash_attention.py `RingFlashAttention` — K/V blocks rotated
around the sep group over p2p send/recv with online-softmax accumulation;
Ulysses = head-scatter/seq-gather alltoall around attention built on
paddle.distributed.alltoall).

TPU-native design (SURVEY.md §5.7): the sep group IS the mesh 'context'
axis. Ring attention is a `shard_map` over that axis; K/V shards rotate
via `lax.ppermute` inside a `lax.scan`, accumulating with the blockwise
(flash) online-softmax recurrence in f32. The scan is reverse-mode
differentiable, so the backward pass is the transposed ring (XLA derives
it) — no hand-written p2p. Collectives ride ICI; compute of step t
overlaps the permute of step t+1 under XLA's latency-hiding scheduler.

Ulysses is two `lax.all_to_all`s: seq-sharded -> head-sharded, local full
(flash) attention, then back. Both paths degrade to plain flash attention
when the context axis has size 1.
"""
from __future__ import annotations

import math as pymath
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..distributed.mesh import get_mesh, axis_size

_NEG_INF = -1e30


def _inside_manual(axis_name):
    """True when tracing inside a shard_map that already manualizes
    axis_name (values are local shards; collectives over it are legal)."""
    ctx = jax.sharding.get_abstract_mesh()
    return not ctx.empty and axis_name in set(ctx.manual_axes)


def _pvary(x, axis_name):
    """Mark x device-varying over every currently-manual mesh axis
    (vma typing). check_vma=True needs every lax.cond branch / scan
    carry to agree on vma; the online-softmax init states start out
    replicated, while the q/k/v they merge with vary over axis_name AND
    any outer shard_map's manual axes (e.g. the pipeline 'stage')."""
    from ..framework.jax_compat import pcast
    axes = {axis_name}
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty:
        axes |= set(ctx.manual_axes)
    return pcast(x, tuple(sorted(axes)), to="varying")


def _shard_map(fn, mesh, in_specs, out_specs, axis_name):
    # Nesting: when called from inside another shard_map (e.g. the
    # pipeline engine's stage body, manual over 'stage'), the inner
    # shard_map must be built against the CONTEXT abstract mesh — whose
    # already-manual axes are typed Manual — not the concrete mesh, and
    # must manualize ONLY its own axis so the outer axes stay auto.
    # check_vma=True is required for a correct transpose: with vma
    # checking off, the backward of the nested ring mis-placed psums and
    # produced silently wrong dq/dk/dv under an outer pipeline shard_map.
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty and ctx.manual_axes:
        mesh = ctx
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={axis_name},
                         check_vma=True)


def _sharded_attn(local_core, mesh, spec, q, k, v, kv_lens, lens_spec,
                  **core_kw):
    """One shard_map entry for all ring/Ulysses variants: builds the
    operand + in_specs lists (kv_lens optional) exactly once."""
    def local(q, k, v, *rest):
        return local_core(q, k, v, rest[0] if rest else None, **core_kw)

    args = [q, k, v]
    in_specs = [spec, spec, spec]
    if kv_lens is not None:
        args.append(jnp.asarray(kv_lens, jnp.int32))
        in_specs.append(lens_spec)
    return _shard_map(local, mesh, tuple(in_specs), spec,
                      core_kw["axis_name"])(*args)



# ---------------------------------------------------------------------------
# Ring attention core (runs INSIDE shard_map; local shards [B, Sl, H, D])
# ---------------------------------------------------------------------------

def _ring_attention_local_zigzag(q, k, v, kv_lens=None, *, axis_name,
                                 cp, scale):
    """Causal ring attention over the zig-zag layout: local shard = global
    chunks (idx, 2cp-1-idx). Each ring step processes the 2x2 sub-chunk
    grid, and a sub-block runs only when its q chunk is causally at-or-
    after its k chunk (lax.cond) — every rank executes the SAME expected
    work per step (~half the sub-blocks), removing the last-rank
    serialization of the contiguous layout. Reference role:
    zig-zag/striped ring attention (llama-3 style load balancing)."""
    b, sl, h, d = q.shape
    half = sl // 2
    idx = lax.axis_index(axis_name)
    qf = q.astype(jnp.float32)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    a_half = jnp.arange(half, dtype=jnp.int32)

    def sub_update(qh, q_pos, m, l, acc, k_sub, v_sub, k_pos):
        s = jnp.einsum("bqhd,bkhd->bhqk", qh, k_sub.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, _NEG_INF)
        if kv_lens is not None:
            s = jnp.where(k_pos[None, None, None, :]
                          < kv_lens[:, None, None, None], s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_sub.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        return m_new, l_new, acc_new

    def process_block(k_blk, v_blk, src, ms, ls, accs):
        """ms/ls/accs: per-q-half state tuples."""
        cq = (idx, 2 * cp - 1 - idx)
        ck = (src, 2 * cp - 1 - src)
        new_m, new_l, new_acc = list(ms), list(ls), list(accs)
        for qi in range(2):
            qh = qf[:, qi * half:(qi + 1) * half]
            q_pos = cq[qi] * half + a_half
            for ki in range(2):
                k_sub = k_blk[:, ki * half:(ki + 1) * half]
                v_sub = v_blk[:, ki * half:(ki + 1) * half]
                k_pos = ck[ki] * half + a_half

                def run(ops, qh=qh, q_pos=q_pos, k_sub=k_sub,
                        v_sub=v_sub, k_pos=k_pos):
                    return sub_update(qh, q_pos, ops[0], ops[1], ops[2],
                                      k_sub, v_sub, k_pos)

                new_m[qi], new_l[qi], new_acc[qi] = lax.cond(
                    cq[qi] >= ck[ki], run,
                    lambda ops: (ops[0], ops[1], ops[2]),
                    (new_m[qi], new_l[qi], new_acc[qi]))
        return tuple(new_m), tuple(new_l), tuple(new_acc)

    m0 = tuple(_pvary(jnp.full((b, h, half), _NEG_INF, jnp.float32),
                      axis_name) for _ in range(2))
    l0 = tuple(_pvary(jnp.zeros((b, h, half), jnp.float32), axis_name)
               for _ in range(2))
    acc0 = tuple(_pvary(jnp.zeros((b, half, h, d), jnp.float32), axis_name)
                 for _ in range(2))

    ms, ls, accs = process_block(k, v, idx, m0, l0, acc0)

    def step(carry, t):
        k_blk, v_blk, ms, ls, accs = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        src = (idx - t) % cp
        ms, ls, accs = process_block(k_blk, v_blk, src, ms, ls, accs)
        return (k_blk, v_blk, ms, ls, accs), None

    if cp > 1:
        (_, _, ms, ls, accs), _ = lax.scan(
            step, (k, v, ms, ls, accs), jnp.arange(1, cp))
    outs = []
    for qi in range(2):
        safe_l = jnp.where(ls[qi] == 0.0, 1.0, ls[qi])
        outs.append(accs[qi] / safe_l.transpose(0, 2, 1)[..., None])
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def _ring_attention_local(q, k, v, kv_lens=None, *, axis_name, cp,
                          causal, scale):
    """Blockwise online-softmax attention with the K/V shard rotating
    around the `axis_name` ring (contiguous sequence layout; the causal
    zig-zag layout has its own kernel above). All accumulation in f32.
    The local block is consumed before the scan so only cp-1 ppermutes
    are issued (a permute whose result is never read still costs ICI
    traffic — XLA cannot DCE a collective out of a shared scan body)."""
    b, sl, h, d = q.shape
    idx = lax.axis_index(axis_name)
    qf = q.astype(jnp.float32)

    m0 = _pvary(jnp.full((b, h, sl), _NEG_INF, jnp.float32), axis_name)
    l0 = _pvary(jnp.zeros((b, h, sl), jnp.float32), axis_name)
    acc0 = _pvary(jnp.zeros((b, sl, h, d), jnp.float32), axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    q_pos = idx * sl + jnp.arange(sl, dtype=jnp.int32)

    def accumulate(k_blk, v_blk, m, l, acc, src):
        """One online-softmax update against the block originating at
        ring rank `src`."""
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        k_pos = src * k.shape[1] + jnp.arange(k.shape[1], dtype=jnp.int32)
        if causal:
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, _NEG_INF)
        if kv_lens is not None:
            # varlen padded batch: keys at-or-past a row's true length
            # never enter the softmax (global positions, so the mask is
            # exact regardless of which ring rank holds the block)
            s = jnp.where(k_pos[None, None, None, :]
                          < kv_lens[:, None, None, None], s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)  # (b, h, sl)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        return m_new, l_new, acc_new

    # step 0: this rank's own block, no communication
    m, l, acc = accumulate(k, v, m0, l0, acc0, idx)

    def step(carry, t):
        k_blk, v_blk, m, l, acc = carry
        # rotate first, then consume: after t rotations the block at this
        # rank originated at rank (idx - t) mod cp
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        src = (idx - t) % cp
        if causal:
            # contiguous layout: skip blocks entirely in the future
            # (src > idx) — a real HLO conditional, so early ranks save
            # the FLOPs; wall-clock is still bounded by the last rank
            # (the zig-zag kernel removes that bound).
            m, l, acc = lax.cond(
                src <= idx,
                lambda ops: accumulate(*ops, src),
                lambda ops: (ops[2], ops[3], ops[4]),
                (k_blk, v_blk, m, l, acc))
        else:
            m, l, acc = accumulate(k_blk, v_blk, m, l, acc, src)
        return (k_blk, v_blk, m, l, acc), None

    if cp > 1:
        (_, _, m, l, acc), _ = lax.scan(
            step, (k, v, m, l, acc), jnp.arange(1, cp))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = acc / safe_l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention_jax(query, key, value, *, causal=False, scale=None,
                       axis_name="context", mesh=None, zigzag=None,
                       kv_lens=None):
    """Pure-jax ring attention. [B, S, H, D] GLOBAL arrays; the sequence
    dim is sharded over `axis_name` by the shard_map. Falls back to plain
    flash attention when the axis is trivial.

    zigzag (default AUTO for causal): re-orders the sequence into the
    zig-zag chunk layout before the ring so causal work is balanced
    across ranks (outputs are inverse-permuted — semantics unchanged)."""
    mesh = mesh or get_mesh()
    cp = axis_size(axis_name, mesh)
    d = query.shape[-1]
    sc = scale if scale is not None else 1.0 / pymath.sqrt(d)
    if mesh is None or cp <= 1:
        from .attention import flash_attention_jax
        return flash_attention_jax(query, key, value, causal=causal,
                                   scale=sc, kv_lens=kv_lens)

    if _inside_manual(axis_name):
        # already inside a shard_map that is manual over axis_name (the
        # pipeline engine runs stage bodies with sequence-sharded
        # activations: manual over {'stage', 'context'}). q/k/v here ARE
        # the local contiguous-sequence shards — run the ring directly;
        # XLA cannot lower a nested manual computation over the same
        # mesh, and the layout is contiguous (no zig-zag pre-permute).
        if kv_lens is not None:
            kv_lens = jnp.asarray(kv_lens, jnp.int32)
        return _ring_attention_local(query, key, value, kv_lens,
                                     axis_name=axis_name, cp=cp,
                                     causal=causal, scale=sc)

    spec = P(None, axis_name, None, None)
    lens_spec = P(None)
    if kv_lens is not None:
        kv_lens = jnp.asarray(kv_lens, jnp.int32)
    S = query.shape[1]
    if zigzag is None:
        zigzag = causal and S % (2 * cp) == 0
    zigzag = bool(zigzag) and causal and S % (2 * cp) == 0

    if zigzag:
        chunk = S // (2 * cp)
        order = np.empty(2 * cp, np.int64)
        order[0::2] = np.arange(cp)
        order[1::2] = 2 * cp - 1 - np.arange(cp)
        inv = np.argsort(order)

        def permute(x, o):
            b, s = x.shape[0], x.shape[1]
            return x.reshape((b, 2 * cp, chunk) + x.shape[2:])[:, o] \
                    .reshape((b, s) + x.shape[2:])

        qz, kz, vz = (permute(x, order) for x in (query, key, value))
        # NOTE: zig-zag permutes SEQUENCE positions, but kv_lens masking
        # uses the pre-permutation global positions, which sub_update
        # reconstructs from chunk ids — so the mask stays exact

        out = _sharded_attn(_ring_attention_local_zigzag, mesh, spec,
                            qz, kz, vz, kv_lens, lens_spec,
                            axis_name=axis_name, cp=cp, scale=sc)
        return permute(out, inv)

    return _sharded_attn(_ring_attention_local, mesh, spec,
                         query, key, value, kv_lens, lens_spec,
                         axis_name=axis_name, cp=cp, causal=causal,
                         scale=sc)


# ---------------------------------------------------------------------------
# Ulysses (DeepSpeed-style) sequence parallelism: two all_to_alls
# ---------------------------------------------------------------------------

def _ulysses_local(q, k, v, kv_lens=None, *, axis_name, causal, scale):
    """Local shards [B, Sl, H, D] -> a2a -> full-seq [B, S, H/cp, D] ->
    attention -> a2a back."""
    def seq2head(x):
        # split heads over the axis, gather sequence
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def head2seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    from .attention import flash_attention_jax
    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    out = flash_attention_jax(qh, kh, vh, causal=causal, scale=scale,
                              kv_lens=kv_lens)
    return head2seq(out)


def ulysses_attention_jax(query, key, value, *, causal=False, scale=None,
                          axis_name="context", mesh=None, kv_lens=None):
    """Ulysses attention on GLOBAL [B, S, H, D] arrays (seq sharded over
    `axis_name` inside). Requires num_heads % cp == 0."""
    mesh = mesh or get_mesh()
    cp = axis_size(axis_name, mesh)
    d = query.shape[-1]
    sc = scale if scale is not None else 1.0 / pymath.sqrt(d)
    if mesh is None or cp <= 1:
        from .attention import flash_attention_jax
        return flash_attention_jax(query, key, value, causal=causal,
                                   scale=sc, kv_lens=kv_lens)
    if query.shape[2] % cp:
        raise ValueError(
            f"ulysses: num_heads {query.shape[2]} not divisible by "
            f"context-parallel degree {cp}")

    if _inside_manual(axis_name):
        if kv_lens is not None:
            kv_lens = jnp.asarray(kv_lens, jnp.int32)
        return _ulysses_local(query, key, value, kv_lens,
                              axis_name=axis_name, causal=causal, scale=sc)

    spec = P(None, axis_name, None, None)
    return _sharded_attn(_ulysses_local, mesh, spec, query, key, value,
                         kv_lens, P(None), axis_name=axis_name,
                         causal=causal, scale=sc)


# ---------------------------------------------------------------------------
# Tensor-level API (tape-aware) — PaddleNLP RingFlashAttention parity
# ---------------------------------------------------------------------------

def _tensor_entry(fn_jax, query, key, value, causal, scale, group,
                  kv_lens=None):
    from ..ops._dispatch import apply
    from ..ops.creation import _coerce

    axis_name = getattr(group, "axis", None) or "context"
    args = [_coerce(query), _coerce(key), _coerce(value)]
    if kv_lens is not None:
        args.append(_coerce(kv_lens))

    def fn(q, k, v, *rest):
        return fn_jax(q, k, v, causal=causal, scale=scale,
                      axis_name=axis_name,
                      kv_lens=rest[0] if rest else None)

    return apply(fn, *args, _name="ring_attention")


def _check_unsupported(attn_mask, dropout):
    if attn_mask is not None:
        raise NotImplementedError(
            "ring/Ulysses attention: arbitrary dense attn_mask tensors are "
            "not supported; use is_causal= for causal masking and "
            "kv_lens=[B] for varlen padded batches instead")
    if dropout:
        raise NotImplementedError(
            "ring/Ulysses attention does not support dropout yet; apply "
            "dropout on the attention output instead")


class RingFlashAttention:
    """PaddleNLP `RingFlashAttention.apply(q, k, v, group=...)` parity.
    Tensors are [B, S, H, D] with S the (logically global) sequence."""

    @staticmethod
    def apply(query, key, value, group=None, is_causal=True, scale=None,
              attn_mask=None, dropout=0.0, kv_lens=None):
        _check_unsupported(attn_mask, dropout)
        return _tensor_entry(ring_attention_jax, query, key, value,
                             is_causal, scale, group, kv_lens=kv_lens)


class UlyssesAttention:
    @staticmethod
    def apply(query, key, value, group=None, is_causal=True, scale=None,
              attn_mask=None, dropout=0.0, kv_lens=None):
        _check_unsupported(attn_mask, dropout)
        return _tensor_entry(ulysses_attention_jax, query, key, value,
                             is_causal, scale, group, kv_lens=kv_lens)


def ring_flash_attention(query, key, value, is_causal=True, scale=None,
                         group=None, kv_lens=None):
    return RingFlashAttention.apply(query, key, value, group=group,
                                    kv_lens=kv_lens,
                                    is_causal=is_causal, scale=scale)


def split_inputs_sequence_dim(inputs, rank=None, degree=None, axis=1):
    """Parity helper (PaddleNLP trainer): under single-controller SPMD the
    global batch stays whole; sharding over 'context' happens via specs, so
    this is an identity that validates divisibility."""
    degree = degree or axis_size("context")
    if degree > 1:
        shape = inputs.shape if hasattr(inputs, "shape") else None
        if shape is not None and shape[axis] % degree:
            raise ValueError(
                f"sequence length {shape[axis]} not divisible by sep degree "
                f"{degree}")
    return inputs

"""Bytes and operations of one decode step's attention over the
SELECTED keys in ONE layer with an attention indexer
(`paddle_tpu/kernels/paged_attention.py`, `paged_sparse_attention`): a
slot that holds c keys attends to min(c, topk) of them. The least any
exact form moves is the K and V rows of those selected keys, the queries
in and the outputs back. The form the program runs reads every LIVE
page and applies the selection as a mask on its scores, so at a context
of c it moves about c / min(c, topk) times these bytes: its share of
this roofline reads near topk / c, which is the headroom a form that
gathers rows would have to win back from its descriptors.
"""
from __future__ import annotations


def bytes_per_call(ctx_tokens, topk, kv_heads, head_dim, q_heads, itemsize):
    """`ctx_tokens`: keys held by each slot that carries a request."""
    selected = sum(min(c, topk) for c in ctx_tokens)
    kv = selected * kv_heads * head_dim * 2 * itemsize
    q_and_out = len(ctx_tokens) * q_heads * head_dim * 2 * itemsize
    return kv + q_and_out


def flops_per_call(ctx_tokens, topk, q_heads, head_dim):
    """QK^T and PV: 2 x 2 x heads x head_dim a selected key."""
    return 4 * q_heads * head_dim * sum(min(c, topk) for c in ctx_tokens)


def least_seconds(ctx_tokens, topk, kv_heads, head_dim, q_heads, itemsize,
                  peaks):
    b = bytes_per_call(ctx_tokens, topk, kv_heads, head_dim, q_heads,
                       itemsize)
    f = flops_per_call(ctx_tokens, topk, q_heads, head_dim)
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])

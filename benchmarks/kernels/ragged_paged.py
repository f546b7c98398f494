"""Bytes one call of the ragged paged-attention decode kernel has to
move (`kernels/paged_attention.py`, `_ragged_kernel`): the K and V of
every valid page of every active slot, the queries in and the outputs
back. One call serves one layer for one decode step. The kernel is
bound by memory, so its roofline is bytes over the HBM rate; its
operations (4 x heads x head_dim a cached token) stay far under the
compute roof and are given for completeness.
"""
from __future__ import annotations

import math


def bytes_per_call(ctx_tokens, page, kv_heads, head_dim, q_heads, itemsize):
    """`ctx_tokens`: cached tokens of each active slot in the step."""
    pages = sum(math.ceil(c / page) for c in ctx_tokens)
    kv = pages * page * kv_heads * head_dim * 2 * itemsize
    q_and_out = len(ctx_tokens) * q_heads * head_dim * 2 * itemsize
    return kv + q_and_out


def flops_per_call(ctx_tokens, q_heads, head_dim):
    """QK^T and PV: 2 x 2 x heads x head_dim a cached token."""
    return 4 * q_heads * head_dim * sum(ctx_tokens)


def least_seconds(ctx_tokens, page, kv_heads, head_dim, q_heads, itemsize,
                  peaks):
    b = bytes_per_call(ctx_tokens, page, kv_heads, head_dim, q_heads,
                       itemsize)
    f = flops_per_call(ctx_tokens, q_heads, head_dim)
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])

"""Bytes and operations of one decode step's attention in ONE latent-
attention layer, by the DEFINITION in absorbed form
(`paddle_tpu/kernels/latent_attention.py`), whatever implements it: for
every slot that carries a request, each of the H heads scores the
absorbed query `[q^ | q_rope]` (rank + rope numbers) against every live
row `[c | k_r]`, and sums the rows' first `rank` numbers under the
softmax. The least a step must move is the live rows ONCE (one row a
token for all heads, rank + rope numbers in the cache's type: the row is
key and value both), the queries in and the summed latents back.
2 H (rank + rope) + 2 H rank operations a live token against (rank +
rope) x itemsize bytes: 60 operations a byte at 32 heads in bfloat16,
under the chip's 240, so bound by memory.

The pool stores a row on whole 128-lane rows (576 numbers on 640 lanes),
so the kernel as built moves a ninth more; the count is the least any
exact form moves.
"""
from __future__ import annotations


def bytes_per_call(ctx_tokens, heads, rank, rope, itemsize):
    """`ctx_tokens`: rows held by each slot that carries a request."""
    rows = sum(ctx_tokens) * (rank + rope) * itemsize
    query = len(ctx_tokens) * heads * (rank + rope) * itemsize
    out = len(ctx_tokens) * heads * rank * itemsize
    return rows + query + out


def flops_per_call(ctx_tokens, heads, rank, rope):
    return sum(ctx_tokens) * heads * (2 * (rank + rope) + 2 * rank)


def least_seconds(ctx_tokens, heads, rank, rope, itemsize, peaks):
    b = bytes_per_call(ctx_tokens, heads, rank, rope, itemsize)
    f = flops_per_call(ctx_tokens, heads, rank, rope)
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])

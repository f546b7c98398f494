"""Bytes and operations of one decode step's attention over the SELECTED
rows in ONE latent-attention layer with an indexer, by the DEFINITION in
absorbed form (`paddle_tpu/kernels/latent_attention.py`,
`paged_sparse_latent_attention`), whatever implements it: a slot that
holds c rows attends to min(c, topk) of them; each of the H heads scores
the absorbed query `[q^ | q_rope]` (rank + rope numbers) against every
selected row `[c | k_r]` and sums the rows' first `rank` numbers under
the softmax. The least any exact form moves is the selected rows ONCE
(one row a token for all heads: key and value both), the queries in and
the summed latents back. 2 H (rank + rope) + 2 H rank operations a
selected row against (rank + rope) x itemsize bytes: 121 operations a
byte at 64 heads in bfloat16, under the chip's 240, so bound by memory.

The form the program runs reads every LIVE page and applies the
selection as a mask on its scores, so at a context of c it moves about
c / min(c, topk) times these bytes (and a ninth more: 576 numbers lie on
640 lanes): its share of this roofline reads near topk / c, which is the
headroom a form that gathers rows would have to win back from its
descriptors.
"""
from __future__ import annotations


def bytes_per_call(ctx_tokens, topk, heads, rank, rope, itemsize):
    """`ctx_tokens`: rows held by each slot that carries a request."""
    rows = sum(min(c, topk) for c in ctx_tokens) * (rank + rope) * itemsize
    query = len(ctx_tokens) * heads * (rank + rope) * itemsize
    out = len(ctx_tokens) * heads * rank * itemsize
    return rows + query + out


def flops_per_call(ctx_tokens, topk, heads, rank, rope):
    return sum(min(c, topk) for c in ctx_tokens) * heads \
        * (2 * (rank + rope) + 2 * rank)


def least_seconds(ctx_tokens, topk, heads, rank, rope, itemsize, peaks):
    b = bytes_per_call(ctx_tokens, topk, heads, rank, rope, itemsize)
    f = flops_per_call(ctx_tokens, topk, heads, rank, rope)
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])

"""Bytes and operations of one verify span's attention in ONE latent-
attention layer, by the DEFINITION in absorbed form, whatever implements
the span (`paddle_tpu/kernels/latent_attention.py` folds the span's
queries into the row axis; the same kernel called once a query would
read the rows `span` times and is held to the same count): for every
slot that carries a request, each of the `span` x H absorbed queries
`[q^ | q_rope]` (rank + rope numbers) scores every live row `[c | k_r]`
and sums the rows' first `rank` numbers under the softmax. The least a
call must move is the live rows ONCE (one row a token for all heads and
for the whole span: the row is key and value both), the queries in and
the summed latents back. 2 (rank + rope) + 2 rank operations a query
head a live token against (rank + rope) x itemsize bytes a token: 483
operations a byte at 2 x 128 heads in bfloat16, over the chip's 240, so
the span is bound by the MXU where the single-query step (60 an byte at
32 heads, `mla_decode.py`) is bound by memory.

`ctx_tokens` counts, for each occupied slot, the rows the span's LAST
query sees; the earlier queries see up to `span - 1` fewer, which the
count leaves in (under one row in a few hundred).
"""
from __future__ import annotations


def bytes_per_call(ctx_tokens, span, heads, rank, rope, itemsize):
    rows = sum(ctx_tokens) * (rank + rope) * itemsize
    query = len(ctx_tokens) * span * heads * (rank + rope) * itemsize
    out = len(ctx_tokens) * span * heads * rank * itemsize
    return rows + query + out


def flops_per_call(ctx_tokens, span, heads, rank, rope):
    return sum(ctx_tokens) * span * heads * (2 * (rank + rope) + 2 * rank)


def least_seconds(ctx_tokens, span, heads, rank, rope, itemsize, peaks):
    b = bytes_per_call(ctx_tokens, span, heads, rank, rope, itemsize)
    f = flops_per_call(ctx_tokens, span, heads, rank, rope)
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])

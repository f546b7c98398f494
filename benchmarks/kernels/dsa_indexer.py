"""Bytes and operations of one decode step's index scoring in ONE layer
with an attention indexer (`paddle_tpu/kernels/paged_attention.py`,
`paged_index_scores`): for every slot that carries a request and every
key s it holds, `I[s] = sum_j w[j] * relu(qI[j] . kI[s])` over the J
index heads. The least a step must move is the slot's live index keys,
read once (one key of `index_dim` a token, in the cache's type), the
query's J index vectors and J float32 weights in, and one float32 score
a key out. The scoring is bound by memory: 2 J `index_dim` + 3 J
operations a key against `index_dim` x itemsize + 4 bytes.

The pool stores a key on a whole 128-lane row (the TPU's tile: a
64-wide bfloat16 key occupies 256 B there, not 128), so the kernel as
built moves up to twice these bytes; the count is the least any exact
form moves, and the gap is headroom.
"""
from __future__ import annotations

SCORE_ITEMSIZE = 4      # index scores are float32


def bytes_per_call(ctx_tokens, index_heads, index_dim, itemsize):
    """`ctx_tokens`: keys held by each slot that carries a request."""
    keys = sum(ctx_tokens) * index_dim * itemsize
    query = len(ctx_tokens) * index_heads * (index_dim * itemsize + 4)
    scores = sum(ctx_tokens) * SCORE_ITEMSIZE
    return keys + query + scores


def flops_per_call(ctx_tokens, index_heads, index_dim):
    """J dot products of `index_dim`, a relu, a multiply and an add a
    head a key."""
    return sum(ctx_tokens) * index_heads * (2 * index_dim + 3)


def least_seconds(ctx_tokens, index_heads, index_dim, itemsize, peaks):
    b = bytes_per_call(ctx_tokens, index_heads, index_dim, itemsize)
    f = flops_per_call(ctx_tokens, index_heads, index_dim)
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])

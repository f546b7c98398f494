"""Operations and bytes of a prefill's attention under the indexer's
selection in ONE latent-attention layer, by the DEFINITION in
decompressed form (`paddle_tpu/models/glm_moe_dsa.py`), whichever form
runs: query t attends to its min(t + 1, topk) selected keys, and each of
the H heads spends 2 `qk_dim` operations on a score and 2 `v_dim` on the
weighted sum, a query a selected key. What must move is q, k, v in and o
out once, in the activations' type: thousands of operations a byte, so
bound by the MXU.

The form the program runs is a flash kernel over EVERY key up to a
chunk's last query with the selection as a mask, so at a prompt of L
tokens it computes about L / (2 topk) times these operations: its share
of this roofline reads near 2 topk / L of the MXU's use, which is the
headroom a form that gathers the selected keys would have.

`pairs` counts (query, selected key) pairs: `chunk_pairs` gives them for
what a trace shows of a prefill, its chunks of queries by kind.
"""
from __future__ import annotations


def pairs_of_prompt(tokens, topk):
    """sum over t < tokens of min(t + 1, topk)."""
    head = min(tokens, topk)
    return head * (head + 1) // 2 + max(0, tokens - topk) * topk


def chunk_pairs(dense_chunks, selected_chunks, chunk, topk):
    """A chunk under a selection holds `chunk` queries of `topk` keys
    each; the chunks before it (no query sees more than `topk` keys)
    are a prompt's first `topk` tokens, (topk + 1) / 2 keys a query on
    average."""
    return selected_chunks * chunk * topk \
        + dense_chunks * chunk * (topk + 1) / 2.0


def flops_per_call(pairs, heads, qk_dim, v_dim):
    return pairs * heads * (2 * qk_dim + 2 * v_dim)


def bytes_per_call(tokens, heads, qk_dim, v_dim, itemsize):
    return tokens * heads * (2 * qk_dim + 2 * v_dim) * itemsize


def least_seconds(pairs, tokens, heads, qk_dim, v_dim, itemsize, peaks):
    b = bytes_per_call(tokens, heads, qk_dim, v_dim, itemsize)
    f = flops_per_call(pairs, heads, qk_dim, v_dim)
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])

"""Bytes and operations of a prefill's delta-rule recurrence in ONE Kimi
Delta Attention layer, by the DEFINITION (`paddle_tpu/kernels/kda.py`,
`kda_sequential`), whatever implements it: the recurrence's seven
operations a state element a head a token (`kda_state_update.py` counts
them), with the state kept on the chip between tokens, so that what must
move is a token's q, k, v in and o out in the activations' type, its log
decay g a channel and its beta a head in float32. The chunked form the
program runs (`kda_chunked`) does other arithmetic (matmuls a chunk, a
triangular solve): its share of this count says how far it is from the
least any exact form needs, not how busy the MXU is.
"""
from __future__ import annotations

OPS_PER_ELEMENT = 7


def bytes_per_call(tokens, heads, d_k, d_v, itemsize):
    return tokens * heads * ((2 * d_k + 2 * d_v) * itemsize + d_k * 4 + 4)


def flops_per_call(tokens, heads, d_k, d_v):
    return OPS_PER_ELEMENT * tokens * heads * d_k * d_v


def least_seconds(tokens, heads, d_k, d_v, itemsize, peaks):
    b = bytes_per_call(tokens, heads, d_k, d_v, itemsize)
    f = flops_per_call(tokens, heads, d_k, d_v)
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])

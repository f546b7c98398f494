"""Bytes and operations of one decode step's state update in ONE Kimi
Delta Attention layer, by the DEFINITION (`paddle_tpu/kernels/kda.py`),
whatever implements it: for every slot that carries a request and every
head, `S' = diag(exp(g)) S`, `u = beta (v - S'^T k)`, `S = S' + k u^T`,
`o = S^T q` on a float32 state [d_k, d_v]. The least a step must move is
that state read once and written once, and the step's small tensors (q,
k, v in and o out in the activations' type; g a channel and beta a head
in float32). Seven operations a state element (the decay's multiply, the
multiply-add of `S'^T k`, the multiply-add of the outer product, the
multiply-add of the read-out) against eight bytes: bound by memory.
"""
from __future__ import annotations

STATE_ITEMSIZE = 4      # the state is float32 in the pool
OPS_PER_ELEMENT = 7


def state_bytes(slots, heads, d_k, d_v):
    return slots * heads * d_k * d_v * STATE_ITEMSIZE


def bytes_per_call(slots, heads, d_k, d_v, itemsize):
    """`slots`: the slots that carry a request in the step."""
    small = slots * heads * ((2 * d_k + 2 * d_v) * itemsize   # q, k, v, o
                             + d_k * 4 + 4)                   # g, beta
    return 2 * state_bytes(slots, heads, d_k, d_v) + small


def flops_per_call(slots, heads, d_k, d_v):
    return OPS_PER_ELEMENT * slots * heads * d_k * d_v


def least_seconds(slots, heads, d_k, d_v, itemsize, peaks):
    b = bytes_per_call(slots, heads, d_k, d_v, itemsize)
    f = flops_per_call(slots, heads, d_k, d_v)
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])

"""Bytes and operations of one decode step's SSM state update in ONE
Mamba-2 layer (`paddle_tpu/models/granite_hybrid.py`, `ssd_step`): for
every slot and head, `H = exp(dt A) H + (dt x) (x) B`, then `y = H C +
D x`. The least a step must move is the float32 state of the slots that
carry a request, read once and written once, and the step's small
tensors (x, B, C, dt in; y out) in the activations' type. The update is
bound by memory: about six operations a state element (the decay's
multiply, the outer product's two, the add, and the multiply-add of the
read-out) against eight bytes.
"""
from __future__ import annotations

STATE_ITEMSIZE = 4      # the SSM state is float32 in the pool


def state_bytes(slots, heads, head_dim, d_state):
    return slots * heads * head_dim * d_state * STATE_ITEMSIZE


def bytes_per_call(slots, heads, head_dim, d_state, groups, itemsize):
    """`slots`: the slots that carry a request in the step."""
    small = slots * (2 * heads * head_dim           # x in, y out
                     + 2 * groups * d_state) * itemsize \
        + slots * heads * 4                         # dt, float32
    return 2 * state_bytes(slots, heads, head_dim, d_state) + small


def flops_per_call(slots, heads, head_dim, d_state):
    return 6 * slots * heads * head_dim * d_state


def least_seconds(slots, heads, head_dim, d_state, groups, itemsize, peaks):
    b = bytes_per_call(slots, heads, head_dim, d_state, groups, itemsize)
    f = flops_per_call(slots, heads, head_dim, d_state)
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])

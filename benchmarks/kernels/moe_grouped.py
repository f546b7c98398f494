"""Bytes and operations of the two grouped expert matmuls of ONE layer
in one step (`paddle_tpu/incubate/distributed/models/moe/dropless.py`):
`up = xs W_in[e]` ([rows, hidden] x [hidden, 2 x width]) and `out =
act W_out[e]` ([rows, width] x [width, hidden]) over the rows routed to
the experts this chip holds.

The least a step must move: the weights of every held expert that at
least one token chose, once, and the rows' activations (the gathered
inputs in, `up` out, `act` in, `out` out). With few tokens a step, as in
decode, the weights dominate and the matmuls are bound by memory; with a
prompt's thousands of tokens they are bound by compute.

How many held experts a step touches is not counted by the program. It
is taken as its expectation when a token's `top_k` experts are `top_k`
distinct uniform draws of `experts`: a given expert is missed by a token
with probability 1 - top_k / experts, by all `tokens` with that to the
power `tokens`. A skewed router touches fewer, so the estimate of the
least time is from above where routing is uneven; the counter
`moe.expert_tokens` says how uneven it is.
"""
from __future__ import annotations


def experts_touched(tokens, held, experts, top_k):
    return held * (1.0 - (1.0 - top_k / experts) ** tokens)


def local_rows(tokens, held, experts, top_k):
    """Expected assignments that land on a held expert."""
    return tokens * top_k * held / experts


def bytes_per_layer(tokens, hidden, width, held, experts, top_k, itemsize):
    weights = experts_touched(tokens, held, experts, top_k) \
        * 3 * hidden * width * itemsize
    rows = local_rows(tokens, held, experts, top_k)
    activations = rows * (hidden + 2 * width + width + hidden) * itemsize
    return weights + activations


def flops_per_layer(tokens, hidden, width, held, experts, top_k):
    return 2 * local_rows(tokens, held, experts, top_k) * 3 * hidden * width


def least_seconds(tokens, hidden, width, held, experts, top_k, itemsize,
                  peaks):
    b = bytes_per_layer(tokens, hidden, width, held, experts, top_k,
                        itemsize)
    f = flops_per_layer(tokens, hidden, width, held, experts, top_k)
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])

"""Bytes and operations of the residual streams' traffic at ONE
sublayer of a model with manifold-constrained hyper-connections, by the
DEFINITION, whatever implements it and however it is fused
(`paddle_tpu/kernels/hyper_connections.py` runs it as two kernels that
each pass over the streams, and is held to the same count): a token
reads its `n` streams of `C` numbers ONCE (the maps are made from them
and the sublayer's input `u` is their weighted sum), writes `u`, reads
the sublayer's output `f` and writes the `n` mixed streams ONCE: (2n +
2) C numbers a token. The maps take 2n + n^2 dot products over the n C
numbers, a multiply and an add each; the Sinkhorn sweeps work on n^2
numbers a token and are not counted. 1.3 operations a byte at n = 4 in
bfloat16, under the chip's 240: bound by memory.

Two kernels that each read the streams move (3n + 2) C numbers a token:
they can reach (2n + 2) / (3n + 2) = 71 % of this count's time at n = 4.
"""
from __future__ import annotations


def bytes_per_token(streams, hidden, itemsize):
    return (2 * streams + 2) * hidden * itemsize


def flops_per_token(streams, hidden):
    return 2 * (2 * streams + streams * streams) * streams * hidden


def least_seconds(tokens, streams, hidden, itemsize, peaks):
    """`tokens` counts a token once a sublayer it passed."""
    return tokens * max(
        bytes_per_token(streams, hidden, itemsize)
        / peaks["hbm_bytes_per_s"],
        flops_per_token(streams, hidden) / peaks["bf16_flops_per_s"])

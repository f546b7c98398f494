"""A dropless, gated expert layer that is told which experts it holds.

`MoELayer` (moe_layer.py) gives every expert a fixed capacity and drops
what overflows: right for training at scale, and something no plain
reference can match token for token. Serving needs the other contract:
every assignment is computed. This layer routes over ALL `num_experts`
(the router keeps its published width) by a routing rule (`route`: the
`top_k` largest logits of each token, gated by the softmax over those
`top_k` logits alone, unless the model brings another, such as
`group_limited_sigmoid_route`), and computes the part of the result
that the experts in `held` give. An assignment to an expert that lives on another chip adds
nothing here, and nothing stands in for that chip or for its exchange:
under expert parallelism the partial results of the ranks add up (the
shared expert, which every rank computes alike, counted once).

Static shapes at N x top_k assignments, none dropped: the assignments
are sorted by the local index of their expert (absent experts last),
and the two expert matmuls run as grouped matmuls over the held
experts' stacked banks (`jax.lax.ragged_dot`, which the TPU compiler
lowers to a grouped-matmul kernel of its own: each expert's weights are
read once, and no row of the other experts is computed). Rows past the
last group belong to absent experts and are zeroed by their gate.

A program's tokens are routed in pieces (`block_tokens`): every piece
reads every held bank once, so a piece wants enough tokens that the
routing is expected to give an expert the chip's ridge in rows, or that
the banks are no longer the larger part of what the piece moves, and no
more, because the gathered rows of a piece are temporaries. The piece
follows from the shapes the layer is traced with (tokens, `top_k`, the
router's width, the activations' and the banks' bytes) and from nothing
a caller sets; a token's result does not depend on the piece that held
it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .....nn.layer_base import Layer
from .....nn.initializer import Normal
from .....ops._dispatch import apply
from .....kernels._common import mxu_precision

# Tokens routed in one piece, at least. A prefill of 8192 tokens under
# 72 experts top-10 makes 81,920 assignments, whose gathered inputs and
# outputs at width 4096 are 0.7 GB each; in pieces of 2048 tokens they
# are a quarter of that.
MIN_BLOCK_TOKENS = 2048
# Rows an expert has to expect before its grouped matmul stops being
# bound by reading its bank: a row of bfloat16 does as many operations
# as the bank has bytes, and a TPU v5e does 197 TFLOP/s beside 819 GB/s.
# (The compiler's grouped kernel: about 34 us an expert a piece, 2.4
# times its bank's read, and half the MXU a row; PERF.md section 6.)
RIDGE_ROWS = 240
# Times a piece moves its gathered rows: written by the gather and read
# by the first matmul, written by the second and read by the gather back.
ROW_PASSES = 4


def block_tokens(n, top_k, num_experts, row_bytes, bank_bytes):
    """How many of a program's `n` tokens are routed in one piece.
    Every piece reads every held bank once (`bank_bytes`: both banks),
    so twice the tokens a piece halve that; the piece doubles from
    `MIN_BLOCK_TOKENS` while both hold:

    - the routing is EXPECTED to give an expert fewer than `RIDGE_ROWS`
      rows (`block x top_k / num_experts`: the router spreads a token's
      `top_k` assignments over its whole width): above the ridge the
      grouped matmuls are bound by the MXU and more tokens buy only
      temporaries (72 experts top-10 stop here, at 2048);
    - the banks are the larger part of what a piece moves: more bytes
      than its `block x top_k` gathered rows of `row_bytes`, moved
      `ROW_PASSES` times. Under that the read saved is small beside
      the piece's own traffic, and the compiler's gather costs twice
      as much a row at 32768 and 65536 rows as at 16384 (16 of 128
      experts top-8 at width 2048 stop here, at 2048: 0.15 GB of banks
      beside 0.27 GB; on the chip their layer is a sixth slower at
      4096).

    128 of 512 experts top-8 at width 2560 (1.5 GB of banks) reach
    16384. `n` at or under the piece is one piece, as is an `n` the
    piece does not divide (the serve programs' are powers of two)."""
    block = MIN_BLOCK_TOKENS
    while block * top_k < RIDGE_ROWS * num_experts \
            and ROW_PASSES * block * top_k * row_bytes < bank_bytes:
        block *= 2
    return block if n > block and n % block == 0 else n


def _count(ids, n):
    """How many of `ids` (int32, n = none) name each of 0..n-1."""
    return jnp.zeros((n + 1,), jnp.int32).at[ids].add(1)[:n]


def softmax_topk_route(logits, top_k):
    """The `top_k` largest logits of each token, gated by the softmax
    over those alone. logits [n, experts] float32 -> (gates [n, k]
    float32, expert ids [n, k] int32)."""
    topv, topi = jax.lax.top_k(logits, top_k)                  # [n, k]
    return jax.nn.softmax(topv, axis=-1), topi


def group_limited_sigmoid_route(logits, bias, top_k, n_group, topk_group,
                                scale, norm=True):
    """DeepSeek-V3's rule (arXiv:2412.19437, `noaux_tc`). Scores `s =
    sigmoid(logits)`; the CHOICE is made on `s + bias`: the experts lie
    in `n_group` equal groups, a group scores the sum of its two largest
    choice scores, the `topk_group` best groups stay, and among their
    experts the `top_k` largest choice scores are taken (ties to the
    lower id). The GATES are the chosen experts' `s`, without the bias:
    over their sum (`norm`), times `scale`. logits [n, experts] float32,
    bias [experts] -> (gates [n, k] float32, ids [n, k] int32)."""
    n, e = logits.shape
    s = jax.nn.sigmoid(logits)
    choice = s + bias.astype(jnp.float32)[None, :]
    per = choice.reshape(n, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(per, 2)[0], axis=-1)   # [n, groups]
    _, kept = jax.lax.top_k(group_score, topk_group)
    open_ = jnp.zeros((n, n_group), jnp.bool_).at[
        jnp.arange(n, dtype=jnp.int32)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(open_, e // n_group, axis=1), choice,
                       -jnp.inf)
    _, topi = jax.lax.top_k(masked, top_k)
    gates = jnp.take_along_axis(s, topi, axis=1)
    if norm:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                         + jnp.float32(1e-20))
    return gates * jnp.float32(scale), topi


def _route_block(x, valid, router_w, w_in, w_out, local_of, n_held, top_k,
                 route=None):
    """x [n, h], valid [n] bool -> (y [n, h] float32, counts int32
    [2 + n_held] = assignments, local assignments, tokens per held
    expert; pad and idle rows are computed but not counted)."""
    n, _ = x.shape
    with jax.named_scope("moe.route"):
        logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
        gates, topi = softmax_topk_route(logits, top_k) if route is None \
            else route(logits)
    loc = local_of[topi].reshape(-1)          # [n * k], n_held = absent
    rows = jnp.arange(n * top_k, dtype=jnp.int32)
    _, order = jax.lax.sort_key_val(loc, rows)     # stable, by expert
    sizes = _count(loc, n_held)
    with jax.named_scope("moe.experts"):
        xs = x[order // top_k]                                 # [n * k, h]
        # the compiler's grouped kernel is a Mosaic kernel: it refuses
        # bf16 operands under the global "highest" (kernels/_common.py)
        prec = mxu_precision(x, w_in)
        up = jax.lax.ragged_dot(xs, w_in, sizes, precision=prec)
        f = w_out.shape[1]
        act = jax.nn.silu(up[:, :f].astype(jnp.float32)) \
            * up[:, f:].astype(jnp.float32)
        out = jax.lax.ragged_dot(act.astype(x.dtype), w_out, sizes,
                                 precision=prec)
    _, back = jax.lax.sort_key_val(order, rows)    # sorted row of (t, j)
    out = out[back].reshape(n, top_k, -1).astype(jnp.float32)
    mine = (loc < n_held).reshape(n, top_k)
    y = jnp.sum(jnp.where(mine[..., None], out * gates[..., None], 0.0),
                axis=1)
    counted = jnp.where(jnp.repeat(valid, top_k), loc, n_held)
    per = _count(counted, n_held)
    head = jnp.stack([jnp.sum(valid, dtype=jnp.int32) * top_k,
                      jnp.sum(per, dtype=jnp.int32)])
    return y, jnp.concatenate([head, per])


def _moe_in_blocks(x, valid, router_w, w_in, w_out, *, held, top_k, route,
                   block):
    """`dropless_moe` with its tokens cut into pieces of `block` (which
    divides them). Counts the pieces where the program is traced:
    `moe.prefill_blocks{tokens}`."""
    n_experts = router_w.shape[1]
    local_of = np.full((n_experts,), len(held), np.int32)
    local_of[list(held)] = np.arange(len(held), dtype=np.int32)
    local_of = jnp.asarray(local_of)
    flat = x.reshape(-1, x.shape[-1])
    ok = jnp.ones(flat.shape[:1], jnp.bool_) if valid is None \
        else valid.reshape(-1)

    def piece(xv):
        return _route_block(xv[0], xv[1], router_w, w_in, w_out, local_of,
                            len(held), top_k, route)

    n = flat.shape[0]
    from .....observability import metrics as _obsm
    _obsm.counter("moe.prefill_blocks").inc(n // block, tokens=str(block))
    if n > block:
        y, counts = jax.lax.map(
            piece, (flat.reshape(-1, block, flat.shape[1]),
                    ok.reshape(-1, block)))
        y, counts = y.reshape(n, -1), counts.sum(axis=0, dtype=jnp.int32)
    else:
        y, counts = piece((flat, ok))
    return y.astype(x.dtype).reshape(x.shape), counts


def dropless_moe(x, valid, router_w, w_in, w_out, *, held, top_k,
                 route=None):
    """x [..., h] -> (y like x, counts int32 [2 + len(held)]). `route`
    (logits [n, experts] float32 -> gates [n, top_k] float32, ids [n,
    top_k]) replaces the softmax-over-top-k rule."""
    nbytes = lambda a: a.size * a.dtype.itemsize
    return _moe_in_blocks(
        x, valid, router_w, w_in, w_out, held=held, top_k=top_k, route=route,
        block=block_tokens(x.size // x.shape[-1], top_k, router_w.shape[1],
                           x.shape[-1] * x.dtype.itemsize,
                           nbytes(w_in) + nbytes(w_out)))


class DroplessMoELayer(Layer):
    """Router over `num_experts`, banks of the experts in `held` (all
    of them when None): `w_in` [held, d_model, 2 x d_ff] (gate half,
    then up half), `w_out` [held, d_ff, d_model]; expert e computes
    `w_out_e(silu(gate) * up)`. `forward(x, valid)` returns the held
    experts' part of the layer's result and the routing counts."""

    def __init__(self, d_model, d_ff, num_experts, top_k, held=None,
                 initializer_range=0.02):
        super().__init__()
        self.held = tuple(range(num_experts)) if held is None \
            else tuple(int(e) for e in held)
        if len(set(self.held)) != len(self.held) or not all(
                0 <= e < num_experts for e in self.held):
            raise ValueError(f"held experts {self.held} are not distinct "
                             f"ids below {num_experts}")
        self.top_k = int(top_k)
        init = Normal(0.0, initializer_range)
        self.router = self.create_parameter([d_model, num_experts],
                                            default_initializer=init)
        self.w_in = self.create_parameter(
            [len(self.held), d_model, 2 * d_ff], default_initializer=init)
        self.w_out = self.create_parameter(
            [len(self.held), d_ff, d_model], default_initializer=init)

    def forward(self, x, valid=None):
        def fn(xv, rw, wi, wo, *ok):
            return dropless_moe(xv, ok[0] if ok else None, rw, wi, wo,
                                held=self.held, top_k=self.top_k)
        extra = () if valid is None else (valid,)
        return apply(fn, x, self.router, self.w_in, self.w_out, *extra,
                     _name="dropless_moe")

"""Kimi Delta Attention (arXiv:2510.26692): linear attention whose state,
a [d_k, d_v] matrix a head, decays by a gate of its own on every key
channel and is corrected by a delta rule.

    S'  = diag(exp(g_t)) S_{t-1}                 g_t in R^{d_k}, g <= 0
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T     beta_t in (0, 1)
    o_t = S_t^T q_t

Three forms of the same recurrence (models/ling_hybrid.py):

- `kda_sequential`: as written, one token at a time (`lax.scan`): the
  form the other two are tested against.
- `kda_step`: one token for the rows of a state pool, the decode step:
  elementwise passes and two reductions over the float32 state, nothing
  a matmul. XLA fuses them into two passes over every row; the Pallas
  kernel holds a head's state in VMEM, reads and writes it once, and
  visits only the rows that carry a request.
- `kda_chunked`: a prompt by chunks of `chunk` tokens (the WY / UT form
  of the gated delta rule). With G_t the decay cumulated from the
  chunk's start and u_t = beta_t (v_t - S'^T k_t), a chunk's updates
  solve

      (I + diag(beta) A) U = diag(beta) (V - (K * exp(G)) S_0)
      A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])       i < t

  which is triangular and does not hold S_0: `T = (I + diag(beta) A)^-1
  diag(beta)` applied to V and to K * exp(G) is computed for all chunks
  at once, and only

      U = T V - (T (K exp(G))) S_0
      O = (Q exp(G)) S_0 + B U          B[t, i] = A's form with q_t, i <= t
      S_C = diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

  runs chunk after chunk. exp(G_t - G_i) is never formed from exp(G_t)
  / exp(G_i) (G reaches chunk x lower bound, past float32's range): a
  row takes its decay from the start of its own sub-chunk of `sub`
  tokens and a column up to there, so that every exponent is at most
  sub x |lower bound| (16 x 5 = 80 < 88). The caller takes a long
  prompt a segment at a time and hands the state on, so that what is
  computed for all chunks at once stays a segment's size.

The inverse is built, not solved for (`unit_lower_inverse`): XLA expands
a triangular solve of systems this small into a sweep over rows that
took a sixth of a prefill on the chip (PR 36). The `sub`-wide diagonal
blocks are inverted by forward substitution, a row at a time over every
block of every chunk at once; the blocks below them follow by block
forward substitution as float32 matmuls, and one float32 matmul a chunk
applies the inverse to [V, K exp(G)]. That is as close to a float64
solve as the solve was (2e-7 of the largest entry, nearly identical
keys under beta = 0.999 included). The nilpotent doubling product
(I - N)(I + N^2)(I + N^4)... is not: on those keys its factors reach
1e11 and cancel to nothing, and it is 6e-4 off on 16 x 16 blocks alone.

The state, the decays and the triangular system are float32 whatever
the activations' dtype; the matmuls against the state take the
activations' dtype and accumulate in float32, as `ssd_chunked` does.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _Z, use_pallas as _use_pallas, pallas_interpret

F32 = jnp.float32
_HEADS_PER_BLOCK = 8        # heads of one grid step: a sublane tile


def kda_gate(a, a_log, dt_bias, lower):
    """The safe gate: a [..., H, d_k] (the decay projection), a_log [H],
    dt_bias [H, d_k] -> g = lower * sigmoid(exp(a_log) * (a + dt_bias)),
    float32 in (lower, 0)."""
    x = jnp.exp(a_log.astype(F32))[:, None] \
        * (a.astype(F32) + dt_bias.astype(F32))
    return F32(lower) * jax.nn.sigmoid(x)


def _recur(state, q, k, v, g, beta):
    """One token, all float32: state [r, h, dk, dv]; q, k, g [r, h,
    dk]; v [r, h, dv]; beta [r, h] -> (new state, o [r, h, dv])."""
    decayed = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(decayed * k[..., None], axis=-2))
    state = decayed + k[..., None] * u[..., None, :]
    return state, jnp.sum(state * q[..., None], axis=-2)


# ---------------------------------------------------------------------------
# The decode step as a kernel. XLA makes two fusions of `_recur` (the
# reduction `S'^T k`, then the update and the read-out), so every row of
# the pool is read twice and written once whatever the occupancy. Here a
# grid step holds 8 heads of one row in VMEM: the state is read once and
# written once, in place, and only the rows that carry a request are
# visited: the grid walks `order` (the occupied rows first, then the
# pool's last row, nobody's, again and again: a block whose index does
# not change is neither fetched nor written again).
#
# A head's state is [d_k, d_v] with d_k on sublanes. The decay, k and q
# multiply along d_k, so they come as COLUMNS (`cols` [rows, heads / 8,
# d_k, 24]: exp(g), k, q of a block's 8 heads side by side on lanes,
# transposed by XLA: 260 KB a row beside the state's 4 MB); v and beta
# multiply along d_v and come as rows.
# ---------------------------------------------------------------------------

def _step_kernel(order_ref, live_ref, cols_ref, rows_ref, s_ref, o_in_ref,
                 s_out, o_out, *, hb):
    del order_ref, o_in_ref

    # past the occupied rows the grid stands on one block of the last
    # row: nothing is computed, and what is written back there at the
    # end is nobody's
    @pl.when(pl.program_id(0) < live_ref[0])
    def _row():
        for j in range(hb):
            decay = cols_ref[0, 0, :, j:j + 1]             # (dk, 1)
            k = cols_ref[0, 0, :, hb + j:hb + j + 1]
            q = cols_ref[0, 0, :, 2 * hb + j:2 * hb + j + 1]
            v = rows_ref[0, 0, j:j + 1, :]                 # (1, dv)
            beta = rows_ref[0, 0, hb + j:hb + j + 1, :]
            decayed = s_ref[0, j] * decay                  # (dk, dv)
            u = beta * (v - jnp.sum(decayed * k, axis=0, keepdims=True))
            new = decayed + k * u
            s_out[0, j] = new
            o_out[0, 0, j:j + 1, :] = jnp.sum(new * q, axis=0,
                                              keepdims=True)


def _kda_step_pallas(state, q, k, v, g, beta, active, interpret):
    r, h, dk, dv = state.shape
    hb = _HEADS_PER_BLOCK
    nb = h // hb
    i32 = jnp.int32
    live = jnp.sum(active, dtype=i32)
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(i32)
    order = jnp.where(jnp.arange(r, dtype=i32) < live, order, i32(r - 1))
    blocks = lambda a: a.reshape(r, nb, hb, a.shape[-1])
    cols = jnp.concatenate(
        [jnp.swapaxes(blocks(a), 2, 3) for a in (jnp.exp(g), k, q)], axis=-1)
    rows = jnp.concatenate(
        [blocks(v), blocks(jnp.broadcast_to(beta[..., None], (r, h, dv)))],
        axis=2)

    def at(i, j, order_ref, live_ref):
        # past the occupied rows: one block of the last row, unmoved
        return order_ref[i], jnp.where(i < live_ref[0], j, np.int32(0))

    spec = lambda *tail: pl.BlockSpec(
        (1, 1) + tail, lambda i, j, o, n: at(i, j, o, n) + (_Z,) * len(tail))
    state_spec = pl.BlockSpec(
        (1, hb, dk, dv),
        lambda i, j, o, n: at(i, j, o, n) + (_Z, _Z))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(r, nb),
        in_specs=[spec(dk, 3 * hb), spec(2 * hb, dv), state_spec,
                  spec(hb, dv)],
        out_specs=[state_spec, spec(hb, dv)])
    new, o = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((r, nb, hb, dv), F32)],
        # operands count the two prefetched scalars: the state and the
        # zeroed outputs are updated where they lie, and a row that is
        # not visited keeps what it held
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(order, live[None], cols, rows, state,
      jnp.zeros((r, nb, hb, dv), F32))
    return new, o.reshape(r, h, dv)


def step_gate_reason(h, dk, dv):
    """Why the Pallas state update cannot take this geometry, or None:
    whole blocks of 8 heads, d_k whole sublane tiles, d_v whole lanes."""
    if h % _HEADS_PER_BLOCK:
        return "head_count_tiling"
    if dk % 8 or dv % 128:
        return "head_dim_tiling"
    return None


def kda_step(state, q, k, v, g, beta, active=None, interpret=False):
    """One decode step for the rows of the state pool. state [r, h, dk,
    dv] float32; q, k [r, h, dk]; v [r, h, dv]; g [r, h, dk] and beta
    [r, h] float32 -> (new state, o like v). `active` [r] bool says
    which rows carry a request (the pool's last row never does): with
    it the Pallas kernel advances those rows alone, and the others keep
    their state and give o = 0; without it, or where the kernel cannot
    take the geometry, every row advances (XLA)."""
    interpret = interpret or pallas_interpret()
    with jax.named_scope("kda.state_update"):
        args = (state, q.astype(F32), k.astype(F32), v.astype(F32), g, beta)
        if active is not None and (interpret or _use_pallas()):
            reason = step_gate_reason(*state.shape[1:])
            if reason is None:
                state, o = _kda_step_pallas(*args, active, interpret)
                return state, o.astype(v.dtype)
            from ._common import note_fallback
            note_fallback("kda_state_update", reason)
        state, o = _recur(*args)
        return state, o.astype(v.dtype)


def kda_sequential(q, k, v, g, beta, state=None):
    """The recurrence as written. q, k, g [b, l, h, dk]; v [b, l, h,
    dv]; beta [b, l, h] -> (o like v, final state [b, h, dk, dv])."""
    b, _, h, dk = q.shape
    if state is None:
        state = jnp.zeros((b, h, dk, v.shape[-1]), F32)
    xs = tuple(jnp.moveaxis(a.astype(F32), 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(lambda s, x: _recur(s, *x), state, xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype), state


def unit_lower_inverse(strict, sub):
    """(I + N)^-1 for N = `strict` [..., c, c] float32, strictly lower
    triangular, c whole blocks of `sub`: the diagonal blocks by forward
    substitution, a row at a time over every block at once (multiply
    and reduce: nothing a matmul), then the blocks below them by block
    forward substitution, as matmuls."""
    ns = strict.shape[-1] // sub
    diag = jnp.stack([strict[..., a * sub:(a + 1) * sub,
                             a * sub:(a + 1) * sub] for a in range(ns)],
                     axis=-3)                           # [..., ns, sub, sub]
    at = jnp.arange(sub, dtype=jnp.int32)

    def row(i, x):      # X[i] = e_i - N[i, :i] X[:i]; X's later rows are e_j
        n_i = jax.lax.dynamic_index_in_dim(diag, i, diag.ndim - 2, False)
        new = (at == i).astype(F32) - jnp.sum(n_i[..., None] * x, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(x, new, i, x.ndim - 2)

    # rolled: unrolled rows run a third slower on the chip and take
    # twice the set-up (PR 36)
    x = jax.lax.fori_loop(jnp.int32(1), jnp.int32(sub), row,
                          jnp.broadcast_to(jnp.eye(sub, dtype=F32),
                                           diag.shape))
    # `inv` inverts the leading a blocks; block row a of the inverse is
    # [-X_a N[a, :a] inv, X_a]
    inv = x[..., 0, :, :]
    for a in range(1, ns):
        below = strict[..., a * sub:(a + 1) * sub, :a * sub]
        x_a = x[..., a, :, :]
        inv = jnp.concatenate([
            jnp.pad(inv, [(0, 0)] * (inv.ndim - 1) + [(0, sub)]),
            jnp.concatenate([-(x_a @ (below @ inv)), x_a], axis=-1)], axis=-2)
    return inv


# jitted: a prefill program calls it once a KDA layer on one set of
# shapes, so it is traced once a process and lowered once a program and
# not once a layer (that is 5 s of a warm set-up on the chip's host, what
# the inverse's ops had added to it: PR 36); XLA inlines the calls
@functools.partial(jax.jit, static_argnames=("chunk", "sub"))
def kda_chunked(q, k, v, g, beta, state=None, chunk=64, sub=16):
    """The same recurrence by chunks, from `state` (zeros when None). q,
    k [b, l, h, dk]; v [b, l, h, dv]; g [b, l, h, dk] and beta [b, l, h]
    float32 -> (o like v, final state [b, h, dk, dv] float32). A token
    with g = 0 and beta = 0 (padding) leaves the state as it was. What
    does not hold the state is computed for all l / chunk chunks at
    once: the caller bounds l (models/ling_hybrid.py takes a prompt a
    segment at a time and carries the state)."""
    b, l, h, dk = q.shape
    dv = v.shape[-1]
    c = int(chunk)
    sub = min(int(sub), c)
    if c % sub:
        raise ValueError(f"chunk {c} is not whole sub-chunks of {sub}")
    ns, dtype = c // sub, q.dtype
    pad = -l % c
    n = (l + pad) // c
    with jax.named_scope("kda.chunk"):
        def lay(a):     # [b, l, h, x] -> [b, h, chunks, c, x] float32
            a = jnp.pad(a.astype(F32), [(0, 0), (0, pad), (0, 0), (0, 0)])
            return a.reshape(b, n, c, h, a.shape[-1]).transpose(0, 3, 1, 2, 4)
        q, k, v, g = lay(q), lay(k), lay(v), lay(g)
        beta = lay(beta[..., None])[..., 0]
        if state is None:
            state = jnp.zeros((b, h, dk, dv), F32)
        cum = jnp.cumsum(g, axis=3)                     # inclusive, <= 0
        by_sub = lambda a: a.reshape(b, h, n, ns, sub, a.shape[-1])
        # the decay cumulated before a sub-chunk's first token
        ref = jnp.concatenate([jnp.zeros((b, h, n, 1, dk), F32),
                               by_sub(cum)[:, :, :, :-1, -1]], axis=3)
        row = jnp.exp(by_sub(cum) - ref[..., None, :])  # <= 1
        # a column's decay up to the start of row sub-chunk a; the
        # columns after that sub-chunk are seen by none of its rows
        seen = jnp.arange(c, dtype=jnp.int32)[None, :] \
            < (jnp.arange(ns, dtype=jnp.int32)[:, None] + 1) * sub
        col = jnp.exp(jnp.where(seen[..., None],
                                ref[:, :, :, :, None] - cum[:, :, :, None],
                                -jnp.inf))              # [b, h, n, ns, c, dk]
        kcol = k[:, :, :, None] * col
        pair = lambda rows: jnp.einsum(
            "bhnasd,bhnaid->bhnasi", by_sub(rows) * row, kcol,
            preferred_element_type=F32).reshape(b, h, n, c, c)
        t = jnp.arange(c, dtype=jnp.int32)
        a_mat = jnp.where(t[:, None] > t[None, :], pair(k), F32(0))
        b_mat = jnp.where(t[:, None] >= t[None, :], pair(q), F32(0))
        decay = jnp.exp(cum)
        solved = jnp.einsum(
            "bhnti,bhnix->bhntx",
            unit_lower_inverse(beta[..., None] * a_mat, sub),
            beta[..., None] * jnp.concatenate([v, k * decay], axis=-1),
            preferred_element_type=F32)
        w_v, w_k = solved[..., :dv], solved[..., dv:]
        q_in = q * decay
        k_out = k * jnp.exp(cum[:, :, :, -1:] - cum)
        whole = decay[:, :, :, -1]                      # [b, h, n, dk]
        mm = lambda spec, x, y: jnp.einsum(spec, x.astype(dtype),
                                           y.astype(dtype),
                                           preferred_element_type=F32)

        def one(s, xs):
            w_v_n, w_k_n, q_n, b_n, k_n, whole_n = xs
            u = w_v_n - mm("bhck,bhkv->bhcv", w_k_n, s)
            o = mm("bhck,bhkv->bhcv", q_n, s) + mm("bhci,bhiv->bhcv", b_n, u)
            s = whole_n[..., None] * s + mm("bhck,bhcv->bhkv", k_n, u)
            return s, o

        state, o = jax.lax.scan(one, state, tuple(
            jnp.moveaxis(a, 2, 0)
            for a in (w_v, w_k, q_in, b_mat, k_out, whole)))
        # [chunks, b, h, c, dv] -> [b, l, h, dv]
        o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * c, h, dv)[:, :l]
        return o.astype(dtype), state

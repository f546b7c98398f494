"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606): a token carries `n` residual
streams `X` in R^{n x C}, and every sublayer `F` reads and writes them
through three maps made from the token's own streams,

    u  = sum_j H_pre[j] X[j]                      (what F reads)
    X' = H_res X + H_post^T F(u)                  (what the layer keeps)

with `v = vec(X)`, `r = v / sqrt(mean(v^2) + eps)` and, from `phi` [nC,
2n + n^2] (columns: pre, post, then res row-major), scalars `a` [3] and
biases `b` [2n + n^2]:

    H_pre  = sigmoid(a_pre  (r phi_pre)  + b_pre)                 [n]
    H_post = 2 sigmoid(a_post (r phi_post) + b_post)              [n]
    H_res  = Sinkhorn(clip(a_res mat(r phi_res) + b_res, lo, hi)) [n, n]

Sinkhorn: `M = exp(.)`, then `iters` times: every COLUMN over (its sum
+ `hc_eps`), then every ROW over (its sum + `hc_eps`); exactly `iters`
sweeps, no early exit.

Two kernels, one pass over the streams each: `mhc_pre` (the RMS over
all nC numbers, the 2n + n^2 dot products on the MXU, the coefficient
arithmetic with the tokens on the lanes, the H_pre-weighted sum) and
`mhc_post` (H_res x streams + H_post (x) F's output). All coefficient
arithmetic is float32 whatever the streams' dtype; the streams keep
theirs.

Shapes on the chip decide two things about the interface. The streams
travel as `[T, n x C]` (stream j on columns jC .. (j + 1)C): a `[T, n,
C]` array is tiled over its last two axes and a 4-row tile is stored on
16. And the coefficients travel from `mhc_pre` to `mhc_post` as ONE
float32 row of `COEF_LANES` a token (`coef`): `[T, n]` and `[T, n, n]`
float32 arrays are each stored on 128-lane rows anyway. `unpack` gives
(H_pre, H_post, H_res) of a `coef`; `coef_lanes` says where each sits.

Each kernel has an XLA form of the same signature (`_mhc_pre_xla`,
`_mhc_post_xla`): the CPU's route and the kernel's oracle.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (_Z, use_pallas as _use_pallas, pallas_dtype_ok,
                      pallas_interpret, note_fallback, mxu_precision)

F32 = jnp.float32
COEF_LANES = 128
# a coefficient row: H_pre on lanes 0 .., H_post on 8 .., row i of H_res
# on 16 + 8 i ..: every group starts a sublane tile once the tokens are
# on the lanes
_GROUP = 8
_POST, _RES = _GROUP, 2 * _GROUP
# tokens a block: a block holds whole rows of n x C numbers (the maps
# need all of a token's streams before the first can be weighted)
_PRE_TOKENS, _POST_TOKENS = 256, 128
_VMEM_BYTES = 64 << 20


def coef_lanes(n):
    """The lane of each of phi's 2n + n^2 columns in a `coef` row."""
    return np.concatenate(
        [np.arange(n), _POST + np.arange(n)]
        + [_RES + _GROUP * i + np.arange(n) for i in range(n)]).astype(
            np.int32)


def unpack(coef, n):
    """coef [T, COEF_LANES] -> (H_pre [T, n], H_post [T, n], H_res [T,
    n, n]), float32."""
    res = coef[:, _RES:_RES + _GROUP * n].reshape(-1, n, _GROUP)[..., :n]
    return coef[:, :n], coef[:, _POST:_POST + n], res


def _sigmoid(z):
    return F32(1.0) / (F32(1.0) + jnp.exp(-z))


# ------------------------------------------------------------ XLA forms ---

def _mhc_pre_xla(x, phi, a, b, n, iters, eps, hc_eps, clamp):
    t, nc = x.shape
    c = nc // n
    v = x.astype(F32)
    inv = jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + F32(eps))
    z = jnp.dot(x, phi, preferred_element_type=F32) * inv
    a, b = a.astype(F32), b.astype(F32)
    h_pre = _sigmoid(a[0] * z[:, :n] + b[:n])
    h_post = F32(2.0) * _sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * z[:, 2 * n:] + b[2 * n:], F32(clamp[0]),
                         F32(clamp[1]))).reshape(t, n, n)

    def sweep(_, m):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + F32(hc_eps))
        return m / (jnp.sum(m, axis=2, keepdims=True) + F32(hc_eps))

    m = jax.lax.fori_loop(0, iters, sweep, m)
    u = jnp.einsum("tj,tjc->tc", h_pre, v.reshape(t, n, c))
    coef = jnp.zeros((t, COEF_LANES), F32).at[:, coef_lanes(n)].set(
        jnp.concatenate([h_pre, h_post, m.reshape(t, n * n)], axis=1))
    return u.astype(x.dtype), coef


def _mhc_post_xla(x, f, coef, n):
    t, nc = x.shape
    _, h_post, h_res = unpack(coef, n)
    out = jnp.einsum("tij,tjc->tic", h_res,
                     x.astype(F32).reshape(t, n, nc // n)) \
        + h_post[:, :, None] * f.astype(F32)[:, None, :]
    return out.reshape(t, nc).astype(x.dtype)


# -------------------------------------------------------------- kernels ---

def _pre_kernel(x_ref, phi_ref, ab_ref, u_ref, coef_ref, *, n, iters, eps,
                hc_eps, clamp):
    tt, nc = x_ref.shape
    c = nc // n
    tc = max(tt, COEF_LANES)       # the tokens, on whole 128-lane rows
    x = x_ref[...]
    z = jnp.dot(x, phi_ref[...], preferred_element_type=F32,
                precision=mxu_precision(x))                 # (tt, 128)
    ss = jnp.zeros((tt, 1), F32)
    for j in range(n):
        v = x_ref[:, j * c:(j + 1) * c].astype(F32)
        ss = ss + jnp.sum(v * v, axis=1, keepdims=True)
    inv = jax.lax.rsqrt(ss * np.float32(1.0 / nc) + np.float32(eps))
    z = z * inv * ab_ref[0:1, :] + ab_ref[1:2, :]
    if tc > tt:
        z = jnp.concatenate(
            [z, jnp.zeros((tc - tt, COEF_LANES), F32)], axis=0)
    zt = z.T                                                # (128, tc)
    real = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, tc), 0) \
        < np.int32(n)
    group = lambda at: zt[at:at + _GROUP, :]        # (8, tc), n of them real
    only = lambda v: jnp.where(real, v, np.float32(0.0))
    pre = only(_sigmoid(group(0)))
    post = only(np.float32(2.0) * _sigmoid(group(_POST)))
    rows = tuple(only(jnp.exp(jnp.clip(
        group(_RES + _GROUP * i), np.float32(clamp[0]),
        np.float32(clamp[1])))) for i in range(n))

    def sweep(carry):
        k, rows = carry
        col = functools.reduce(jnp.add, rows) + np.float32(hc_eps)
        rows = tuple(r / col for r in rows)
        rows = tuple(r / (jnp.sum(r, axis=0, keepdims=True)
                          + np.float32(hc_eps)) for r in rows)
        return k + np.int32(1), rows

    # rolled, and counted in int32 (x64 is on: see kernels/_common.py)
    _, rows = jax.lax.while_loop(lambda cr: cr[0] < np.int32(iters), sweep,
                                 (np.int32(0), rows))
    packed = jnp.concatenate(
        [pre, post, *rows,
         jnp.zeros((COEF_LANES - _GROUP * (n + 2), tc), F32)], axis=0)
    coef = packed.T[:tt, :]                                 # (tt, 128)
    coef_ref[...] = coef
    u = jnp.zeros((tt, c), F32)
    for j in range(n):
        u = u + coef[:, j:j + 1] * x_ref[:, j * c:(j + 1) * c].astype(F32)
    u_ref[...] = u.astype(u_ref.dtype)


def _post_kernel(x_ref, f_ref, coef_ref, o_ref, *, n):
    c = f_ref.shape[1]
    coef = coef_ref[...]
    f = f_ref[...].astype(F32)
    xs = [x_ref[:, j * c:(j + 1) * c].astype(F32) for j in range(n)]
    for i in range(n):
        acc = coef[:, _POST + i:_POST + i + 1] * f
        for j in range(n):
            at = _RES + _GROUP * i + j
            acc = acc + coef[:, at:at + 1] * xs[j]
        o_ref[:, i * c:(i + 1) * c] = acc.astype(o_ref.dtype)


def _mhc_pre_pallas(x, phi, a, b, n, iters, eps, hc_eps, clamp, interpret):
    t, nc = x.shape
    lanes = coef_lanes(n)
    wide = jnp.zeros((nc, COEF_LANES), x.dtype).at[:, lanes].set(
        phi.astype(x.dtype))
    scale = jnp.concatenate([jnp.full((n,), a[0]), jnp.full((n,), a[1]),
                             jnp.full((n * n,), a[2])]).astype(F32)
    ab = jnp.zeros((_GROUP, COEF_LANES), F32).at[0, lanes].set(scale) \
        .at[1, lanes].set(b.astype(F32))
    tt = min(t, _PRE_TOKENS)
    row = lambda width: pl.BlockSpec((tt, width), lambda i: (i, _Z))
    whole = lambda shape: pl.BlockSpec(shape, lambda i: (_Z, _Z))
    return pl.pallas_call(
        functools.partial(_pre_kernel, n=n, iters=iters, eps=eps,
                          hc_eps=hc_eps, clamp=clamp),
        grid=(pl.cdiv(t, tt),),
        in_specs=[row(nc), whole((nc, COEF_LANES)),
                  whole((_GROUP, COEF_LANES))],
        out_specs=[row(nc // n), row(COEF_LANES)],
        out_shape=[jax.ShapeDtypeStruct((t, nc // n), x.dtype),
                   jax.ShapeDtypeStruct((t, COEF_LANES), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(x, wide, ab)


def _mhc_post_pallas(x, f, coef, n, interpret):
    t, nc = x.shape
    tt = min(t, _POST_TOKENS)
    row = lambda width: pl.BlockSpec((tt, width), lambda i: (i, _Z))
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n),
        grid=(pl.cdiv(t, tt),),
        in_specs=[row(nc), row(nc // n), row(COEF_LANES)],
        out_specs=row(nc),
        out_shape=jax.ShapeDtypeStruct((t, nc), x.dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(x, f, coef)


def mhc_gate_reason(t, nc, n):
    """Why the Pallas kernels cannot take this geometry (a reason label
    of ``kernels.pallas_fallbacks``), or None: every stream on whole
    128-lane stretches, whole sublane tiles of tokens, the maps' rows
    inside a `coef` row."""
    if nc % n or (nc // n) % 128:
        return "stream_width_tiling"
    if t % 16:
        return "token_tiling"
    if n > _GROUP:      # a row of H_res on one group of lanes
        return "stream_count"
    return None


def _route(kernel, x, n, interpret, *others):
    """True where the Pallas kernel takes the call."""
    if not (interpret or _use_pallas()):
        return False
    reason = mhc_gate_reason(x.shape[0], x.shape[1], n)
    if reason is None and not interpret and not pallas_dtype_ok(x, *others):
        reason = "dtype"
    if reason is not None:
        note_fallback(kernel, reason)
    return reason is None


def mhc_pre(x, phi, a, b, *, n, iters, eps, hc_eps, clamp, interpret=False):
    """What a sublayer reads, and the maps it writes back through. x [T,
    n x C] the streams; phi [n x C, 2n + n^2]; a [3]; b [2n + n^2] ->
    (u [T, C] in x's dtype, coef [T, COEF_LANES] float32: `unpack`)."""
    interpret = interpret or pallas_interpret()
    with jax.named_scope("mhc.pre"):
        if _route("mhc_pre", x, n, interpret, phi):
            return _mhc_pre_pallas(x, phi, a, b, n, iters, eps, hc_eps,
                                   tuple(clamp), interpret)
        return _mhc_pre_xla(x, phi, a, b, n, iters, eps, hc_eps, clamp)


def mhc_post(x, f, coef, *, n, interpret=False):
    """The streams after a sublayer: x [T, n x C]; f [T, C] its output;
    coef as `mhc_pre` gave it -> x' [T, n x C], `x'[i] = sum_j H_res[i,
    j] x[j] + H_post[i] f`."""
    interpret = interpret or pallas_interpret()
    with jax.named_scope("mhc.post"):
        if _route("mhc_post", x, n, interpret, f):
            return _mhc_post_pallas(x, f, coef, n, interpret)
        return _mhc_post_xla(x, f, coef, n)

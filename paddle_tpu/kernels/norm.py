"""Fused LayerNorm / RMSNorm Pallas kernels.

Reference parity: paddle/phi/kernels/fusion/gpu/fused_bias_dropout_residual
_layer_norm and rms_norm kernels (paddle/phi/kernels/gpu/rms_norm_kernel.cu).
On TPU XLA already fuses the reduction+normalize chain well, so these
kernels mainly (a) guarantee single-pass VMEM-resident normalization for
the LLM hot path and (b) keep the f32 statistics in-register for bf16
activations. Forward is Pallas; backward recomputes via the standard
analytic formulas in XLA (fused by the compiler).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (_Z, _NEG_INF, use_pallas as _use_pallas,
                      pallas_dtype_ok, pallas_interpret, partitioned)

# rows of the flattened [B*S, D] activations split with the batch; the
# weight vectors are whole on every shard
_ROWS = ("batch", None)


# ------------------------------------------------------------- rms norm ----

def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(var + eps) * w_ref[:].astype(jnp.float32)
                ).astype(o_ref.dtype)


def _block_rows(n, d, block_rows):
    """Rows a block: the float32 copies of a block of 256 rows fit the
    scoped VMEM up to 4096 columns; a wider row takes fewer."""
    while block_rows * d > 256 * 4096 and block_rows > 8:
        block_rows //= 2
    return min(block_rows, n)


def _rms_pallas(x2d, w, eps, block_rows=256):
    n, d = x2d.shape
    block_rows = _block_rows(n, d, block_rows)
    return pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(pl.cdiv(n, block_rows),),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, _Z)),
            pl.BlockSpec((d,), lambda i: (_Z,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, _Z)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
        interpret=pallas_interpret(),
    )(x2d, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_core(x, w, eps):
    return _rms_fwd(x, w, eps)[0]


def _rms_fwd(x, w, eps):
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    if _use_pallas() and d % 128 == 0 and pallas_dtype_ok(x2, w):
        out2 = partitioned(lambda a, g: _rms_pallas(a, g, eps),
                           [_ROWS, None], _ROWS, x2, w)
    else:
        # f64 inputs keep f64 statistics (the x64 user asked for it)
        cdt = jnp.promote_types(x.dtype, jnp.float32)
        xf = x2.astype(cdt)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out2 = (xf * jax.lax.rsqrt(var + eps) * w.astype(cdt)
                ).astype(x.dtype)
    return out2.reshape(shape), (x, w)


def _rms_bwd(eps, res, g):
    x, w = res
    shape = x.shape
    d = shape[-1]
    cdt = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.reshape(-1, d).astype(cdt)
    gf = g.reshape(-1, d).astype(cdt)
    wf = w.astype(cdt)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xf * inv
    gw = jnp.sum(gf * xhat, axis=0).astype(w.dtype)
    gx_hat = gf * wf
    gx = inv * (gx_hat - xhat * jnp.mean(gx_hat * xhat, axis=-1, keepdims=True))
    return gx.reshape(shape).astype(x.dtype), gw


_rms_core.defvjp(lambda x, w, eps: _rms_fwd(x, w, eps), _rms_bwd)


def fused_rms_norm(x, weight, eps=1e-6):
    """jax-level fused RMSNorm: y = x / rms(x) * weight."""
    return _rms_core(x, weight, eps)


# ------------------------------------------------------------ layer norm ---

def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    o_ref[:] = (xc * jax.lax.rsqrt(var + eps) * w_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_pallas(x2d, w, b, eps, block_rows=256):
    n, d = x2d.shape
    block_rows = _block_rows(n, d, block_rows)
    return pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(pl.cdiv(n, block_rows),),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, _Z)),
            pl.BlockSpec((d,), lambda i: (_Z,)),
            pl.BlockSpec((d,), lambda i: (_Z,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, _Z)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
        interpret=pallas_interpret(),
    )(x2d, w, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln_core(x, w, b, eps):
    return _ln_fwd(x, w, b, eps)[0]


def _ln_fwd(x, w, b, eps):
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    if _use_pallas() and d % 128 == 0 and pallas_dtype_ok(x2, w):
        out2 = partitioned(lambda a, g, c: _ln_pallas(a, g, c, eps),
                           [_ROWS, None, None], _ROWS, x2, w, b)
    else:
        cdt = jnp.promote_types(x.dtype, jnp.float32)
        xf = x2.astype(cdt)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        xc = xf - mu
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        out2 = (xc * jax.lax.rsqrt(var + eps) * w.astype(cdt)
                + b.astype(cdt)).astype(x.dtype)
    return out2.reshape(shape), (x, w, b)


def _ln_bwd(eps, res, g):
    x, w, b = res
    shape = x.shape
    d = shape[-1]
    cdt = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.reshape(-1, d).astype(cdt)
    gf = g.reshape(-1, d).astype(cdt)
    wf = w.astype(cdt)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xc * inv
    gw = jnp.sum(gf * xhat, axis=0).astype(w.dtype)
    gb = jnp.sum(gf, axis=0).astype(b.dtype)
    gx_hat = gf * wf
    gx = inv * (gx_hat
                - jnp.mean(gx_hat, axis=-1, keepdims=True)
                - xhat * jnp.mean(gx_hat * xhat, axis=-1, keepdims=True))
    return gx.reshape(shape).astype(x.dtype), gw, gb


_ln_core.defvjp(lambda x, w, b, eps: _ln_fwd(x, w, b, eps), _ln_bwd)


def fused_layer_norm(x, weight, bias, eps=1e-5):
    return _ln_core(x, weight, bias, eps)

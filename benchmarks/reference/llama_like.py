"""Plain reference for Llama-shaped decoders: the forward pass in
`jax.numpy`, float32, matmul precision "highest"; no kernels, no cache,
no batching, no sharding. Written from the published description of the
architecture (pre-norm RMSNorm, rotary embeddings in the half-split
layout, grouped-query causal attention, SwiGLU, untied head); it
imports nothing of the program and regenerates its weights from the
seed, one layer at a time, so that it fits beside nothing in particular.

`quant="int8"` (or `"fp8"`, e4m3) computes the same pass with every
matmul's operands rounded to 8 bits (weights per output channel,
activations per token, symmetric scales): the nearest precision below
bfloat16, used as the control that the comparison has to fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import weights

F32 = jnp.float32
Q_BLOCK = 1024          # attention is computed in blocks of query rows


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F32(127.0)
    scale = jnp.where(scale > 0, scale, F32(1.0))
    return jnp.round(x / scale) * scale


def _fake_fp8(x, axis):
    """Scaled to the e4m3 range, rounded to float8_e4m3fn, scaled back."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F32(448.0)
    scale = jnp.where(scale > 0, scale, F32(1.0))
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, quant):
    if quant in ("int8", "fp8"):
        fake = _fake_int8 if quant == "int8" else _fake_fp8
        x = fake(x, -1)             # per token
        w = fake(w, 0)              # per output channel
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision="highest")


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + F32(eps))


def _rope(x, theta):
    """x [T, H, D]; rotate pairs (i, i + D/2) by position * theta^(-2i/D)."""
    t, _, d = x.shape
    inv = F32(1.0) / (F32(theta) ** (jnp.arange(0, d, 2, dtype=F32) / F32(d)))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal attention, q [T, H, D], k/v [T, Hkv, D], by query blocks."""
    t, h, d = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for s in range(0, t, Q_BLOCK):
        e = min(t, s + Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", q[s:e], k[:e],
                        precision="highest") / jnp.sqrt(F32(d))
        ok = (jnp.arange(e)[None, :] <= jnp.arange(s, e)[:, None])
        sc = jnp.where(ok[None], sc, F32(-1e30))
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:e],
                               precision="highest"))
    return jnp.concatenate(outs, 0)


@functools.partial(jax.jit, static_argnames=("cfg_t", "quant"))
def _layer(x, key, index, cfg_t, quant):
    cfg = dict(cfg_t)
    w = {n: a.astype(F32) for n, a in
         weights.layer(cfg, key, index).items()}
    t = x.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // nh
    h = _rms(x, cfg["rms_norm_eps"])
    q = _rope(_mm(h, w["wq"], quant).reshape(t, nh, d), cfg["rope_theta"])
    k = _rope(_mm(h, w["wk"], quant).reshape(t, nkv, d), cfg["rope_theta"])
    v = _mm(h, w["wv"], quant).reshape(t, nkv, d)
    a = _attention(q, k, v).reshape(t, nh * d)
    x = x + _mm(a, w["wo"], quant)
    h = _rms(x, cfg["rms_norm_eps"])
    up = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(up, w["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("cfg_t",))
def _embed(ids, key, cfg_t):
    return weights.top(dict(cfg_t), key)["embed"].astype(F32)[ids]


@functools.partial(jax.jit, static_argnames=("cfg_t", "quant"))
def _head(x, rows, key, cfg_t, quant):
    cfg = dict(cfg_t)
    h = _rms(x[rows], cfg["rms_norm_eps"])
    return _mm(h, weights.top(cfg, key)["head"].astype(F32), quant)


def _static(cfg):
    keys = ("hidden_size", "intermediate_size", "vocab_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "rms_norm_eps", "rope_theta",
            "initializer_range")
    return tuple((k, cfg[k]) for k in keys)


def pad_len(n):
    """Sequences are right-padded (causal attention never sees the pad)
    to a few lengths, so that few programs compile."""
    b = 256
    while b < n:
        b *= 2
    return b


def logits_at(cfg, seed, ids, rows, quant=None):
    """Logits [len(rows), vocab] (float32, numpy) of one sequence `ids`
    at positions `rows`: row r predicts token r + 1."""
    cfg_t = _static(cfg)
    key = weights.base_key(seed)
    n = len(ids)
    padded = np.zeros((pad_len(n),), np.int32)
    padded[:n] = ids
    rows_p = np.zeros((pad_len(len(rows)),), np.int32)
    rows_p[:len(rows)] = rows
    x = _embed(jnp.asarray(padded), key, cfg_t)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, key, i, cfg_t, quant)
    out = _head(x, jnp.asarray(rows_p), key, cfg_t, quant)
    return np.asarray(out)[:len(rows)]

"""Plain reference for the Granite-4.0-H family: the forward pass in
`jax.numpy`, float32, matmul precision "highest"; no kernels, no cache,
no batching, no sharding, no chunked scan. It imports nothing of the
program and regenerates its weights from the seed, one layer (and one
expert) at a time.

Written from the published config keys of `granitemoehybrid` and the
Mamba-2 recurrence (arXiv:2405.21060), h in R^hidden, no biases but the
convolution's, RMSNorm eps `rms_norm_eps`:

- block: `h += r * mixer(RMSNorm(h))`, then `h += r * (experts(
  RMSNorm(h)) + shared(RMSNorm(h)))`, r = `residual_multiplier`;
  embedding rows times `embedding_multiplier`; logits = the tied
  embedding applied to the final RMSNorm, over `logits_scaling`.
- Mamba-2 mixer: `[z | xBC | dt] = W_in u`; `xBC' = silu(causal
  depthwise conv(xBC) + b)`; `[x | B | C] = xBC'` (B and C shared by
  the heads of a group); `D_t = softplus(dt + dt_bias)`; `A =
  -exp(A_log)`; a head `H_t = exp(D_t A) H_{t-1} + D_t x_t (x) B_t`,
  `y_t = H_t C_t + D x_t`; out = `W_out RMSNorm_w(y * silu(z))`, the
  gate before the norm, the norm over a group's share of the channels.
  The recurrence is computed as written, by `lax.scan` over tokens.
- attention: causal grouped-query attention, no positional embedding,
  softmax scale `attention_multiplier`.
- experts: `r = W_r h` over the PUBLISHED number of experts; the
  `num_experts_per_tok` largest; gates = softmax over those logits;
  expert e gives `W2_e(silu(W1a_e h) * W1b_e h)`; the shared expert the
  same at its width, ungated, for every token.

Departures from the published model, all stated in the configuration
file: this chip's share (`experts_held` of the router's experts: what
an absent expert would add is left out, here as in the program; a
vocabulary of `vocab_size` rows; `num_hidden_layers` layers); weights
from the seed (`lib/granite_weights.py`), not a checkpoint; no upper
limit on the time step; head size hidden / heads.

`quant="int8"` (or `"fp8"`) computes the same pass with every matmul's
operands rounded to 8 bits (weights per output channel, activations per
token), as `llama_like` does: the control the comparison has to fail.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import granite_weights as gw

F32 = jnp.float32
Q_BLOCK = 1024          # attention is computed in blocks of query rows


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F32(127.0)
    scale = jnp.where(scale > 0, scale, F32(1.0))
    return jnp.round(x / scale) * scale


def _fake_fp8(x, axis):
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


def _f32(tree):
    return {n: a.astype(F32) for n, a in tree.items()}


def _attention(q, k, v, scale):
    """Causal attention, q [T, H, D], k/v [T, Hkv, D], by query blocks."""
    t, h, _ = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for s in range(0, t, Q_BLOCK):
        e = min(t, s + Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", q[s:e], k[:e],
                        precision="highest") * F32(scale)
        ok = (jnp.arange(e)[None, :] <= jnp.arange(s, e)[:, None])
        sc = jnp.where(ok[None], sc, F32(-1e30))
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:e],
                               precision="highest"))
    return jnp.concatenate(outs, 0)


def _attn_mixer(h, w, cfg, quant):
    t = h.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = gw.sizes(cfg)["head_dim"]
    q = _mm(h, w["wq"], quant).reshape(t, nh, d)
    k = _mm(h, w["wk"], quant).reshape(t, nkv, d)
    v = _mm(h, w["wv"], quant).reshape(t, nkv, d)
    a = _attention(q, k, v, cfg["attention_multiplier"])
    return _mm(a.reshape(t, nh * d), w["wo"], quant)


def _mamba_mixer(u, w, cfg, quant):
    """u [T, hidden] -> [T, hidden], the recurrence token by token."""
    s = gw.sizes(cfg)
    t = u.shape[0]
    nh, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n, kc = cfg["mamba_n_groups"], cfg["mamba_d_state"], \
        cfg["mamba_d_conv"]
    zxd = _mm(u, w["in_proj"], quant)
    z = zxd[:, :s["d_inner"]]
    xbc = zxd[:, s["d_inner"]:s["d_inner"] + s["conv"]]
    dt = zxd[:, s["d_inner"] + s["conv"]:]
    # tap k of the convolution multiplies the input d_conv - 1 - k back
    padded = jnp.concatenate([jnp.zeros((kc - 1, s["conv"]), F32), xbc], 0)
    conv = w["conv_b"][None, :] + sum(
        w["conv_w"][k][None, :] * padded[k:k + t] for k in range(kc))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :s["d_inner"]].reshape(t, nh, p)
    bm = xbc[:, s["d_inner"]:s["d_inner"] + g * n].reshape(t, g, n)
    cm = xbc[:, s["d_inner"] + g * n:].reshape(t, g, n)
    bm = jnp.repeat(bm, nh // g, axis=1)                    # [T, heads, n]
    cm = jnp.repeat(cm, nh // g, axis=1)
    step_size = jax.nn.softplus(dt + w["dt_bias"][None, :])  # [T, heads]
    a = -jnp.exp(w["a_log"])

    def step(state, inp):
        x_t, b_t, c_t, d_t = inp
        state = jnp.exp(d_t * a)[:, None, None] * state \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y_t = jnp.einsum("hpn,hn->hp", state, c_t, precision="highest") \
            + x_t                                           # D = 1
        return state, y_t

    _, y = jax.lax.scan(step, jnp.zeros((nh, p, n), F32),
                        (x, bm, cm, step_size), unroll=4)
    gated = (y.reshape(t, s["d_inner"]) * jax.nn.silu(z)).reshape(
        t, g, s["d_inner"] // g)
    normed = _rms(gated, cfg["rms_norm_eps"]).reshape(t, s["d_inner"])
    return _mm(normed, w["out_proj"], quant)


def _swiglu(h, w_in, w_out, quant):
    up = _mm(h, w_in, quant)
    f = w_out.shape[0]
    return _mm(jax.nn.silu(up[:, :f]) * up[:, f:], w_out, quant)


def _experts(h, w, key, index, cfg, quant):
    """The held experts' part of the routed layer, one expert at a
    time, plus the shared expert."""
    k = cfg["num_experts_per_tok"]
    logits = _mm(h, w["router"], quant)            # [T, published experts]
    topv, topi = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(topv, axis=-1)

    def one(acc, e):
        we = _f32(gw.expert(cfg, key, index, e))
        gate_e = jnp.sum(jnp.where(topi == e, gates, F32(0)), axis=-1)
        return acc + gate_e[:, None] * _swiglu(h, we["w_in"], we["w_out"],
                                               quant), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             jnp.asarray(cfg["experts_held"], jnp.int32))
    return routed + _swiglu(h, w["shared_in"], w["shared_out"], quant)


@functools.partial(jax.jit, static_argnames=("kind", "cfg_s", "quant"))
def _layer(x, key, index, kind, cfg_s, quant):
    """Layer `index` (traced: one program a kind of layer)."""
    cfg = json.loads(cfg_s)
    r = F32(cfg["residual_multiplier"])
    h = _rms(x, cfg["rms_norm_eps"])
    w = _f32(gw.mixer(cfg, key, index, kind=kind))
    if kind == "attention":
        x = x + r * _attn_mixer(h, w, cfg, quant)
    else:
        x = x + r * _mamba_mixer(h, w, cfg, quant)
    h = _rms(x, cfg["rms_norm_eps"])
    return x + r * _experts(h, _f32(gw.moe(cfg, key, index)), key, index,
                            cfg, quant)


@functools.partial(jax.jit, static_argnames=("cfg_s",))
def _embed(ids, key, cfg_s):
    cfg = json.loads(cfg_s)
    return gw.top(cfg, key)["embed"].astype(F32)[ids] \
        * F32(cfg["embedding_multiplier"])


@functools.partial(jax.jit, static_argnames=("cfg_s", "quant"))
def _head(x, rows, key, cfg_s, quant):
    cfg = json.loads(cfg_s)
    h = _rms(x[rows], cfg["rms_norm_eps"])
    return _mm(h, gw.top(cfg, key)["embed"].astype(F32).T, quant) \
        / F32(cfg["logits_scaling"])


_KEYS = ("hidden_size", "intermediate_size", "shared_intermediate_size",
         "vocab_size", "num_hidden_layers", "layer_types",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "attention_multiplier", "embedding_multiplier", "logits_scaling",
         "residual_multiplier", "rms_norm_eps", "num_experts_per_tok",
         "experts_held", "published", "mamba_n_heads", "mamba_d_head",
         "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
         "initializer_range", "embedding_initializer_range")


def _static(cfg):
    """The keys the pass reads, as one hashable string."""
    return json.dumps({k: cfg[k] for k in _KEYS if k in cfg},
                      sort_keys=True)


def pad_len(n):
    """Sequences are right-padded (neither causal attention nor the
    recurrence lets a position see what follows it) to a few lengths,
    so that few programs compile."""
    b = 256
    while b < n:
        b *= 2
    return b


def logits_at(cfg, seed, ids, rows, quant=None):
    """Logits [len(rows), vocab] (float32, numpy) of one sequence `ids`
    at positions `rows`: row r predicts token r + 1."""
    cfg_s = _static(cfg)
    key = gw.base_key(seed)
    n = len(ids)
    padded = np.zeros((pad_len(n),), np.int32)
    padded[:n] = ids
    rows_p = np.zeros((pad_len(len(rows)),), np.int32)
    rows_p[:len(rows)] = rows
    x = _embed(jnp.asarray(padded), key, cfg_s)
    for i, kind in enumerate(cfg["layer_types"]):
        x = _layer(x, key, jnp.int32(i), kind, cfg_s, quant)
    out = _head(x, jnp.asarray(rows_p), key, cfg_s, quant)
    return np.asarray(out)[:len(rows)]

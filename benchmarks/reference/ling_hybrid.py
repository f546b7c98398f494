"""Plain reference for the Ling-3.0-flash language model: the forward
pass in `jax.numpy`, float32, matmul precision "highest"; no kernels, no
cache, no batching, no sharding, no chunked recurrence, no absorbed
attention. It imports nothing of the program and regenerates its weights
from the seed, one layer (and one expert) at a time.

Written from the published config keys of `bailing_hybrid`, the KDA
paper (arXiv:2510.26692), DeepSeek-V2 (MLA, arXiv:2405.04434) and
DeepSeek-V3 (routing, arXiv:2412.19437); h in R^hidden, no biases,
RMSNorm eps `rms_norm_eps`, block `h += mixer(RMSNorm(h))`, `h +=
ffn(RMSNorm(h))`:

- layer i is MLA when `(i + 1) % layer_group_size == 0`, else KDA; its
  FFN is a dense SwiGLU for `i < first_k_dense_replace`, else the
  expert layer.
- KDA: `q, k, v = W_q x, W_k x, W_v x`; a causal depthwise convolution
  of `short_conv_kernel_size` taps on each, then SiLU; a head `q <- q /
  |q| / sqrt(d)`, `k <- k / |k|` (|.| with 1e-6 under the root); `beta =
  sigmoid(W_b x)` a head; `g = kda_lower_bound * sigmoid(exp(A_log_h)
  (W_f x + dt_bias))` a head and channel; from `S = 0`, token by token
  (`lax.scan`): `S' = diag(exp(g)) S`, `S = S' + beta k (v - S'^T k)^T`,
  `o = S^T q`; out `W_o (RMSNorm_head(o) * sigmoid(W_g x)_head)`.
- MLA: `q = W_q x` -> heads x [nope | rope]; `[c | k_r] = W_a x`; `c <-
  RMSNorm(c)`; `q_rope`, `k_r` rotated at the position, interleaved
  pairs, `rope_theta`; `[k_nope,h | v_h] = W_b,h c`; scores `(q_nope .
  k_nope + q_rope . k_r) / sqrt(nope + rope)`, causal softmax, `o_h =
  sum p v_h`; out `W_o (o * sigmoid(W_g x)_head)`. Decompressed: every
  head's keys and values are formed.
- experts: `s = sigmoid(W_r y)`; choice scores `s + b`; `n_group` equal
  groups, a group's score the sum of its two largest choice scores, the
  `topk_group` best groups stay; among their experts the
  `num_experts_per_tok` largest choice scores; gates the chosen `s`
  (without `b`) over their sum (`norm_topk_prob`), times
  `routed_scaling_factor`; expert e gives `W2_e(silu(W1a_e y) * W1b_e
  y)`; the shared expert the same, ungated, for every token.
- logits: the untied head on the final RMSNorm.

Departures from the published model, all stated in the configuration
file: this chip's share (`experts_held` of the router's experts: what an
absent expert would add is left out, here as in the program; a
vocabulary of `vocab_size` rows; `num_hidden_layers` layers); weights
from the seed (`lib/ling_weights.py`), not a checkpoint; no multi-token
prediction layer and no clamped SwiGLU (neither lies in these layers).

`quant="int8"` (or `"fp8"`) computes the same pass with every matmul's
operands rounded to 8 bits (weights per output channel, activations per
token), as the other references do: the control the comparison has to
fail. `state_dtype` rounds the KDA state to that type after every token
(a second control: the state is float32 in the program).
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import ling_weights as lw

F32 = jnp.float32
Q_BLOCK = 1024          # attention is computed in blocks of query rows
L2_EPS = 1e-6


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


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + F32(L2_EPS))


def _f32(tree):
    return {n: a.astype(F32) for n, a in tree.items()}


def _rotate(x, pos, theta):
    """x [T, ..., D] at positions pos [T], pairs (2i, 2i + 1)."""
    d = x.shape[-1]
    inv = F32(1.0) / (F32(theta) ** (jnp.arange(0, d, 2, dtype=F32) / F32(d)))
    ang = pos.astype(F32)[:, None] * inv                    # [T, D / 2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    v = x.reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = v[..., 0], v[..., 1]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x2 * jnp.cos(ang) + x1 * jnp.sin(ang)],
                     axis=-1).reshape(x.shape)


def kda_recurrence(q, k, v, g, beta, state_dtype=None):
    """The delta rule as written, token by token. q, k, g [T, H, dk];
    v [T, H, dv]; beta [T, H] -> (o [T, H, dv], final state)."""
    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, :, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                                             precision="highest"))
        s = s + k_t[:, :, None] * u[:, None, :]
        if state_dtype is not None:
            s = s.astype(state_dtype).astype(F32)
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision="highest")

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta), unroll=4)
    return o, s


def _kda_mixer(x, w, cfg, quant, state_dtype):
    s = lw.sizes(cfg)
    t = x.shape[0]
    nh, d, taps = s["heads"], s["head_dim"], s["taps"]

    def conv(a, w_c):
        padded = jnp.concatenate([jnp.zeros((taps - 1, a.shape[1]), F32), a])
        return jax.nn.silu(sum(w_c[j][None, :] * padded[j:j + t]
                               for j in range(taps)))

    w_q, w_k, w_v = jnp.split(w["conv_w"], 3, axis=1)
    heads = lambda a: a.reshape(t, nh, d)
    q = _l2(heads(conv(_mm(x, w["wq"], quant), w_q))) * F32(d ** -0.5)
    k = _l2(heads(conv(_mm(x, w["wk"], quant), w_k)))
    v = heads(conv(_mm(x, w["wv"], quant), w_v))
    beta = jax.nn.sigmoid(_mm(x, w["wb"], quant))           # [T, H]
    a = heads(_mm(x, w["wf"], quant))
    g = F32(cfg["kda_lower_bound"]) * jax.nn.sigmoid(
        jnp.exp(w["a_log"])[None, :, None] * (a + w["dt_bias"][None]))
    o, _ = kda_recurrence(q, k, v, g, beta, state_dtype)
    gate = jax.nn.sigmoid(_mm(x, w["wg"], quant))           # [T, H]
    o = _rms(o, cfg["rms_norm_eps"]) * gate[:, :, None]
    return _mm(o.reshape(t, nh * d), w["wo"], quant)


def _attention(q, k, v, scale):
    """Causal attention, q, k [T, H, D], v [T, H, Dv], by query blocks."""
    t = q.shape[0]
    outs = []
    for s in range(0, t, Q_BLOCK):
        e = min(t, s + Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", q[s:e], k[:e],
                        precision="highest") * F32(scale)
        ok = (jnp.arange(e)[None, :] <= jnp.arange(s, e)[:, None])
        p = jax.nn.softmax(jnp.where(ok[None], sc, F32(-1e30)), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:e],
                               precision="highest"))
    return jnp.concatenate(outs, 0)


def _mla_mixer(x, w, cfg, quant):
    s = lw.sizes(cfg)
    t = x.shape[0]
    nh, dn, dr, dv, r = s["heads"], s["nope"], s["rope"], s["v"], s["rank"]
    pos = jnp.arange(t, dtype=jnp.int32)
    q = _mm(x, w["wq"], quant).reshape(t, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn],
                         _rotate(q[..., dn:], pos, cfg["rope_theta"])], -1)
    ckr = _mm(x, w["wa"], quant)
    c = _rms(ckr[:, :r], cfg["rms_norm_eps"])
    k_r = _rotate(ckr[:, r:], pos, cfg["rope_theta"])
    kv = _mm(c, w["wb"], quant).reshape(t, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_r[:, None, :], (t, nh, dr))], -1)
    o = _attention(q, k, kv[..., dn:], (dn + dr) ** -0.5)
    gate = jax.nn.sigmoid(_mm(x, w["wg"], quant))
    return _mm((o * gate[:, :, None]).reshape(t, nh * dv), w["wo"], quant)


def _swiglu(h, w_in, w_out, quant):
    up = _mm(h, w_in, quant)
    f = w_out.shape[0]
    return _mm(jax.nn.silu(up[:, :f]) * up[:, f:], w_out, quant)


def route(scores, bias, cfg):
    """scores [T, E] = sigmoid(router logits) -> (gates [T, k], expert
    ids [T, k]): the rule written out step by step."""
    t, e = scores.shape
    groups, keep, k = cfg["n_group"], cfg["topk_group"], \
        cfg["num_experts_per_tok"]
    choice = scores + bias[None, :]
    per_group = choice.reshape(t, groups, e // groups)
    two_best = jnp.sort(per_group, axis=-1)[..., -2:].sum(-1)   # [T, groups]
    # the `keep` best groups, ties to the lower group
    order = jnp.argsort(-two_best, axis=-1, stable=True)[:, :keep]
    open_group = jnp.any(order[:, :, None] == jnp.arange(groups)[None, None],
                         axis=1)                                # [T, groups]
    open_expert = jnp.repeat(open_group, e // groups, axis=1)
    masked = jnp.where(open_expert, choice, -jnp.inf)
    ids = jnp.argsort(-masked, axis=-1, stable=True)[:, :k]
    gates = jnp.take_along_axis(scores, ids, axis=1)
    if cfg["norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + F32(1e-20))
    return gates * F32(cfg["routed_scaling_factor"]), ids


def _experts(h, w, key, index, cfg, quant):
    """The held experts' part of the routed layer, one expert at a
    time, plus the shared expert."""
    gates, ids = route(jax.nn.sigmoid(_mm(h, w["router"], quant)),
                       w["bias"], cfg)

    def one(acc, e):
        we = _f32(lw.expert(cfg, key, index, e))
        gate_e = jnp.sum(jnp.where(ids == e, gates, F32(0)), axis=-1)
        return acc + gate_e[:, None] * _swiglu(h, we["w_in"], we["w_out"],
                                               quant), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             jnp.asarray(cfg["experts_held"], jnp.int32))
    return routed + _swiglu(h, w["shared_in"], w["shared_out"], quant)


@functools.partial(jax.jit, static_argnames=("kind", "is_dense", "cfg_s",
                                             "quant", "state_dtype"))
def _layer(x, key, index, kind, is_dense, cfg_s, quant, state_dtype):
    """Layer `index` (traced: one program a kind of layer)."""
    cfg = json.loads(cfg_s)
    h = _rms(x, cfg["rms_norm_eps"])
    w = _f32(lw.mixer(cfg, key, index, kind=kind))
    if kind == "mla":
        x = x + _mla_mixer(h, w, cfg, quant)
    else:
        x = x + _kda_mixer(h, w, cfg, quant, state_dtype)
    h = _rms(x, cfg["rms_norm_eps"])
    if is_dense:
        w = _f32(lw.dense(cfg, key, index))
        return x + _swiglu(h, w["w_in"], w["w_out"], quant)
    return x + _experts(h, _f32(lw.moe(cfg, key, index)), key, index, cfg,
                        quant)


@functools.partial(jax.jit, static_argnames=("cfg_s",))
def _embed(ids, key, cfg_s):
    return lw.top(json.loads(cfg_s), key)["embed"].astype(F32)[ids]


@functools.partial(jax.jit, static_argnames=("cfg_s", "quant"))
def _head(x, rows, key, cfg_s, quant):
    cfg = json.loads(cfg_s)
    return _mm(_rms(x[rows], cfg["rms_norm_eps"]),
               lw.top(cfg, key)["head"].astype(F32), quant)


_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
         "moe_shared_expert_intermediate_size", "vocab_size",
         "num_hidden_layers", "layer_group_size", "first_k_dense_replace",
         "num_attention_heads", "head_dim", "short_conv_kernel_size",
         "kda_lower_bound", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "rope_theta", "num_experts_per_tok",
         "n_group", "topk_group", "routed_scaling_factor", "norm_topk_prob",
         "experts_held", "published", "rms_norm_eps", "initializer_range",
         "router_bias_std")


def _static(cfg):
    """The keys the pass reads, as one hashable string."""
    return json.dumps({k: cfg[k] for k in _KEYS if k in cfg}, sort_keys=True)


def pad_len(n):
    """Sequences are right-padded (neither causal attention nor the
    recurrence lets a position see what follows it) to a few lengths,
    so that few programs compile."""
    b = 256
    while b < n:
        b *= 2
    return b


def hidden_states(cfg, seed, ids, quant=None, state_dtype=None, layers=None):
    """The residual stream [padded length, hidden] after `layers`
    layers (all of them when None) of one sequence `ids`."""
    cfg_s = _static(cfg)
    key = lw.base_key(seed)
    padded = np.zeros((pad_len(len(ids)),), np.int32)
    padded[:len(ids)] = ids
    x = _embed(jnp.asarray(padded), key, cfg_s)
    for i, kind in enumerate(lw.kinds(cfg)[:layers]):
        x = _layer(x, key, jnp.int32(i), kind,
                   i < cfg["first_k_dense_replace"], cfg_s, quant,
                   state_dtype)
    return x


def logits_at(cfg, seed, ids, rows, quant=None, state_dtype=None):
    """Logits [len(rows), vocab] (float32, numpy) of one sequence `ids`
    at positions `rows`: row r predicts token r + 1."""
    x = hidden_states(cfg, seed, ids, quant, state_dtype)
    rows_p = np.zeros((pad_len(len(rows)),), np.int32)
    rows_p[:len(rows)] = rows
    out = _head(x, jnp.asarray(rows_p), lw.base_key(seed), _static(cfg),
                quant)
    return np.asarray(out)[:len(rows)]

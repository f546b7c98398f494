"""Plain reference for Xing4.0 (`xing4_0`): the forward pass in
`jax.numpy`, float32, matmul precision "highest"; the residual streams
float32 throughout; no kernels, no cache, no batching, no sharding, no
absorbed attention. It imports nothing of the program and regenerates
its weights from the seed, one layer (and one expert) at a time.

Written from the published config keys, manifold-constrained hyper-
connections (mHC, arXiv:2512.24880, section 4's parameterisation) over
hyper-connections (arXiv:2409.19606), DeepSeek-V2 (MLA and its YaRN,
arXiv:2405.04434) and DeepSeek-V3 (routing, arXiv:2412.19437). C =
hidden, n = `hc_mult`, no biases, RMSNorm eps `rms_norm_eps`, every
gain 1:

- streams: `X_0 = [Emb(x)] x n` in R^{n x C}; after the last layer `h
  = sum_j X_L[j]`, the final RMSNorm, the untied head.
- a sublayer F (attention, then FFN, each with its one pre-norm and
  maps of its own): `v = vec(X)`, `r = v / sqrt(mean(v^2) + eps)`;
  `H_pre = sigmoid(a_pre (r phi_pre) + b_pre)`, `H_post = 2 sigmoid(
  a_post (r phi_post) + b_post)`, `H_res = Sinkhorn(clip(a_res mat(r
  phi_res) + b_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))`:
  `M = exp(.)`, then `hc_sinkhorn_iters` times every COLUMN over (its
  sum + `hc_eps`), then every ROW over (its sum + `hc_eps`);
  `u = sum_j H_pre[j] X[j]`, `X'[i] = sum_j H_res[i, j] X[j] +
  H_post[i] F(RMSNorm(u))`.
- attention: `c_q = RMSNorm(W_qa x)`; `q = W_qb c_q` -> heads x [nope |
  rope]; `[c | k_r] = W_kva x`; `c <- RMSNorm(c)`; `q_rope`, `k_r`
  rotated at the position, interleaved pairs, YaRN's frequencies
  (`yarn_inv_freq`); `[k_nope,h | v_h] = W_kvb,h c`; scores `(q_nope .
  k_nope + q_rope . k_r) m^2 / sqrt(nope + rope)`, `m = 0.1
  mscale_all_dim ln(factor) + 1`; causal softmax; `W_o concat(o)`.
- FFN: dense SwiGLU for `i < first_k_dense_replace`; else `s =
  sigmoid(W_r y)`, the `num_experts_per_tok` largest of `s + b` (ties
  to the lower id), gates the chosen `s` over their sum
  (`norm_topk_prob`) times `routed_scaling_factor`; expert e gives
  `W2_e(silu(W1a_e y) * W1b_e y)`; the shared expert the same, ungated.

Departures from the published model, all stated in the configuration
file: `num_hidden_layers` layers of which `first_k_dense_replace`
dense; weights from the seed (`lib/xing_weights.py`), not a checkpoint;
and what the config has no key for (`assumed`). The multi-token-
prediction layer is not built.

`quant="int8"` (or `"fp8"`) computes the same pass with every matmul's
operands rounded to 8 bits (weights per output channel, activations per
token; the maps' 2n + n^2 dot products among them), as the other
references do: the control the comparison has to fail.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import xing_weights as xw

F32 = jnp.float32
Q_BLOCK = 256           # attention by blocks of query rows


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


def yarn_inv_freq(cfg):
    """(lo, hi, the rope / 2 frequencies): `f_i = theta^(-2i / d)` below
    pair `lo`, `f_i / factor` above `hi`, the linear blend between; lo
    and hi are the pairs that turn `beta_fast` and `beta_slow` times
    over the original context."""
    s, d, theta = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    pair = lambda turns: d * math.log(
        s["original_max_position_embeddings"] / (2 * math.pi * turns)) \
        / (2 * math.log(theta))
    lo = max(math.floor(pair(s["beta_fast"])), 0)
    hi = min(math.ceil(pair(s["beta_slow"])), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return lo, hi, (f * (1 - ramp) + f / s["factor"] * ramp).astype(
        np.float32)


def score_scale(cfg):
    """`m(mscale_all_dim)^2 / sqrt(nope + rope)`."""
    s = cfg["rope_scaling"]
    m = 0.1 * s["mscale_all_dim"] * math.log(s["factor"]) + 1.0
    return m * m / math.sqrt(cfg["qk_nope_head_dim"]
                             + cfg["qk_rope_head_dim"])


def _rotate(x, inv_freq):
    """x [T, ..., D] at positions 0..T-1, pairs (2i, 2i + 1)."""
    t, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(t, dtype=F32)[:, None] * jnp.asarray(inv_freq, F32)
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    v = x.reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = v[..., 0], v[..., 1]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x2 * jnp.cos(ang) + x1 * jnp.sin(ang)],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, scale):
    """q, k [T, H, D], v [T, H, Dv] -> [T, H, Dv], causal, by blocks of
    query rows (T is whole blocks, or shorter than one)."""
    t, h, _ = q.shape
    n = min(Q_BLOCK, t)
    pos = jnp.arange(t, dtype=jnp.int32)

    def block(start):
        rows = start + jnp.arange(n, dtype=jnp.int32)
        sc = jnp.einsum("qhd,khd->hqk",
                        jax.lax.dynamic_slice_in_dim(q, start, n), k,
                        precision="highest") * F32(scale)
        sc = jnp.where((pos[None, :] <= rows[:, None])[None], sc, F32(-1e30))
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                          precision="highest")

    return jax.lax.map(block, jnp.arange(0, t, n, dtype=jnp.int32)).reshape(
        t, h, v.shape[-1])


def attention_layer(x, w, cfg, quant):
    """x = RMSNorm(u) [T, hidden] -> W_o o [T, hidden]."""
    s = xw.sizes(cfg)
    t = x.shape[0]
    nh, dn, dr, dv, r = s["heads"], s["nope"], s["rope"], s["v"], s["rank"]
    eps, inv = cfg["rms_norm_eps"], yarn_inv_freq(cfg)[2]
    c_q = _rms(_mm(x, w["wqa"], quant), eps)
    q = _mm(c_q, w["wqb"], quant).reshape(t, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], inv)], -1)
    ckr = _mm(x, w["wkva"], quant)
    c = _rms(ckr[:, :r], eps)
    k_r = _rotate(ckr[:, r:], inv)
    kv = _mm(c, w["wkvb"], quant).reshape(t, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_r[:, None, :], (t, nh, dr))], -1)
    o = _attention(q, k, kv[..., dn:], score_scale(cfg))
    return _mm(o.reshape(t, nh * dv), w["wo"], quant)


def _swiglu(h, w_in, w_out, quant):
    up = _mm(h, w_in, quant)
    f = w_out.shape[0]
    return _mm(jax.nn.silu(up[:, :f]) * up[:, f:], w_out, quant)


def route(scores, bias, cfg):
    """scores [T, E] = sigmoid(router logits), bias [E] -> (gates [T,
    k], expert ids [T, k]): the k largest of scores + bias, ties to the
    lower id; the gates are the chosen SCORES."""
    ids = jnp.argsort(-(scores + bias[None, :]), axis=-1, stable=True)[
        :, :cfg["num_experts_per_tok"]]
    gates = jnp.take_along_axis(scores, ids, axis=1)
    if cfg["norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + F32(1e-20))
    return gates * F32(cfg["routed_scaling_factor"]), ids


def routed_part(h, w, key, index, cfg, quant, held=None):
    """The part of the routed layer that the experts in `held` (all of
    them when None) give, one expert at a time."""
    gates, ids = route(jax.nn.sigmoid(_mm(h, w["router"], quant)),
                       w["bias"], cfg)

    def one(acc, e):
        we = _f32(xw.expert(cfg, key, index, e))
        gate_e = jnp.sum(jnp.where(ids == e, gates, F32(0)), axis=-1)
        return acc + gate_e[:, None] * _swiglu(h, we["w_in"], we["w_out"],
                                               quant), None

    held = xw.held(cfg) if held is None else held
    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             jnp.asarray(held, jnp.int32))
    return routed


def experts_layer(h, w, key, index, cfg, quant, held=None):
    """The held experts' part plus the shared expert."""
    return routed_part(h, w, key, index, cfg, quant, held) \
        + _swiglu(h, w["shared_in"], w["shared_out"], quant)


def sinkhorn(m, iters, hc_eps):
    """m [T, n, n] positive -> after exactly `iters` sweeps: every
    column over (its sum + hc_eps), then every row over (its sum +
    hc_eps)."""
    for _ in range(iters):
        m = m / (m.sum(axis=1, keepdims=True) + F32(hc_eps))
        m = m / (m.sum(axis=2, keepdims=True) + F32(hc_eps))
    return m


def maps(streams, w, cfg, quant=None):
    """streams [T, n, C] -> (H_pre [T, n], H_post [T, n], H_res [T, n,
    n])."""
    t, n, _ = streams.shape
    r = _rms(streams.reshape(t, -1), cfg["rms_norm_eps"])
    z = _mm(r, w["phi"], quant)
    a, b = w["a"], w["b"]
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = F32(2.0) * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    res = jnp.clip(a[2] * z[:, 2 * n:] + b[2 * n:],
                   F32(cfg["mhc_h_res_clamp_min"]),
                   F32(cfg["mhc_h_res_clamp_max"])).reshape(t, n, n)
    return pre, post, sinkhorn(jnp.exp(res), cfg["hc_sinkhorn_iters"],
                               cfg["hc_eps"])


def sublayer(streams, w_maps, cfg, quant, fn):
    """`X' = H_res X + H_post^T F(RMSNorm(sum_j H_pre[j] X[j]))`."""
    pre, post, res = maps(streams, w_maps, cfg, quant)
    u = jnp.einsum("tj,tjc->tc", pre, streams, precision="highest")
    f = fn(_rms(u, cfg["rms_norm_eps"]))
    return jnp.einsum("tij,tjc->tic", res, streams, precision="highest") \
        + post[:, :, None] * f[:, None, :]


@functools.partial(jax.jit, static_argnames=("kind", "cfg_s", "sub"))
def _weights(key, index, kind, cfg_s, sub=None):
    """A layer's attention, dense, router-and-shared-expert matrices or
    one sublayer's maps, as stored, made by a program of their own:
    drawn inside the layer's program at the published widths, the draws'
    temporaries and the layer's activations did not fit the chip
    together (PERF.md section 6, PR 41)."""
    args = () if sub is None else (sub,)
    return getattr(xw, kind)(json.loads(cfg_s), key, index, *args)


@functools.partial(jax.jit, static_argnames=("cfg_s", "quant"))
def _attend(streams, w_maps, w, cfg_s, quant):
    cfg = json.loads(cfg_s)
    return sublayer(streams, _f32(w_maps), cfg, quant,
                    lambda x: attention_layer(x, _f32(w), cfg, quant))


@functools.partial(jax.jit, static_argnames=("is_dense", "cfg_s", "quant"))
def _feed_forward(streams, w_maps, w, key, index, is_dense, cfg_s, quant):
    """The experts' banks are drawn one expert at a time inside."""
    cfg = json.loads(cfg_s)
    w = _f32(w)
    fn = (lambda h: _swiglu(h, w["w_in"], w["w_out"], quant)) if is_dense \
        else (lambda h: experts_layer(h, w, key, index, cfg, quant))
    return sublayer(streams, _f32(w_maps), cfg, quant, fn)


def _layer(streams, key, index, is_dense, cfg_s, quant):
    """Layer `index` (traced: one program a kind of layer)."""
    streams = _attend(
        streams, _weights(key, index, "mhc", cfg_s, sub="attn"),
        _weights(key, index, "attn", cfg_s), cfg_s, quant)
    return _feed_forward(
        streams, _weights(key, index, "mhc", cfg_s, sub="ffn"),
        _weights(key, index, "dense" if is_dense else "moe", cfg_s), key,
        index, is_dense, cfg_s, quant)


@functools.partial(jax.jit, static_argnames=("cfg_s",))
def _embed(ids, key, cfg_s):
    """The embedding, copied into every stream: [T, n, C]."""
    cfg = json.loads(cfg_s)
    e = xw.top(cfg, key)["embed"].astype(F32)[ids]
    return jnp.broadcast_to(e[:, None, :],
                            (e.shape[0], cfg["hc_mult"], e.shape[1]))


@functools.partial(jax.jit, static_argnames=("cfg_s", "quant"))
def _head(streams, rows, key, cfg_s, quant):
    cfg = json.loads(cfg_s)
    return _mm(_rms(streams[rows].sum(axis=1), cfg["rms_norm_eps"]),
               xw.top(cfg, key)["head"].astype(F32), quant)


_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
         "vocab_size", "num_hidden_layers", "first_k_dense_replace",
         "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
         "rope_scaling", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
         "mhc_h_res_clamp_min", "mhc_h_res_clamp_max", "n_routed_experts",
         "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor",
         "norm_topk_prob", "experts_held", "rms_norm_eps",
         "initializer_range", "router_bias_std", "embed_std")


def _static(cfg):
    """The keys the pass reads, as one hashable string."""
    return json.dumps({k: cfg[k] for k in _KEYS if k in cfg}, sort_keys=True)


def pad_len(n):
    """Sequences are right-padded (a position sees nothing that follows
    it) to powers of two from 256, so that few programs compile."""
    b = 256
    while b < n:
        b *= 2
    return b


def _padded(ids):
    out = np.zeros((pad_len(len(ids)),), np.int32)
    out[:len(ids)] = ids
    return jnp.asarray(out)


def streams_after(cfg, seed, ids, quant=None, layers=None):
    """The streams [padded length, n, C] after `layers` layers (all of
    them when None) of one sequence `ids`."""
    cfg_s = _static(cfg)
    key = xw.base_key(seed)
    x = _embed(_padded(ids), key, cfg_s)
    n = cfg["num_hidden_layers"] if layers is None else layers
    for i in range(n):
        x = _layer(x, key, jnp.int32(i), i < cfg["first_k_dense_replace"],
                   cfg_s, quant)
    return x


def logits_at(cfg, seed, ids, rows, quant=None):
    """Logits [len(rows), vocab] (float32, numpy) of one sequence `ids`
    at positions `rows`: row r predicts token r + 1."""
    with jax.default_matmul_precision("highest"):
        x = streams_after(cfg, seed, ids, quant)
        out = _head(x, _padded(rows), xw.base_key(seed), _static(cfg), quant)
        return np.asarray(out)[:len(rows)]

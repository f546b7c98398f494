"""Plain reference for openPangu-Ultra-MoE (`pangu_ultra_moe`): the
forward pass of the trunk and of its multi-token-prediction module in
`jax.numpy`, float32, matmul precision "highest"; no kernels, no cache,
no batching, no sharding, no absorbed attention, no span. It imports
nothing of the program and regenerates its weights from the seed, one
layer (and one expert) at a time.

Written from the published config keys, the Pangu Ultra MoE report
(sandwich norm, arXiv:2505.04519), DeepSeek-V2 (MLA, arXiv:2405.04434)
and DeepSeek-V3 (routing; MTP, section 2.2, arXiv:2412.19437); h in
R^hidden, no biases, RMSNorm eps `rms_norm_eps`, every gain 1:

- block: `h += RMSNorm(Attn(RMSNorm(h)))`, `h += RMSNorm(FFN(
  RMSNorm(h)))`: four norms a layer.
- `c_q = RMSNorm(W_qa x)`; `q = W_qb c_q` -> heads x [nope | rope];
  `[c | k_r] = W_kva x`; `c <- RMSNorm(c)`; `q_rope`, `k_r` rotated at
  the position, interleaved pairs, `rope_theta`; `[k_nope,h | v_h] =
  W_kvb,h c`. Decompressed: every head's keys and values are formed.
- scores `(q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)`, causal
  softmax over all earlier tokens, `o_h = sum p v_h`; out `W_o
  concat(o)`.
- FFN: dense SwiGLU for `i < first_k_dense_replace`; else `s =
  sigmoid(W_r y)`, the `num_experts_per_tok` largest (ties to the lower
  id), gates the chosen `s` over their sum (`norm_topk_prob`), times
  `routed_scaling_factor`; expert e gives `W2_e(silu(W1a_e y) * W1b_e
  y)`; the shared expert the same, ungated, for every token.
- logits: the untied head on the final RMSNorm.
- MTP: `u_i = W_eh [RMSNorm(Emb(x_{i+1})) ; RMSNorm(h_i)]`, `h_i` the
  last layer's output before the final norm; one further expert layer
  over `u` (causal, positions as the trunk's); draft logits `W_head
  RMSNorm(.)` at row i predict `x_{i+2}`.

Departures from the published model, all stated in the configuration
file: this chip's share (`experts_held` of the router's experts: what an
absent expert would add is left out, here as in the program; a
vocabulary of `vocab_size` rows; `num_hidden_layers` layers of which
`first_k_dense_replace` dense); weights from the seed
(`lib/pangu_weights.py`), not a checkpoint; and what the config has no
key for (`assumed`).

`quant="int8"` (or `"fp8"`) computes the same pass with every matmul's
operands rounded to 8 bits (weights per output channel, activations per
token), as the other references do: the control the comparison has to
fail.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import pangu_weights as pw

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


def _rotate(x, theta):
    """x [T, ..., D] at positions 0..T-1, pairs (2i, 2i + 1)."""
    t, d = x.shape[0], x.shape[-1]
    inv = F32(1.0) / (F32(theta) ** (jnp.arange(0, d, 2, dtype=F32) / F32(d)))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv                # [T, D / 2]
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
    """x = RMSNorm(h) [T, hidden] -> W_o o [T, hidden]."""
    s = pw.sizes(cfg)
    t = x.shape[0]
    nh, dn, dr, dv, r = s["heads"], s["nope"], s["rope"], s["v"], s["rank"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    c_q = _rms(_mm(x, w["wqa"], quant), eps)
    q = _mm(c_q, w["wqb"], quant).reshape(t, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], theta)], -1)
    ckr = _mm(x, w["wkva"], quant)
    c = _rms(ckr[:, :r], eps)
    k_r = _rotate(ckr[:, r:], theta)
    kv = _mm(c, w["wkvb"], quant).reshape(t, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_r[:, None, :], (t, nh, dr))], -1)
    o = _attention(q, k, kv[..., dn:], (dn + dr) ** -0.5)
    return _mm(o.reshape(t, nh * dv), w["wo"], quant)


def _swiglu(h, w_in, w_out, quant):
    up = _mm(h, w_in, quant)
    f = w_out.shape[0]
    return _mm(jax.nn.silu(up[:, :f]) * up[:, f:], w_out, quant)


def route(scores, cfg):
    """scores [T, E] = sigmoid(router logits) -> (gates [T, k], expert
    ids [T, k]): the k largest, ties to the lower id."""
    ids = jnp.argsort(-scores, axis=-1, stable=True)[
        :, :cfg["num_experts_per_tok"]]
    gates = jnp.take_along_axis(scores, ids, axis=1)
    if cfg["norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + F32(1e-20))
    return gates * F32(cfg["routed_scaling_factor"]), ids


def routed_part(h, w, key, index, cfg, quant, held=None):
    """The part of the routed layer that the experts in `held` (the
    configuration's `experts_held`) give, one expert at a time."""
    gates, ids = route(jax.nn.sigmoid(_mm(h, w["router"], quant)), cfg)

    def one(acc, e):
        we = _f32(pw.expert(cfg, key, index, e))
        gate_e = jnp.sum(jnp.where(ids == e, gates, F32(0)), axis=-1)
        return acc + gate_e[:, None] * _swiglu(h, we["w_in"], we["w_out"],
                                               quant), None

    held = cfg["experts_held"] if held is None else held
    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             jnp.asarray(held, jnp.int32))
    return routed


def experts_layer(h, w, key, index, cfg, quant, held=None):
    """The held experts' part plus the shared expert."""
    return routed_part(h, w, key, index, cfg, quant, held) \
        + _swiglu(h, w["shared_in"], w["shared_out"], quant)


@functools.partial(jax.jit, static_argnames=("kind", "cfg_s"))
def _weights(key, index, kind, cfg_s):
    """A layer's attention, dense or router-and-shared-expert matrices,
    as stored (bfloat16), made by a program of their own: drawn inside
    the layer's program at these widths, the draws' temporaries and the
    layer's activations did not fit the chip together."""
    return getattr(pw, kind)(json.loads(cfg_s), key, index)


@functools.partial(jax.jit, static_argnames=("cfg_s", "quant"))
def _attend(x, w, cfg_s, quant):
    """h + RMSNorm(Attn(RMSNorm(h)))."""
    cfg = json.loads(cfg_s)
    eps = cfg["rms_norm_eps"]
    return x + _rms(attention_layer(_rms(x, eps), _f32(w), cfg, quant), eps)


@functools.partial(jax.jit, static_argnames=("is_dense", "cfg_s", "quant"))
def _feed_forward(x, w, key, index, is_dense, cfg_s, quant):
    """h + RMSNorm(FFN(RMSNorm(h))); the experts' banks are drawn one
    expert at a time inside."""
    cfg = json.loads(cfg_s)
    eps = cfg["rms_norm_eps"]
    h, w = _rms(x, eps), _f32(w)
    y = _swiglu(h, w["w_in"], w["w_out"], quant) if is_dense \
        else experts_layer(h, w, key, index, cfg, quant)
    return x + _rms(y, eps)


def _layer(x, key, index, is_dense, cfg_s, quant):
    """Layer `index` (traced: one program a kind of layer), sandwich
    norm."""
    x = _attend(x, _weights(key, index, "attn", cfg_s), cfg_s, quant)
    return _feed_forward(
        x, _weights(key, index, "dense" if is_dense else "moe", cfg_s), key,
        index, is_dense, cfg_s, quant)


@functools.partial(jax.jit, static_argnames=("cfg_s",))
def _embed(ids, key, cfg_s):
    return pw.top(json.loads(cfg_s), key)["embed"].astype(F32)[ids]


@functools.partial(jax.jit, static_argnames=("cfg_s", "quant"))
def _join(x, emb_next, key, cfg_s, quant):
    """u = W_eh [RMSNorm(Emb(x_{i+1})) ; RMSNorm(h_i)]."""
    cfg = json.loads(cfg_s)
    eps = cfg["rms_norm_eps"]
    return _mm(jnp.concatenate([_rms(emb_next, eps), _rms(x, eps)], -1),
               pw.mtp_join(cfg, key).astype(F32), quant)


@functools.partial(jax.jit, static_argnames=("cfg_s", "quant"))
def _head(x, rows, key, cfg_s, quant):
    cfg = json.loads(cfg_s)
    return _mm(_rms(x[rows], cfg["rms_norm_eps"]),
               pw.top(cfg, key)["head"].astype(F32), quant)


_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
         "vocab_size", "num_hidden_layers", "first_k_dense_replace",
         "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
         "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor",
         "norm_topk_prob", "experts_held", "published", "rms_norm_eps",
         "initializer_range")


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


def _padded(ids, n=None):
    out = np.zeros((pad_len(len(ids) if n is None else n),), np.int32)
    out[:len(ids)] = ids
    return jnp.asarray(out)


_LAST = [None, None]    # the last sequence's trunk: both logits read it


def hidden_states(cfg, seed, ids, quant=None, layers=None):
    """The residual stream [padded length, hidden] after `layers`
    layers (all of them when None) of one sequence `ids`: the last
    layer's output, before the final norm. The last call's result is
    kept: `logits_at` and `draft_logits_at` of one sequence share it."""
    cfg_s = _static(cfg)
    tag = (cfg_s, int(seed), tuple(int(t) for t in ids), quant, layers)
    if _LAST[0] == tag:
        return _LAST[1]
    key = pw.base_key(seed)
    x = _embed(_padded(ids), key, cfg_s)
    n = cfg["num_hidden_layers"] if layers is None else layers
    for i in range(n):
        x = _layer(x, key, jnp.int32(i), i < cfg["first_k_dense_replace"],
                   cfg_s, quant)
    _LAST[:] = tag, x
    return x


def _rows_of(x, rows, seed, cfg, quant):
    out = _head(x, _padded(rows), pw.base_key(seed), _static(cfg), quant)
    return np.asarray(out)[:len(rows)]


def logits_at(cfg, seed, ids, rows, quant=None):
    """Logits [len(rows), vocab] (float32, numpy) of one sequence `ids`
    at positions `rows`: row r predicts token r + 1."""
    return _rows_of(hidden_states(cfg, seed, ids, quant), rows, seed, cfg,
                    quant)


def draft_logits_at(cfg, seed, ids, rows, quant=None):
    """The MTP module's logits [len(rows), vocab] of one sequence `ids`,
    teacher-forced: row r, from the trunk's output at r and token r + 1,
    predicts token r + 2. Every r is under len(ids) - 1."""
    if len(rows) and max(rows) > len(ids) - 2:
        raise ValueError("row r of the draft logits needs token r + 1")
    cfg_s = _static(cfg)
    key = pw.base_key(seed)
    x = hidden_states(cfg, seed, ids, quant)
    nxt = _embed(_padded(list(ids[1:]), len(ids)), key, cfg_s)
    u = _join(x, nxt, key, cfg_s, quant)
    g = _layer(u, key, jnp.int32(pw.MTP_LAYER), False, cfg_s, quant)
    return _rows_of(g, rows, seed, cfg, quant)

"""Plain reference for the GLM-5 language model (`glm_moe_dsa`): the
forward pass in `jax.numpy`, float32, matmul precision "highest"; no
kernels, no cache, no batching, no sharding, no absorbed attention, no
chunk plan. It imports nothing of the program and regenerates its
weights from the seed, one layer (and one expert) at a time.

Written from the published config keys, DeepSeek-V2 (MLA,
arXiv:2405.04434), DeepSeek-V3 (routing, arXiv:2412.19437) and the
DeepSeek-V3.2 description of the indexer; h in R^hidden, no biases but
the index key's LayerNorm, RMSNorm eps `rms_norm_eps`, block `h +=
attn(RMSNorm(h))`, `h += ffn(RMSNorm(h))`, x = RMSNorm(h):

- `c_q = RMSNorm(W_qa x)`; `q = W_qb c_q` -> heads x [nope | rope];
  `[c | k_r] = W_kva x`; `c <- RMSNorm(c)`; `q_rope`, `k_r` rotated at
  the position, interleaved pairs, `rope_theta`; `[k_nope,h | v_h] =
  W_kvb,h c`. Decompressed: every head's keys and values are formed.
- indexer: `qI = W_iq c_q` (J heads x Di), `kI = LayerNorm(W_ik x)`
  (eps `index_norm_eps`), the first `qk_rope_head_dim` numbers of each
  rotated as above; `w = W_iw x / sqrt(J)`; `I[t, s] = sum_j w[t, j]
  relu(qI[t, j] . kI[s]) / sqrt(Di)`, s <= t.
- `S_t` = the min(index_topk, t + 1) keys of largest `I[t, s]`, ties to
  the lower s (a plain sort of each row); one set a token for all heads.
- scores `(q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)`,
  softmax over `S_t`, `o_h = sum p v_h`; out `W_o concat(o)`.
- FFN: dense SwiGLU for `i < first_k_dense_replace`; else `s =
  sigmoid(W_r y)`, choice scores `s + b`, `n_group` equal groups of
  which the `topk_group` best (by the sum of a group's two largest
  choice scores) stay (1 of 1 as published: none is closed), the
  `num_experts_per_tok` largest choice scores; gates the chosen `s`
  over their sum (`norm_topk_prob`), times `routed_scaling_factor`;
  expert e gives `W2_e(silu(W1a_e y) * W1b_e y)`; the shared expert the
  same, ungated, for every token.
- logits: the untied head on the final RMSNorm.

Departures from the published model, all stated in the configuration
file: this chip's share (`experts_held` of the router's experts: what an
absent expert would add is left out, here as in the program; a
vocabulary of `vocab_size` rows; `num_hidden_layers` layers of which
`first_k_dense_replace` dense); weights from the seed
(`lib/glm_weights.py`), not a checkpoint; no multi-token prediction
layer; the indexer's Hadamard rotation of qI and kI (orthogonal on both
sides: it changes no score) and their fp8 rounding (a quantisation this
bfloat16 configuration does not apply) are left out.

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

from benchmarks.lib import glm_weights as gw

F32 = jnp.float32
Q_BLOCK = 256           # I, the selection and attention by query rows
PAD_STEP = 4096         # sequences are padded to whole steps past it


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


def _layer_norm(x, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return _rms(x, eps)


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


def _rotate_head(x, width, theta):
    """The first `width` numbers of x's last axis rotated."""
    return jnp.concatenate([_rotate(x[..., :width], theta), x[..., width:]],
                           axis=-1)


def _row_blocks(t):
    """Start of each block of Q_BLOCK query rows (T is whole blocks, or
    shorter than one)."""
    return jnp.arange(0, t, min(Q_BLOCK, t), dtype=jnp.int32)


def _rows(a, start):
    return jax.lax.dynamic_slice_in_dim(a, start, min(Q_BLOCK, a.shape[0]))


def index_scores(qi, w, ki):
    """I [T, T] (float32; entries above the diagonal are not used), by
    blocks of query rows. qi [T, J, Di]; w [T, J]; ki [T, Di]."""
    def block(start):
        dots = jnp.einsum("tjd,sd->tjs", _rows(qi, start), ki,
                          precision="highest")
        return jnp.sum(_rows(w, start)[:, :, None] * jax.nn.relu(dots),
                       axis=1) / jnp.sqrt(F32(ki.shape[-1]))
    return jax.lax.map(block, _row_blocks(qi.shape[0])).reshape(
        qi.shape[0], -1)


def selected(scores, topk):
    """S_t as a mask [T, T]: row t keeps the min(topk, t + 1) keys s <=
    t of largest score, ties to the lower s. By a plain sort of each
    row: the topk-th largest value is the threshold, every key above it
    is kept, and the keys that equal it are kept from the lowest
    position up until the row has topk."""
    t = scores.shape[0]
    k = min(int(topk), t)
    pos = jnp.arange(t, dtype=jnp.int32)

    def block(start):
        rows = start + jnp.arange(min(Q_BLOCK, t), dtype=jnp.int32)
        causal = pos[None, :] <= rows[:, None]
        s = jnp.where(causal, _rows(scores, start), -jnp.inf)
        kth = jnp.sort(s, axis=-1)[:, t - k][:, None]     # -inf: keep all
        above = s > kth
        tied = (s == kth) & causal
        room = k - jnp.sum(above, axis=-1, keepdims=True)
        return above | (tied & (jnp.cumsum(tied, axis=-1) <= room))

    return jax.lax.map(block, _row_blocks(t)).reshape(t, t)


def _attention(q, k, v, keep, scale):
    """q, k [T, H, D], v [T, H, Dv], keep [T, T] -> [T, H, Dv]."""
    t, h, _ = q.shape

    def block(start):
        sc = jnp.einsum("qhd,khd->hqk", _rows(q, start), k,
                        precision="highest") * F32(scale)
        sc = jnp.where(_rows(keep, start)[None], sc, F32(-1e30))
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                          precision="highest")

    return jax.lax.map(block, _row_blocks(t)).reshape(t, h, v.shape[-1])


def attention_layer(x, w, cfg, quant, with_selection=False):
    """x = RMSNorm(h) [T, hidden] -> W_o o [T, hidden]."""
    s = gw.sizes(cfg)
    t = x.shape[0]
    nh, dn, dr, dv, r = s["heads"], s["nope"], s["rope"], s["v"], s["rank"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_parameters"]["rope_theta"]
    c_q = _rms(_mm(x, w["wqa"], quant), eps)
    q = _mm(c_q, w["wqb"], quant).reshape(t, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], theta)], -1)
    ckr = _mm(x, w["wkva"], quant)
    c = _rms(ckr[:, :r], eps)
    k_r = _rotate(ckr[:, r:], theta)
    kv = _mm(c, w["wkvb"], quant).reshape(t, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_r[:, None, :], (t, nh, dr))], -1)
    qi = _rotate_head(_mm(c_q, w["wiq"], quant).reshape(
        t, s["index_heads"], s["index_dim"]), dr, theta)
    ki = _rotate_head(_layer_norm(_mm(x, w["wik"], quant),
                                  cfg["index_norm_eps"]), dr, theta)
    a = _mm(x, w["wiw"], quant) / jnp.sqrt(F32(s["index_heads"]))
    keep = selected(index_scores(qi, a, ki), cfg["index_topk"])
    o = _attention(q, k, kv[..., dn:], keep, (dn + dr) ** -0.5)
    out = _mm(o.reshape(t, nh * dv), w["wo"], quant)
    return (out, keep) if with_selection else out


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


def routed_part(h, w, key, index, cfg, quant, held=None):
    """The part of the routed layer that the experts in `held` (the
    configuration's `experts_held`) give, one expert at a time."""
    gates, ids = route(jax.nn.sigmoid(_mm(h, w["router"], quant)),
                       w["bias"], cfg)

    def one(acc, e):
        we = _f32(gw.expert(cfg, key, index, e))
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


@functools.partial(jax.jit, static_argnames=("is_dense", "cfg_s", "quant"))
def _layer(x, key, index, is_dense, cfg_s, quant):
    """Layer `index` (traced: one program a kind of layer)."""
    cfg = json.loads(cfg_s)
    x = x + attention_layer(_rms(x, cfg["rms_norm_eps"]),
                            _f32(gw.attn(cfg, key, index)), cfg, quant)
    h = _rms(x, cfg["rms_norm_eps"])
    if is_dense:
        w = _f32(gw.dense(cfg, key, index))
        return x + _swiglu(h, w["w_in"], w["w_out"], quant)
    return x + experts_layer(h, _f32(gw.moe(cfg, key, index)), key, index,
                             cfg, quant)


@functools.partial(jax.jit, static_argnames=("cfg_s",))
def _embed(ids, key, cfg_s):
    return gw.top(json.loads(cfg_s), key)["embed"].astype(F32)[ids]


@functools.partial(jax.jit, static_argnames=("cfg_s", "quant"))
def _head(x, rows, key, cfg_s, quant):
    cfg = json.loads(cfg_s)
    return _mm(_rms(x[rows], cfg["rms_norm_eps"]),
               gw.top(cfg, key)["head"].astype(F32), quant)


_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
         "vocab_size", "num_hidden_layers", "first_k_dense_replace",
         "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "rope_parameters", "index_n_heads", "index_head_dim", "index_topk",
         "index_norm_eps", "n_shared_experts", "num_experts_per_tok",
         "n_group", "topk_group", "routed_scaling_factor", "norm_topk_prob",
         "experts_held", "published", "rms_norm_eps", "initializer_range",
         "router_bias_std", "embed_std")


def _static(cfg):
    """The keys the pass reads, as one hashable string."""
    return json.dumps({k: cfg[k] for k in _KEYS if k in cfg}, sort_keys=True)


def pad_len(n):
    """Sequences are right-padded (a position sees nothing that follows
    it, and scores nothing that follows it) to a few lengths, so that
    few programs compile: powers of two up to PAD_STEP, whole steps of
    it after (the cost grows with the square of the length)."""
    b = 256
    while b < min(n, PAD_STEP):
        b *= 2
    return b if n <= b else -(-n // PAD_STEP) * PAD_STEP


def hidden_states(cfg, seed, ids, quant=None, layers=None):
    """The residual stream [padded length, hidden] after `layers`
    layers (all of them when None) of one sequence `ids`."""
    cfg_s = _static(cfg)
    key = gw.base_key(seed)
    padded = np.zeros((pad_len(len(ids)),), np.int32)
    padded[:len(ids)] = ids
    x = _embed(jnp.asarray(padded), key, cfg_s)
    n = cfg["num_hidden_layers"] if layers is None else layers
    for i in range(n):
        x = _layer(x, key, jnp.int32(i), i < cfg["first_k_dense_replace"],
                   cfg_s, quant)
    return x


def logits_at(cfg, seed, ids, rows, quant=None):
    """Logits [len(rows), vocab] (float32, numpy) of one sequence `ids`
    at positions `rows`: row r predicts token r + 1."""
    x = hidden_states(cfg, seed, ids, quant)
    rows_p = np.zeros((pad_len(len(rows)),), np.int32)
    rows_p[:len(rows)] = rows
    out = _head(x, jnp.asarray(rows_p), gw.base_key(seed), _static(cfg),
                quant)
    return np.asarray(out)[:len(rows)]

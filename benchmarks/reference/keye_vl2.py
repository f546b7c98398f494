"""Plain reference for the Keye-VL-2.0 language model: the forward pass
in `jax.numpy`, float32, matmul precision "highest"; no kernels, no
cache, no batching, no chunked selection. It imports nothing of the
program and regenerates its weights from the seed, one layer (and one
expert) at a time.

Written from the published config keys (`sa_config` for the indexer), h
in R^hidden, no bias anywhere, RMSNorm eps `rms_norm_eps`, x =
RMSNorm(h):

- projections: `q = W_q x`, `k = W_k x`, `v = W_v x`; RMSNorm (gain 1)
  over head_dim on every q head and k head; RoPE at the token's
  position over all of head_dim, pairs (i, i + head_dim / 2), theta
  `rope_theta`.
- indexer: `qI = W_qI x` (index heads x index dim), `kI =
  LayerNorm(W_kI x)` (one key a token), `a = W_a x`; both rotated at
  the position over the index dim. `I[t, s] = sum_j a[t, j] relu(qI[t,
  j] . kI[s])` for s <= t: the whole [T, T] matrix, built in row blocks.
- selection: `S_t` = the min(topk, t + 1) keys of largest `I[t, s]`,
  ties to the lower s (each row sorted: its topk-th largest value is
  the threshold, ties at it admitted from the lowest position up); one
  set a token, shared by every head.
- attention: `o[t, i] = sum_{s in S_t} softmax_{s in S_t}(q[t, i] .
  k[s, g] / sqrt(head_dim)) v[s, g]`, g = i div (heads / kv heads);
  `h += W_o o`.
- experts: y = RMSNorm(h); `r = softmax(W_r y)` over the PUBLISHED
  number of experts; the `num_experts_per_tok` largest; gates `r_e /
  sum r` over them; expert e gives `W2_e(silu(W1a_e y) * W1b_e y)`; a
  loop over the held ids; no shared expert. Logits = `W_head
  RMSNorm(h)`.

Departures from the published model, all stated in the configuration
file: this chip's share (`experts_held` of the router's experts: what
an absent expert would add is left out, here as in the program; a
vocabulary of `vocab_size` rows; `num_hidden_layers` layers); weights
from the seed (`lib/keye_weights.py`), not a checkpoint; the vision
tower is not built and every position is a text position (t = h = w, so
`mrope_section` changes nothing); what the config has no key for
(QK-norm, the indexer's inputs, norm and rotation) as `assumed` says;
any positive constant on `a` and the orthogonal rotation the FP8
indexer applies to qI and kI leave the selection unchanged and are left
out.

`quant="int8"` (or `"fp8"`) computes the same pass with every weight
matmul's operands rounded to 8 bits (weights per output channel,
activations per token), as `llama_like` does: the control the
comparison has to fail.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import keye_weights as kw

F32 = jnp.float32
Q_BLOCK = 512           # I, the selection and attention by query rows


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


def _rope(x, theta):
    """x [T, ..., D] at positions 0..T-1; pairs (i, i + D/2)."""
    t, d = x.shape[0], x.shape[-1]
    inv = F32(1.0) / (F32(theta) ** (jnp.arange(0, d, 2, dtype=F32) / F32(d)))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _row_blocks(t):
    """Start of each block of Q_BLOCK query rows (T is whole blocks, or
    shorter than one)."""
    return jnp.arange(0, t, min(Q_BLOCK, t), dtype=jnp.int32)


def _rows(a, start):
    return jax.lax.dynamic_slice_in_dim(a, start, min(Q_BLOCK, a.shape[0]))


def index_scores(qi, a, ki):
    """I [T, T] (float32; entries above the diagonal are not used), by
    blocks of query rows."""
    def block(start):
        dots = jnp.einsum("tjd,sd->tjs", _rows(qi, start), ki,
                          precision="highest")
        return jnp.sum(_rows(a, start)[:, :, None] * jax.nn.relu(dots),
                       axis=1)
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


def _attention(q, k, v, keep):
    """q [T, H, D], k/v [T, Hkv, D], keep [T, T] -> [T, H, D]."""
    t, h, d = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)

    def block(start):
        sc = jnp.einsum("qhd,khd->hqk", _rows(q, start), k,
                        precision="highest") / jnp.sqrt(F32(d))
        sc = jnp.where(_rows(keep, start)[None], sc, F32(-1e30))
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                          precision="highest")

    return jax.lax.map(block, _row_blocks(t)).reshape(t, h, d)


def attention_layer(x, w, cfg, quant, with_selection=False):
    """x = RMSNorm(h) [T, hidden] -> W_o o [T, hidden]."""
    t = x.shape[0]
    s, sa = kw.sizes(cfg), cfg["sa_config"]
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        s["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = _rope(_rms(_mm(x, w["wq"], quant).reshape(t, nh, d), eps), theta)
    k = _rope(_rms(_mm(x, w["wk"], quant).reshape(t, nkv, d), eps), theta)
    v = _mm(x, w["wv"], quant).reshape(t, nkv, d)
    qi = _rope(_mm(x, w["wqi"], quant).reshape(
        t, s["index_heads"], s["index_dim"]), theta)
    ki = _rope(_layer_norm(_mm(x, w["wki"], quant), eps), theta)
    a = _mm(x, w["ww"], quant)
    keep = selected(index_scores(qi, a, ki), sa["topk"])
    out = _mm(_attention(q, k, v, keep).reshape(t, nh * d), w["wo"], quant)
    return (out, keep) if with_selection else out


def _swiglu(h, w_in, w_out, quant):
    up = _mm(h, w_in, quant)
    f = w_out.shape[0]
    return _mm(jax.nn.silu(up[:, :f]) * up[:, f:], w_out, quant)


def experts_layer(h, router, key, index, cfg, quant, held=None):
    """The part of the routed layer that the experts in `held` (the
    configuration's `experts_held`) give, one expert at a time."""
    k = cfg["num_experts_per_tok"]
    r = jax.nn.softmax(_mm(h, router, quant), axis=-1)   # published width
    topv, topi = jax.lax.top_k(r, k)
    gates = topv / jnp.sum(topv, axis=-1, keepdims=True)

    def one(acc, e):
        we = {n: a.astype(F32) for n, a in
              kw.expert(cfg, key, index, e).items()}
        gate_e = jnp.sum(jnp.where(topi == e, gates, F32(0)), axis=-1)
        return acc + gate_e[:, None] * _swiglu(h, we["w_in"], we["w_out"],
                                               quant), None

    ids = cfg["experts_held"] if held is None else held
    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             jnp.asarray(list(ids), jnp.int32))
    return routed


@functools.partial(jax.jit, static_argnames=("cfg_s", "quant"))
def _layer(x, key, index, cfg_s, quant):
    """Layer `index` (traced: one program for every layer)."""
    cfg = json.loads(cfg_s)
    w = {n: a.astype(F32) for n, a in kw.attn(cfg, key, index).items()}
    x = x + attention_layer(_rms(x, cfg["rms_norm_eps"]), w, cfg, quant)
    return x + experts_layer(_rms(x, cfg["rms_norm_eps"]), w["router"], key,
                             index, cfg, quant)


@functools.partial(jax.jit, static_argnames=("cfg_s",))
def _embed(ids, key, cfg_s):
    return kw.top(json.loads(cfg_s), key)["embed"].astype(F32)[ids]


@functools.partial(jax.jit, static_argnames=("cfg_s", "quant"))
def _head(x, rows, key, cfg_s, quant):
    cfg = json.loads(cfg_s)
    return _mm(_rms(x[rows], cfg["rms_norm_eps"]),
               kw.top(cfg, key)["head"].astype(F32), quant)


_KEYS = ("hidden_size", "head_dim", "vocab_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads",
         "moe_intermediate_size", "num_experts_per_tok", "experts_held",
         "published", "rms_norm_eps", "rope_theta", "sa_config",
         "initializer_range")


def _static(cfg):
    """The keys the pass reads, as one hashable string."""
    return json.dumps({k: cfg[k] for k in _KEYS}, sort_keys=True)


def pad_len(n):
    """Sequences are right-padded (causal attention lets no position see
    what follows it, and a padded key ranks below none it displaces: a
    row's keys are those at or before it) to a few lengths, so that few
    programs compile: powers of two to 4096, then multiples of 4096."""
    b = 256
    while b < min(n, 4096):
        b *= 2
    return b if n <= 4096 else -(-n // 4096) * 4096


def logits_at(cfg, seed, ids, rows, quant=None):
    """Logits [len(rows), vocab] (float32, numpy) of one sequence `ids`
    at positions `rows`: row r predicts token r + 1."""
    cfg_s = _static(cfg)
    key = kw.base_key(seed)
    n = len(ids)
    padded = np.zeros((pad_len(n),), np.int32)
    padded[:n] = ids
    rows_p = np.zeros((pad_len(len(rows)),), np.int32)
    rows_p[:len(rows)] = rows
    x = _embed(jnp.asarray(padded), key, cfg_s)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, key, jnp.int32(i), cfg_s, quant)
    out = _head(x, jnp.asarray(rows_p), key, cfg_s, quant)
    return np.asarray(out)[:len(rows)]

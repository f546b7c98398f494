"""Seeded weights for the Ling-3.0-flash language model (Kimi Delta
Attention and multi-head latent attention mixers, dense SwiGLU in the
leading layers, then routed experts with a shared one, untied head),
made by the benchmark.

As `lib/granite_weights.py` and `lib/keye_weights.py` do for their
shapes, one generator serves both sides: the builder calls `layer`/`top`
once a layer (a jitted program each, the leaves on the device in the
served dtype) and the plain reference calls the same functions, one
layer and one expert at a time. A leaf's values depend only on (seed,
layer index, leaf name, shape), and an expert's on its id in the
PUBLISHED numbering, never on which share of the experts a chip holds:
four shares of one layer hold parts of the same layer.

Matrices are normal with std `initializer_range`, [in, out]. Assumed,
where the published config has no key (the configuration file says each
under `assumed`): the depthwise convolution uniform in +-1/sqrt(taps)
(no bias); `A_log` = log of uniform [1, 16) a head and `dt_bias` the
inverse softplus of a step log-uniform in [0.001, 0.1] a channel, as
the KDA reference implementation initialises them (flash-linear-
attention, `KimiDeltaAttention`); the router's choice bias `b` normal
with std `router_bias_std` (a trained `noaux_tc` bias is of the order of
the scores' spread; at 0 it would change no choice). RMSNorm gains are
ones and are not stored.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import base_key, _normal  # noqa: F401

KDA_LEAVES = ("wq", "wk", "wv", "wf", "wb", "wg", "wo")
MLA_LEAVES = ("wq", "wa", "wb", "wg", "wo")
FFN_LEAVES = ("w_in", "w_out")


def layer_kind(cfg, index):
    return "mla" if (index + 1) % cfg["layer_group_size"] == 0 else "kda"


def kinds(cfg):
    return [layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])]


def sizes(cfg):
    """The derived sizes both sides need."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"hidden": h, "heads": nh, "head_dim": cfg["head_dim"],
            "kda": nh * cfg["head_dim"], "taps": cfg["short_conv_kernel_size"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
            "dense": cfg["intermediate_size"],
            "expert": cfg["moe_intermediate_size"],
            "shared": cfg["moe_shared_expert_intermediate_size"],
            "router": cfg["published"]["num_experts"]}


def _layer_key(key, index):
    return jax.random.fold_in(key, index + 1)


def mixer(cfg, key, index, dtype=jnp.bfloat16, kind=None):
    """The mixer's leaves of layer `index` (`kind` says which where
    `index` is traced)."""
    s, std = sizes(cfg), cfg["initializer_range"]
    k = jax.random.fold_in(_layer_key(key, index), 1)
    sub = lambda j: jax.random.fold_in(k, j)
    h, nh = s["hidden"], s["heads"]
    if (kind or layer_kind(cfg, index)) == "mla":
        shp = {"wq": (h, nh * (s["nope"] + s["rope"])),
               "wa": (h, s["rank"] + s["rope"]),
               "wb": (s["rank"], nh * (s["nope"] + s["v"])),
               "wg": (h, nh), "wo": (nh * s["v"], h)}
        return {n: _normal(sub(j), shp[n], std, dtype)
                for j, n in enumerate(MLA_LEAVES)}
    w = s["kda"]
    shp = {"wq": (h, w), "wk": (h, w), "wv": (h, w), "wf": (h, w),
           "wb": (h, nh), "wg": (h, nh), "wo": (w, h)}
    out = {n: _normal(sub(j), shp[n], std, dtype)
           for j, n in enumerate(KDA_LEAVES)}
    bound = 1.0 / math.sqrt(s["taps"])
    out["conv_w"] = jax.random.uniform(
        sub(10), (s["taps"], 3 * w), jnp.float32, -bound, bound).astype(dtype)
    out["a_log"] = jnp.log(jax.random.uniform(
        sub(11), (nh,), jnp.float32, 1.0, 16.0)).astype(dtype)
    dt = jnp.exp(jax.random.uniform(sub(12), (nh, s["head_dim"]), jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    dt = jnp.maximum(dt, 1e-4)
    out["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return out


def dense(cfg, key, index, dtype=jnp.bfloat16):
    """The dense SwiGLU of a leading layer: `w_in` [hidden, 2 x width]
    (gate half, then up half), `w_out` [width, hidden]."""
    s, std = sizes(cfg), cfg["initializer_range"]
    k = jax.random.fold_in(_layer_key(key, index), 4)
    return {"w_in": _normal(jax.random.fold_in(k, 0),
                            (s["hidden"], 2 * s["dense"]), std, dtype),
            "w_out": _normal(jax.random.fold_in(k, 1),
                             (s["dense"], s["hidden"]), std, dtype)}


def moe(cfg, key, index, dtype=jnp.bfloat16):
    """Router (published width) with its choice bias (float32), and the
    shared expert, of layer `index`."""
    s, std = sizes(cfg), cfg["initializer_range"]
    k = jax.random.fold_in(_layer_key(key, index), 2)
    sub = lambda j: jax.random.fold_in(k, j)
    return {"router": _normal(sub(0), (s["hidden"], s["router"]), std, dtype),
            "shared_in": _normal(sub(1), (s["hidden"], 2 * s["shared"]), std,
                                 dtype),
            "shared_out": _normal(sub(2), (s["shared"], s["hidden"]), std,
                                  dtype),
            "bias": _normal(sub(3), (s["router"],), cfg["router_bias_std"],
                            jnp.float32)}


def expert(cfg, key, index, expert_id, dtype=jnp.bfloat16):
    """Expert `expert_id` (published numbering, may be traced) of layer
    `index`: `w_in` [hidden, 2 x width] (gate half, then up half) and
    `w_out` [width, hidden]."""
    s, std = sizes(cfg), cfg["initializer_range"]
    k = jax.random.fold_in(jax.random.fold_in(_layer_key(key, index), 3),
                           expert_id)
    return {"w_in": _normal(jax.random.fold_in(k, 0),
                            (s["hidden"], 2 * s["expert"]), std, dtype),
            "w_out": _normal(jax.random.fold_in(k, 1),
                             (s["expert"], s["hidden"]), std, dtype)}


def experts(cfg, key, index, held, dtype=jnp.bfloat16):
    """The stacked banks of the experts in `held`: [len(held), ...]."""
    ids = jnp.asarray(list(held), jnp.int32)
    return jax.vmap(lambda e: expert(cfg, key, index, e, dtype))(ids)


def layer(cfg, key, index, dtype=jnp.bfloat16, kind=None, is_dense=None):
    """Every stored leaf of decoder layer `index` that this chip holds
    (`kind`, `is_dense` say what it is where `index` is traced)."""
    if is_dense is None:
        is_dense = index < cfg["first_k_dense_replace"]
    out = {"mixer": mixer(cfg, key, index, dtype, kind)}
    if is_dense:
        out["dense"] = dense(cfg, key, index, dtype)
    else:
        out["moe"] = moe(cfg, key, index, dtype)
        out["experts"] = experts(cfg, key, index, cfg["experts_held"], dtype)
    return out


def top(cfg, key, dtype=jnp.bfloat16):
    """Embedding table [vocab held, hidden] and untied head [hidden,
    vocab held]."""
    k = jax.random.fold_in(key, 0)
    std, v, h = cfg["initializer_range"], cfg["vocab_size"], \
        cfg["hidden_size"]
    return {"embed": _normal(jax.random.fold_in(k, 0), (v, h), std, dtype),
            "head": _normal(jax.random.fold_in(k, 1), (h, v), std, dtype)}

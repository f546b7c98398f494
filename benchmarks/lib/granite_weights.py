"""Seeded weights for the Granite-4.0-H family (Mamba-2 mixers, NoPE
attention, routed experts and a shared expert), made by the benchmark.

As `lib/weights.py` does for the Llama shape, one generator serves both
sides: the builder calls `layer`/`top` once a layer (a jitted program
each, the leaves on the device in the served dtype) and the plain
reference calls the same functions, one layer and one expert at a time.
A leaf's values depend only on (seed, layer index, leaf name, shape), and
an expert's on its id in the PUBLISHED numbering, never on which share
of the experts a chip holds: two shares of one layer hold parts of the
same layer.

Matrices are normal with std `initializer_range`, [in, out]. The
Mamba-2 leaves follow the usual initialisation of the reference
implementation (state-spaces/mamba, `Mamba2.__init__`): `A_log` = log of
uniform [1, 16), `dt_bias` = the inverse softplus of a time step drawn
log-uniformly from [0.001, 0.1], `D` = 1, the depthwise convolution
uniform in +-1/sqrt(d_conv) (weights and bias). RMSNorm gains and `D`
are ones and are not stored. The tied embedding may take a std of its
own (`embedding_initializer_range`): with random weights, a head tied to
an embedding that is multiplied by 12 gives a token's own logit 64 x its
share of the residual stream; at std 0.02 that share is a fifth, every
served token repeated its predecessor, and no lower precision could move
an argmax (PERF.md, PR 27).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import base_key, _normal  # noqa: F401

ATTN_LEAVES = ("wq", "wk", "wv", "wo")
MOE_LEAVES = ("router", "shared_in", "shared_out")


def sizes(cfg):
    """The derived sizes both sides need."""
    h = cfg["hidden_size"]
    d_inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    conv = d_inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    head = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    return {"hidden": h, "d_inner": d_inner, "conv": conv,
            "in_proj": d_inner + conv + cfg["mamba_n_heads"],
            "head_dim": head, "q": cfg["num_attention_heads"] * head,
            "kv": cfg["num_key_value_heads"] * head,
            "expert": cfg["intermediate_size"],
            "shared": cfg["shared_intermediate_size"],
            "router": cfg["published"]["num_local_experts"]}


def _uniform(key, shape, lo, hi, dtype):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(dtype)


def _layer_key(key, index):
    return jax.random.fold_in(key, index + 1)


def mixer(cfg, key, index, dtype=jnp.bfloat16, kind=None):
    """The mixer's leaves of layer `index`, of the kind
    `cfg["layer_types"][index]` (`kind` says it where `index` is
    traced)."""
    s, std = sizes(cfg), cfg["initializer_range"]
    k = jax.random.fold_in(_layer_key(key, index), 1)
    sub = lambda j: jax.random.fold_in(k, j)
    if (kind or cfg["layer_types"][index]) == "attention":
        shp = {"wq": (s["hidden"], s["q"]), "wk": (s["hidden"], s["kv"]),
               "wv": (s["hidden"], s["kv"]), "wo": (s["q"], s["hidden"])}
        return {n: _normal(sub(j), shp[n], std, dtype)
                for j, n in enumerate(ATTN_LEAVES)}
    nh, kc = cfg["mamba_n_heads"], cfg["mamba_d_conv"]
    bound = 1.0 / math.sqrt(kc)
    dt = jnp.exp(jax.random.uniform(sub(3), (nh,), jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "in_proj": _normal(sub(0), (s["hidden"], s["in_proj"]), std, dtype),
        "conv_w": _uniform(sub(1), (kc, s["conv"]), -bound, bound, dtype),
        "conv_b": _uniform(sub(2), (s["conv"],), -bound, bound, dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "a_log": jnp.log(jax.random.uniform(sub(4), (nh,), jnp.float32,
                                            1.0, 16.0)).astype(dtype),
        "out_proj": _normal(sub(5), (s["d_inner"], s["hidden"]), std, dtype)}


def moe(cfg, key, index, dtype=jnp.bfloat16):
    """Router (published width) and shared expert of layer `index`."""
    s, std = sizes(cfg), cfg["initializer_range"]
    k = jax.random.fold_in(_layer_key(key, index), 2)
    shp = {"router": (s["hidden"], s["router"]),
           "shared_in": (s["hidden"], 2 * s["shared"]),
           "shared_out": (s["shared"], s["hidden"])}
    return {n: _normal(jax.random.fold_in(k, j), shp[n], std, dtype)
            for j, n in enumerate(MOE_LEAVES)}


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


def layer(cfg, key, index, dtype=jnp.bfloat16, kind=None):
    """Every leaf of decoder layer `index` that this chip holds."""
    return {"mixer": mixer(cfg, key, index, dtype, kind),
            "moe": moe(cfg, key, index, dtype),
            "experts": experts(cfg, key, index, cfg["experts_held"], dtype)}


def top(cfg, key, dtype=jnp.bfloat16):
    """The tied embedding table [vocab held, hidden]."""
    k = jax.random.fold_in(key, 0)
    std = cfg.get("embedding_initializer_range", cfg["initializer_range"])
    return {"embed": _normal(jax.random.fold_in(k, 0),
                             (cfg["vocab_size"], cfg["hidden_size"]),
                             std, dtype)}

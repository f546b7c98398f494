"""Seeded weights for the Keye-VL-2.0 language model (GQA with an
attention indexer, routed experts in every layer, untied head), made by
the benchmark.

As `lib/weights.py` and `lib/granite_weights.py` do for their shapes,
one generator serves both sides: the builder calls `layer`/`top` once a
layer (a jitted program each, the leaves on the device in the served
dtype) and the plain reference calls the same functions, one layer and
one expert at a time. A leaf's values depend only on (seed, layer index,
leaf name, shape), and an expert's on its id in the PUBLISHED numbering,
never on which share of the experts a chip holds: two shares of one
layer hold parts of the same layer.

Matrices are normal with std `initializer_range`, [in, out]. The gains
of the RMSNorms (layer input, post-attention, final, per-head q and k)
and of the indexer's LayerNorm are ones, its bias zeros: not stored.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import base_key, _normal  # noqa: F401

ATTN_LEAVES = ("wq", "wk", "wv", "wo", "wqi", "wki", "ww", "router")


def sizes(cfg):
    """The derived sizes both sides need."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    sa = cfg["sa_config"]
    return {"hidden": h, "head_dim": d,
            "q": cfg["num_attention_heads"] * d,
            "kv": cfg["num_key_value_heads"] * d,
            "index_heads": sa["indexer_num_heads"],
            "index_dim": sa["indexer_head_dim"],
            "expert": cfg["moe_intermediate_size"],
            "router": cfg["published"]["num_experts"]}


def _layer_key(key, index):
    return jax.random.fold_in(key, index + 1)


def attn(cfg, key, index, dtype=jnp.bfloat16):
    """Attention, indexer and router matrices of layer `index`."""
    s, std = sizes(cfg), cfg["initializer_range"]
    k = jax.random.fold_in(_layer_key(key, index), 1)
    shp = {"wq": (s["hidden"], s["q"]), "wk": (s["hidden"], s["kv"]),
           "wv": (s["hidden"], s["kv"]), "wo": (s["q"], s["hidden"]),
           "wqi": (s["hidden"], s["index_heads"] * s["index_dim"]),
           "wki": (s["hidden"], s["index_dim"]),
           "ww": (s["hidden"], s["index_heads"]),
           "router": (s["hidden"], s["router"])}
    return {n: _normal(jax.random.fold_in(k, j), shp[n], std, dtype)
            for j, n in enumerate(ATTN_LEAVES)}


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


def layer(cfg, key, index, dtype=jnp.bfloat16):
    """Every stored leaf of decoder layer `index` that this chip holds."""
    return {"attn": attn(cfg, key, index, dtype),
            "experts": experts(cfg, key, index, cfg["experts_held"], dtype)}


def top(cfg, key, dtype=jnp.bfloat16):
    """Embedding table [vocab held, hidden] and untied head [hidden,
    vocab held]."""
    k = jax.random.fold_in(key, 0)
    std, v, h = cfg["initializer_range"], cfg["vocab_size"], \
        cfg["hidden_size"]
    return {"embed": _normal(jax.random.fold_in(k, 0), (v, h), std, dtype),
            "head": _normal(jax.random.fold_in(k, 1), (h, v), std, dtype)}

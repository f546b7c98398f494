"""Seeded weights for the GLM-5 language model (latent attention under
an indexer with a compressed query in every layer, dense SwiGLU in the
leading layers, then routed experts with a shared one, untied head),
made by the benchmark.

As the other `lib/*_weights.py` do for their shapes, one generator
serves both sides: the builder calls `layer`/`top` once a layer (a
jitted program each, the leaves on the device in the served dtype) and
the plain reference calls the same functions, one layer and one expert
at a time. A leaf's values depend only on (seed, layer index, leaf name,
shape), and an expert's on its id in the PUBLISHED numbering, never on
which share of the experts a chip holds: sixteen shares of one layer
hold parts of the same layer.

Matrices are normal with std `initializer_range`, [in, out]. Assumed,
where the published config has no key (the configuration file says each
under `assumed`): the router's choice bias `b` normal with std
`router_bias_std`; the embedding table normal with std `embed_std`
(the matrices' where the file has none). The gains of the RMSNorms (layer input,
post-attention, final, the query's and the keys' latents) and of the
index key's LayerNorm are ones, its bias zeros: not stored.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import base_key, _normal  # noqa: F401

ATTN_LEAVES = ("wqa", "wqb", "wkva", "wkvb", "wo", "wiq", "wik", "wiw")


def sizes(cfg):
    """The derived sizes both sides need."""
    return {"hidden": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
            "q_rank": cfg["q_lora_rank"], "rank": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "index_heads": cfg["index_n_heads"],
            "index_dim": cfg["index_head_dim"],
            "dense": cfg["intermediate_size"],
            "expert": cfg["moe_intermediate_size"],
            "shared": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "router": cfg["published"]["n_routed_experts"]}


def _layer_key(key, index):
    return jax.random.fold_in(key, index + 1)


def attn(cfg, key, index, dtype=jnp.bfloat16):
    """Attention and indexer matrices of layer `index`."""
    s, std = sizes(cfg), cfg["initializer_range"]
    k = jax.random.fold_in(_layer_key(key, index), 1)
    h, nh = s["hidden"], s["heads"]
    shp = {"wqa": (h, s["q_rank"]),
           "wqb": (s["q_rank"], nh * (s["nope"] + s["rope"])),
           "wkva": (h, s["rank"] + s["rope"]),
           "wkvb": (s["rank"], nh * (s["nope"] + s["v"])),
           "wo": (nh * s["v"], h),
           "wiq": (s["q_rank"], s["index_heads"] * s["index_dim"]),
           "wik": (h, s["index_dim"]), "wiw": (h, s["index_heads"])}
    return {n: _normal(jax.random.fold_in(k, j), shp[n], std, dtype)
            for j, n in enumerate(ATTN_LEAVES)}


def _ffn(k, hidden, width, std, dtype):
    """`w_in` [hidden, 2 x width] (gate half, then up half), `w_out`
    [width, hidden]."""
    return {"w_in": _normal(jax.random.fold_in(k, 0), (hidden, 2 * width),
                            std, dtype),
            "w_out": _normal(jax.random.fold_in(k, 1), (width, hidden), std,
                             dtype)}


def dense(cfg, key, index, dtype=jnp.bfloat16):
    """The dense SwiGLU of a leading layer."""
    s = sizes(cfg)
    return _ffn(jax.random.fold_in(_layer_key(key, index), 4), s["hidden"],
                s["dense"], cfg["initializer_range"], dtype)


def moe(cfg, key, index, dtype=jnp.bfloat16):
    """Router (published width) with its choice bias (float32), and the
    shared expert, of layer `index`."""
    s, std = sizes(cfg), cfg["initializer_range"]
    k = jax.random.fold_in(_layer_key(key, index), 2)
    shared = _ffn(jax.random.fold_in(k, 1), s["hidden"], s["shared"], std,
                  dtype)
    return {"router": _normal(jax.random.fold_in(k, 0),
                              (s["hidden"], s["router"]), std, dtype),
            "shared_in": shared["w_in"], "shared_out": shared["w_out"],
            "bias": _normal(jax.random.fold_in(k, 3), (s["router"],),
                            cfg["router_bias_std"], jnp.float32)}


def expert(cfg, key, index, expert_id, dtype=jnp.bfloat16):
    """Expert `expert_id` (published numbering, may be traced) of layer
    `index`."""
    s = sizes(cfg)
    k = jax.random.fold_in(jax.random.fold_in(_layer_key(key, index), 3),
                           expert_id)
    return _ffn(k, s["hidden"], s["expert"], cfg["initializer_range"], dtype)


def experts(cfg, key, index, held, dtype=jnp.bfloat16):
    """The stacked banks of the experts in `held`: [len(held), ...]."""
    ids = jnp.asarray(list(held), jnp.int32)
    return jax.vmap(lambda e: expert(cfg, key, index, e, dtype))(ids)


def layer(cfg, key, index, dtype=jnp.bfloat16, is_dense=None):
    """Every stored leaf of decoder layer `index` that this chip holds
    (`is_dense` says what it is where `index` is traced)."""
    if is_dense is None:
        is_dense = index < cfg["first_k_dense_replace"]
    out = {"attn": attn(cfg, key, index, dtype)}
    if is_dense:
        out["dense"] = dense(cfg, key, index, dtype)
    else:
        out["moe"] = moe(cfg, key, index, dtype)
        out["experts"] = experts(cfg, key, index, cfg["experts_held"], dtype)
    return out


def top(cfg, key, dtype=jnp.bfloat16):
    """Embedding table [vocab held, hidden] (std `embed_std`) and untied
    head [hidden, vocab held]."""
    k = jax.random.fold_in(key, 0)
    std, v, h = cfg["initializer_range"], cfg["vocab_size"], \
        cfg["hidden_size"]
    return {"embed": _normal(jax.random.fold_in(k, 0), (v, h),
                             cfg.get("embed_std", std), dtype),
            "head": _normal(jax.random.fold_in(k, 1), (h, v), std, dtype)}

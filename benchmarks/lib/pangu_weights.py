"""Seeded weights for openPangu-Ultra-MoE (latent attention with a
compressed query and four norms in every layer, dense SwiGLU in the
leading layers, then routed experts with a shared one, untied head, and
one multi-token-prediction module), made by the benchmark.

As the other `lib/*_weights.py` do for their shapes, one generator
serves both sides: the builder calls `layer`/`top`/`mtp` (a jitted
program each, the leaves on the device in the served dtype) and the
plain reference calls the same functions, one layer and one expert at a
time. A leaf's values depend only on (seed, layer index, leaf name,
shape), and an expert's on its id in the PUBLISHED numbering, never on
which share of the experts a chip holds: sixteen shares of one layer
hold parts of the same layer. The MTP module's decoder layer is layer
`MTP_LAYER` whatever the depth built.

Matrices are normal with std `initializer_range`, [in, out]. The gains
of every RMSNorm (a layer's four, the final one, the query's and the
keys' latents, the MTP module's three) are ones and the router has no
choice bias: not stored.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.lib.glm_weights import _ffn, _layer_key
from benchmarks.lib.weights import base_key, _normal  # noqa: F401

ATTN_LEAVES = ("wqa", "wqb", "wkva", "wkvb", "wo")
MTP_LAYER = 1 << 20     # the MTP module's layer, in the layers' numbering


def sizes(cfg):
    """The derived sizes both sides need."""
    return {"hidden": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
            "q_rank": cfg["q_lora_rank"], "rank": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "dense": cfg["intermediate_size"],
            "expert": cfg["moe_intermediate_size"],
            "shared": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "router": cfg["published"]["n_routed_experts"]}


def attn(cfg, key, index, dtype=jnp.bfloat16):
    """Attention matrices of layer `index`."""
    s, std = sizes(cfg), cfg["initializer_range"]
    k = jax.random.fold_in(_layer_key(key, index), 1)
    h, nh = s["hidden"], s["heads"]
    shp = {"wqa": (h, s["q_rank"]),
           "wqb": (s["q_rank"], nh * (s["nope"] + s["rope"])),
           "wkva": (h, s["rank"] + s["rope"]),
           "wkvb": (s["rank"], nh * (s["nope"] + s["v"])),
           "wo": (nh * s["v"], h)}
    return {n: _normal(jax.random.fold_in(k, j), shp[n], std, dtype)
            for j, n in enumerate(ATTN_LEAVES)}


def dense(cfg, key, index, dtype=jnp.bfloat16):
    """The dense SwiGLU of a leading layer."""
    s = sizes(cfg)
    return _ffn(jax.random.fold_in(_layer_key(key, index), 4), s["hidden"],
                s["dense"], cfg["initializer_range"], dtype)


def moe(cfg, key, index, dtype=jnp.bfloat16):
    """Router (published width) and shared expert of layer `index`."""
    s, std = sizes(cfg), cfg["initializer_range"]
    k = jax.random.fold_in(_layer_key(key, index), 2)
    shared = _ffn(jax.random.fold_in(k, 1), s["hidden"], s["shared"], std,
                  dtype)
    return {"router": _normal(jax.random.fold_in(k, 0),
                              (s["hidden"], s["router"]), std, dtype),
            "shared_in": shared["w_in"], "shared_out": shared["w_out"]}


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


def mtp_join(cfg, key, dtype=jnp.bfloat16):
    """`W_eh` [2 x hidden, hidden]: the next token's normed embedding
    on the first `hidden` rows, the trunk's normed output on the rest."""
    h = cfg["hidden_size"]
    return _normal(jax.random.fold_in(_layer_key(key, MTP_LAYER), 5),
                   (2 * h, h), cfg["initializer_range"], dtype)


def top(cfg, key, dtype=jnp.bfloat16):
    """Embedding table [vocab held, hidden] and untied head [hidden,
    vocab held]."""
    k = jax.random.fold_in(key, 0)
    std, v, h = cfg["initializer_range"], cfg["vocab_size"], \
        cfg["hidden_size"]
    return {"embed": _normal(jax.random.fold_in(k, 0), (v, h), std, dtype),
            "head": _normal(jax.random.fold_in(k, 1), (h, v), std, dtype)}

"""Seeded weights for Xing4.0 (latent attention with a compressed query
in every layer, two sets of manifold-constrained hyper-connection maps a
layer, dense SwiGLU in the leading layers, then routed experts with a
choice bias and a shared one, untied head), made by the benchmark.

As the other `lib/*_weights.py` do for their shapes, one generator
serves both sides: the builder calls `layer`/`top` once a layer (a
jitted program each, the leaves on the device in the served dtype) and
the plain reference calls the same functions, one layer and one expert
at a time. A leaf's values depend only on (seed, layer index, leaf name,
shape), and an expert's on its id.

Matrices are normal with std `initializer_range`, [in, out]. Assumed,
where the published config has no key (the configuration file says each
under `assumed`): the router's choice bias `b` normal with std
`router_bias_std`; the embedding table normal with std `embed_std` (the
matrices' where the file has none: `glm_weights.top`, shared); and the hyper-connections' start
(`mhc_init`), drawn so that the three maps DIFFER from token to token:
`phi` normal with std 1 / sqrt(n C) (so that `r phi` has std 1), every
`a` 1, `b_pre` and `b_post` normal with std 0.5, `b_res` = 2 I + normal
with std 0.5. The paper's near-identity start (`a` = 0.01) would make
the sweeps and the projections invisible to any comparison. The gains
of the RMSNorms (a layer's two, the final one, the query's and the keys'
latents) are ones: not stored.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.lib.glm_weights import _ffn, _layer_key, top  # noqa: F401
from benchmarks.lib.weights import base_key, _normal  # noqa: F401

ATTN_LEAVES = ("wqa", "wqb", "wkva", "wkvb", "wo")
SUBLAYERS = ("attn", "ffn")     # each with hyper-connection maps of its own
MHC_BIAS_STD, MHC_DIAGONAL = 0.5, 2.0


def sizes(cfg):
    """The derived sizes both sides need."""
    n = cfg["hc_mult"]
    return {"hidden": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
            "q_rank": cfg["q_lora_rank"], "rank": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "dense": cfg["intermediate_size"],
            "expert": cfg["moe_intermediate_size"],
            "shared": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "router": cfg["n_routed_experts"], "streams": n,
            "maps": 2 * n + n * n}


def held(cfg):
    """The experts this chip holds: all of them unless the file says."""
    return cfg.get("experts_held") or list(range(cfg["n_routed_experts"]))


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


def mhc(cfg, key, index, sublayer, dtype=jnp.bfloat16):
    """The maps' parameters of sublayer `sublayer` (of `SUBLAYERS`) of
    layer `index`: `phi` [n C, 2n + n^2] (columns: pre, post, res
    row-major), `a` [3] and `b` [2n + n^2] float32."""
    s = sizes(cfg)
    n, width = s["streams"], s["streams"] * s["hidden"]
    k = jax.random.fold_in(jax.random.fold_in(_layer_key(key, index), 5),
                           SUBLAYERS.index(sublayer))
    b = _normal(jax.random.fold_in(k, 1), (s["maps"],), MHC_BIAS_STD,
                jnp.float32)
    b = b.at[2 * n:].add(MHC_DIAGONAL * jnp.eye(n, dtype=jnp.float32)
                         .reshape(-1))
    return {"phi": _normal(jax.random.fold_in(k, 0), (width, s["maps"]),
                           width ** -0.5, dtype),
            "a": jnp.ones((3,), jnp.float32), "b": b}


def dense(cfg, key, index, dtype=jnp.bfloat16):
    """The dense SwiGLU of a leading layer."""
    s = sizes(cfg)
    return _ffn(jax.random.fold_in(_layer_key(key, index), 4), s["hidden"],
                s["dense"], cfg["initializer_range"], dtype)


def moe(cfg, key, index, dtype=jnp.bfloat16):
    """Router with its choice bias (float32), and the shared expert, of
    layer `index`."""
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
    """Expert `expert_id` (may be traced) of layer `index`."""
    s = sizes(cfg)
    k = jax.random.fold_in(jax.random.fold_in(_layer_key(key, index), 3),
                           expert_id)
    return _ffn(k, s["hidden"], s["expert"], cfg["initializer_range"], dtype)


def experts(cfg, key, index, ids, dtype=jnp.bfloat16):
    """The stacked banks of the experts in `ids`: [len(ids), ...]."""
    ids = jnp.asarray(list(ids), jnp.int32)
    return jax.vmap(lambda e: expert(cfg, key, index, e, dtype))(ids)


def layer(cfg, key, index, dtype=jnp.bfloat16, is_dense=None):
    """Every stored leaf of decoder layer `index` (`is_dense` says what
    it is where `index` is traced)."""
    if is_dense is None:
        is_dense = index < cfg["first_k_dense_replace"]
    out = {"attn": attn(cfg, key, index, dtype),
           "mhc": {s: mhc(cfg, key, index, s, dtype) for s in SUBLAYERS}}
    if is_dense:
        out["dense"] = dense(cfg, key, index, dtype)
    else:
        out["moe"] = moe(cfg, key, index, dtype)
        out["experts"] = experts(cfg, key, index, held(cfg), dtype)
    return out


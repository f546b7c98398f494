"""Seeded weights for Llama-shaped decoders, made by the benchmark.

One generator serves both sides: the builder calls `make_all` once (one
jitted program, every leaf on the device in the served dtype) and the
plain reference calls `layer`/`top` for one layer at a time. A leaf's
values depend only on (seed, layer index, leaf name, shape), so the two
sides hold the same numbers without either handing arrays to the other.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
TOP_LEAVES = ("embed", "head")


def base_key(seed):
    """A key from any non-negative seed, also past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def shapes(cfg):
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * d
    layer = {"wq": (h, h), "wk": (h, kv), "wv": (h, kv), "wo": (h, h),
             "w_gate": (h, i), "w_up": (h, i), "w_down": (i, h)}
    top = {"embed": (v, h), "head": (h, v)}
    return layer, top


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * jnp.float32(std)).astype(dtype)


def layer(cfg, key, index, dtype=jnp.bfloat16):
    """Matrices of decoder layer `index` ([in, out] layout); the two
    RMSNorm gains are ones and are not stored."""
    shp, _ = shapes(cfg)
    k = jax.random.fold_in(key, index + 1)
    return {n: _normal(jax.random.fold_in(k, j), shp[n],
                       cfg["initializer_range"], dtype)
            for j, n in enumerate(LAYER_LEAVES)}


def top(cfg, key, dtype=jnp.bfloat16):
    """Embedding table [vocab, hidden] and untied head [hidden, vocab]."""
    _, shp = shapes(cfg)
    k = jax.random.fold_in(key, 0)
    return {n: _normal(jax.random.fold_in(k, j), shp[n],
                       cfg["initializer_range"], dtype)
            for j, n in enumerate(TOP_LEAVES)}


def make_all(cfg, seed, dtype=jnp.bfloat16):
    """Every leaf in one jitted call: {"top": {...}, "layers": [...]}."""
    n = cfg["num_hidden_layers"]

    def build(key):
        return {"top": top(cfg, key, dtype),
                "layers": [layer(cfg, key, i, dtype) for i in range(n)]}

    return jax.jit(build)(base_key(seed))

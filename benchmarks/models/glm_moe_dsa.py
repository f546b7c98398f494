"""Builder: a configuration file of the GLM-5 language model (latent
attention under an indexer in every layer, dense SwiGLU in the first
`first_k_dense_replace` layers, then routed experts of which this chip
holds `experts_held` and a shared expert, untied head) -> the program's
`GlmMoeDsaForCausalLM`, holding the benchmark's seeded weights.

As in `ling_hybrid`, the module tree is built under `jax.eval_shape`
(the program's constructor initialises every parameter in float32) and
every leaf is then replaced by `lib.glm_weights`, one jitted program a
layer, so that the float32 draws of one layer are freed before the next
is made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.lib import glm_weights as gw

_ATTN = {"self_attn.q_a_proj.weight": "wqa",
         "self_attn.q_b_proj.weight": "wqb",
         "self_attn.kv_a_proj.weight": "wkva",
         "self_attn.kv_b_proj.weight": "wkvb",
         "self_attn.o_proj.weight": "wo",
         "self_attn.index_q_proj.weight": "wiq",
         "self_attn.index_k_proj.weight": "wik",
         "self_attn.index_w_proj.weight": "wiw"}
_DENSE = {"mlp.in_proj.weight": "w_in", "mlp.out_proj.weight": "w_out"}
_MOE = {"moe.router": "router", "moe.expert_bias": "bias",
        "shared_mlp.in_proj.weight": "shared_in",
        "shared_mlp.out_proj.weight": "shared_out"}
_EXPERTS = {"moe.w_in": "w_in", "moe.w_out": "w_out"}
_ONES = ("input_layernorm.weight", "post_attention_layernorm.weight",
         "self_attn.q_a_norm", "self_attn.kv_a_norm",
         "self_attn.index_k_norm")
_ZEROS = ("self_attn.index_k_norm_bias",)
_FLOAT32 = ("moe.expert_bias",)


def glm_config(cfg, **over):
    from paddle_tpu.models import GlmMoeDsaConfig
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
            "n_shared_experts", "num_experts_per_tok", "n_group",
            "topk_group", "routed_scaling_factor", "norm_topk_prob",
            "rms_norm_eps", "max_position_embeddings", "initializer_range",
            "dtype")
    args = {k: cfg[k] for k in same}
    args.update(
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        index_norm_eps=cfg["index_norm_eps"],
        q_chunk_size=cfg.get("q_chunk_size", 512),
        n_routed_experts=cfg["published"]["n_routed_experts"],
        experts_held=tuple(cfg["experts_held"]))
    args.update(over)
    return GlmMoeDsaConfig(**args)


def _skeleton(gcfg):
    from paddle_tpu.models import GlmMoeDsaForCausalLM
    box = []

    def make():
        box.append(GlmMoeDsaForCausalLM(gcfg))
        return 0

    jax.eval_shape(make)
    return box[0]


def build(cfg, seed, dtype=None, abstract=False):
    """The program's model for `cfg` with weights from `seed`; returns
    (model, number of parameters held here). `abstract` leaves every
    parameter a `jax.ShapeDtypeStruct`."""
    import paddle_tpu as paddle
    dt = jnp.dtype(dtype or cfg["dtype"])
    if len(cfg["experts_held"]) != cfg["n_routed_experts"]:
        raise ValueError("experts_held must list n_routed_experts ids")
    model = _skeleton(glm_config(cfg))
    paddle.seed(int(seed) & 0x7FFFFFFF)   # the skeleton left a tracer there
    key = gw.base_key(seed)
    make_layer = functools.partial(jax.jit, static_argnames=("is_dense",))(
        lambda k, index, is_dense: gw.layer(cfg, k, index,
                                            is_dense=is_dense))
    run = (lambda f, *a, **kw: jax.eval_shape(
        functools.partial(f, **kw), *a)) if abstract \
        else (lambda f, *a, **kw: f(*a, **kw))
    top = run(jax.jit(lambda k: gw.top(cfg, k)), key)
    layers = [run(make_layer, key, jnp.int32(i),
                  is_dense=i < cfg["first_k_dense_replace"])
              for i in range(cfg["num_hidden_layers"])]
    n_params = 0
    for name, p in model.named_parameters():
        parts = name.split(".")
        leaf = ".".join(parts[3:])
        want = jnp.dtype(jnp.float32) if leaf in _FLOAT32 else dt
        w = layers[int(parts[2])] if parts[1] == "layers" else None
        if name == "model.embed_tokens.weight":
            val = top["embed"]
        elif name == "lm_head.weight":
            val = top["head"]
        elif name == "model.norm.weight" or leaf in _ONES:
            val = jnp.ones(p.shape, dt)
        elif leaf in _ZEROS:
            val = jnp.zeros(p.shape, dt)
        elif leaf in _ATTN:
            val = w["attn"][_ATTN[leaf]]
        elif leaf in _DENSE:
            val = w["dense"][_DENSE[leaf]]
        elif leaf in _MOE:
            val = w["moe"][_MOE[leaf]]
        elif leaf in _EXPERTS:
            val = w["experts"][_EXPERTS[leaf]]
        else:
            raise KeyError(f"builder glm_moe_dsa: unknown parameter {name}")
        if tuple(val.shape) != tuple(p.shape):
            raise ValueError(f"{name}: built {val.shape}, model {p.shape}")
        p._value = jax.ShapeDtypeStruct(val.shape, want) if abstract \
            else val.astype(want)
        n_params += int(val.size)
    model.eval()
    return model, n_params

"""Builder: a configuration file of the Ling-3.0-flash language model
(KDA and MLA mixers by `layer_group_size`, dense SwiGLU in the first
`first_k_dense_replace` layers, then routed experts of which this chip
holds `experts_held` and a shared expert, untied head) -> the program's
`LingHybridForCausalLM`, holding the benchmark's seeded weights.

As in `granite_hybrid`, the module tree is built under `jax.eval_shape`
(the program's constructor initialises every parameter in float32) and
every leaf is then replaced by `lib.ling_weights`, one jitted program a
layer, so that the float32 draws of one layer are freed before the next
is made. The program fuses KDA's four wide projections into one matrix
and its two head-wise ones into another; the generator keeps them apart,
as the reference reads them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.lib import ling_weights as lw

_MLA = {"self_attn.q_proj.weight": "wq", "self_attn.kv_a_proj.weight": "wa",
        "self_attn.kv_b_proj.weight": "wb", "self_attn.g_proj.weight": "wg",
        "self_attn.o_proj.weight": "wo"}
_KDA = {"kda.conv_weight": "conv_w", "kda.A_log": "a_log",
        "kda.dt_bias": "dt_bias", "kda.out_proj.weight": "wo"}
_FUSED = {"kda.in_proj.weight": ("wq", "wk", "wv", "wf"),
          "kda.head_proj.weight": ("wb", "wg")}
_DENSE = {"mlp.in_proj.weight": "w_in", "mlp.out_proj.weight": "w_out"}
_MOE = {"moe.router": "router", "moe.expert_bias": "bias",
        "shared_mlp.in_proj.weight": "shared_in",
        "shared_mlp.out_proj.weight": "shared_out"}
_EXPERTS = {"moe.w_in": "w_in", "moe.w_out": "w_out"}
_ONES = ("input_layernorm.weight", "post_attention_layernorm.weight",
         "kda.norm_weight", "self_attn.kv_a_norm")
_FLOAT32 = ("moe.expert_bias",)


def ling_config(cfg, **over):
    from paddle_tpu.models import LingHybridConfig
    args = dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        layer_group_size=cfg["layer_group_size"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_attention_heads=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"],
        short_conv_kernel_size=cfg["short_conv_kernel_size"],
        kda_lower_bound=cfg["kda_lower_bound"],
        kda_chunk_size=cfg.get("kda_chunk_size", 64),
        kda_sub_chunk_size=cfg.get("kda_sub_chunk_size", 16),
        kda_segment_size=cfg.get("kda_segment_size", 1024),
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=cfg["rope_theta"],
        num_experts=cfg["published"]["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        experts_held=tuple(cfg["experts_held"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg["initializer_range"], dtype=cfg["dtype"])
    args.update(over)
    return LingHybridConfig(**args)


def _skeleton(lcfg):
    from paddle_tpu.models import LingHybridForCausalLM
    box = []

    def make():
        box.append(LingHybridForCausalLM(lcfg))
        return 0

    jax.eval_shape(make)
    return box[0]


def build(cfg, seed, dtype=None, abstract=False):
    """The program's model for `cfg` with weights from `seed`; returns
    (model, number of parameters held here). `abstract` leaves every
    parameter a `jax.ShapeDtypeStruct`."""
    import paddle_tpu as paddle
    dt = jnp.dtype(dtype or cfg["dtype"])
    if len(cfg["experts_held"]) != cfg["num_experts"]:
        raise ValueError("experts_held must list num_experts ids")
    model = _skeleton(ling_config(cfg))
    paddle.seed(int(seed) & 0x7FFFFFFF)   # the skeleton left a tracer there
    key = lw.base_key(seed)

    @functools.partial(jax.jit, static_argnames=("kind", "is_dense"))
    def make_layer(k, index, kind, is_dense):
        w = lw.layer(cfg, k, index, kind=kind, is_dense=is_dense)
        if kind == "kda":       # the program's fused projections
            for name, parts in _FUSED.items():
                w["mixer"][name] = jnp.concatenate(
                    [w["mixer"].pop(p) for p in parts], axis=1)
        return w

    run = (lambda f, *a, **kw: jax.eval_shape(
        functools.partial(f, **kw), *a)) if abstract \
        else (lambda f, *a, **kw: f(*a, **kw))
    top = run(jax.jit(lambda k: lw.top(cfg, k)), key)
    layers = [run(make_layer, key, jnp.int32(i), kind=kind,
                  is_dense=i < cfg["first_k_dense_replace"])
              for i, kind in enumerate(lw.kinds(cfg))]
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
        elif leaf in _FUSED:
            val = w["mixer"][leaf]
        elif leaf in _KDA:
            val = w["mixer"][_KDA[leaf]]
        elif leaf in _MLA:
            val = w["mixer"][_MLA[leaf]]
        elif leaf in _DENSE:
            val = w["dense"][_DENSE[leaf]]
        elif leaf in _MOE:
            val = w["moe"][_MOE[leaf]]
        elif leaf in _EXPERTS:
            val = w["experts"][_EXPERTS[leaf]]
        else:
            raise KeyError(f"builder ling_hybrid: unknown parameter {name}")
        if tuple(val.shape) != tuple(p.shape):
            raise ValueError(f"{name}: built {val.shape}, model {p.shape}")
        p._value = jax.ShapeDtypeStruct(val.shape, want) if abstract \
            else val.astype(want)
        n_params += int(val.size)
    model.eval()
    return model, n_params

"""Builder: a configuration file of Xing4.0 (latent attention at YaRN's
frequencies in every layer, four residual streams mixed by manifold-
constrained hyper-connections at each of a layer's two sublayers, dense
SwiGLU in the first `first_k_dense_replace` layers, then the whole bank
of routed experts and a shared expert, untied head) -> the program's
`XingMoEForCausalLM`, holding the benchmark's seeded weights.

As in `glm_moe_dsa`, the module tree is built under `jax.eval_shape`
(the program's constructor initialises every parameter in float32) and
every leaf is then replaced by `lib.xing_weights`, one jitted program a
layer, so that the float32 draws of one layer are freed before the next
is made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.lib import xing_weights as xw

_ATTN = {"self_attn.q_a_proj.weight": "wqa",
         "self_attn.q_b_proj.weight": "wqb",
         "self_attn.kv_a_proj.weight": "wkva",
         "self_attn.kv_b_proj.weight": "wkvb",
         "self_attn.o_proj.weight": "wo"}
_DENSE = {"mlp.in_proj.weight": "w_in", "mlp.out_proj.weight": "w_out"}
_MOE = {"moe.router": "router", "moe.expert_bias": "bias",
        "shared_mlp.in_proj.weight": "shared_in",
        "shared_mlp.out_proj.weight": "shared_out"}
_EXPERTS = {"moe.w_in": "w_in", "moe.w_out": "w_out"}
# <sublayer's maps>.<leaf> -> (sublayer, leaf of lib.xing_weights.mhc)
_MHC = {f"{module}.{leaf}": (sub, name)
        for module, sub in (("attn_hc", "attn"), ("mlp_hc", "ffn"))
        for leaf, name in (("phi", "phi"), ("alpha", "a"), ("beta", "b"))}
_ONES = ("input_layernorm.weight", "post_attention_layernorm.weight",
         "self_attn.q_a_norm", "self_attn.kv_a_norm")
# float32 whatever the served dtype
_FLOAT32 = ("moe.expert_bias", "attn_hc.alpha", "attn_hc.beta",
            "mlp_hc.alpha", "mlp_hc.beta")


def xing_config(cfg, **over):
    from paddle_tpu.models import XingMoEConfig
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "rope_scaling", "hc_mult",
            "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max", "n_routed_experts", "n_shared_experts",
            "num_experts_per_tok", "n_group", "topk_group",
            "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
            "max_position_embeddings", "initializer_range", "dtype")
    args = {k: cfg[k] for k in same}
    if cfg.get("experts_held"):
        args["experts_held"] = tuple(cfg["experts_held"])
    args.update(over)
    return XingMoEConfig(**args)


def _skeleton(xcfg):
    from paddle_tpu.models import XingMoEForCausalLM
    box = []

    def make():
        box.append(XingMoEForCausalLM(xcfg))
        return 0

    jax.eval_shape(make)
    return box[0]


def build(cfg, seed, dtype=None, abstract=False):
    """The program's model for `cfg` with weights from `seed`; returns
    (model, number of parameters held here). `abstract` leaves every
    parameter a `jax.ShapeDtypeStruct`."""
    import paddle_tpu as paddle
    dt = jnp.dtype(dtype or cfg["dtype"])
    model = _skeleton(xing_config(cfg))
    paddle.seed(int(seed) & 0x7FFFFFFF)   # the skeleton left a tracer there
    key = xw.base_key(seed)
    make_layer = functools.partial(jax.jit, static_argnames=("is_dense",))(
        lambda k, index, is_dense: xw.layer(cfg, k, index,
                                            is_dense=is_dense))
    run = (lambda f, *a, **kw: jax.eval_shape(
        functools.partial(f, **kw), *a)) if abstract \
        else (lambda f, *a, **kw: f(*a, **kw))
    top = run(jax.jit(lambda k: xw.top(cfg, k)), key)
    layers = [run(make_layer, key, jnp.int32(i),
                  is_dense=i < cfg["first_k_dense_replace"])
              for i in range(cfg["num_hidden_layers"])]
    n_params = 0
    for name, p in model.named_parameters():
        parts = name.split(".")            # model.layers.<i>.<leaf>
        leaf = ".".join(parts[3:])
        w = layers[int(parts[2])] if parts[1] == "layers" else None
        want = jnp.dtype(jnp.float32) if leaf in _FLOAT32 else dt
        if name == "model.embed_tokens.weight":
            val = top["embed"]
        elif name == "lm_head.weight":
            val = top["head"]
        elif name == "model.norm.weight" or (w is not None
                                             and leaf in _ONES):
            val = jnp.ones(p.shape, dt)
        elif w is not None and leaf in _MHC:
            sub, which = _MHC[leaf]
            val = w["mhc"][sub][which]
        elif w is not None and leaf in _ATTN:
            val = w["attn"][_ATTN[leaf]]
        elif w is not None and leaf in _DENSE:
            val = w["dense"][_DENSE[leaf]]
        elif w is not None and leaf in _MOE:
            val = w["moe"][_MOE[leaf]]
        elif w is not None and leaf in _EXPERTS:
            val = w["experts"][_EXPERTS[leaf]]
        else:
            raise KeyError(f"builder xing_moe: unknown parameter {name}")
        if tuple(val.shape) != tuple(p.shape):
            raise ValueError(f"{name}: built {val.shape}, model {p.shape}")
        p._value = jax.ShapeDtypeStruct(val.shape, want) if abstract \
            else val.astype(want)
        n_params += int(val.size)
    model.eval()
    return model, n_params

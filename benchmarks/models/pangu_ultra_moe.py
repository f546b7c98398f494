"""Builder: a configuration file of openPangu-Ultra-MoE (latent
attention and four norms in every layer, dense SwiGLU in the first
`first_k_dense_replace` layers, then routed experts of which this chip
holds `experts_held` and a shared expert, untied head, one multi-token-
prediction module) -> the program's `OpenPanguMoEForCausalLM`, holding
the benchmark's seeded weights.

As in `glm_moe_dsa`, the module tree is built under `jax.eval_shape`
(the program's constructor initialises every parameter in float32) and
every leaf is then replaced by `lib.pangu_weights`, one jitted program a
layer, so that the float32 draws of one layer are freed before the next
is made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.lib import pangu_weights as pw

_ATTN = {"self_attn.q_a_proj.weight": "wqa",
         "self_attn.q_b_proj.weight": "wqb",
         "self_attn.kv_a_proj.weight": "wkva",
         "self_attn.kv_b_proj.weight": "wkvb",
         "self_attn.o_proj.weight": "wo"}
_DENSE = {"mlp.in_proj.weight": "w_in", "mlp.out_proj.weight": "w_out"}
_MOE = {"moe.router": "router",
        "shared_mlp.in_proj.weight": "shared_in",
        "shared_mlp.out_proj.weight": "shared_out"}
_EXPERTS = {"moe.w_in": "w_in", "moe.w_out": "w_out"}
_ONES = ("input_layernorm.weight", "post_attention_layernorm.weight",
         "pre_mlp_layernorm.weight", "post_mlp_layernorm.weight",
         "self_attn.q_a_norm", "self_attn.kv_a_norm")
# the router's choice bias: the published config has none (b = 0)
_ZEROS_FLOAT32 = ("moe.expert_bias",)


def pangu_config(cfg, **over):
    from paddle_tpu.models import OpenPanguMoEConfig
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "n_shared_experts",
            "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
            "num_nextn_predict_layers", "rms_norm_eps",
            "max_position_embeddings", "initializer_range", "dtype")
    args = {k: cfg[k] for k in same}
    args.update(n_routed_experts=cfg["published"]["n_routed_experts"],
                experts_held=tuple(cfg["experts_held"]))
    args.update(over)
    return OpenPanguMoEConfig(**args)


def _skeleton(pcfg):
    from paddle_tpu.models import OpenPanguMoEForCausalLM
    box = []

    def make():
        box.append(OpenPanguMoEForCausalLM(pcfg))
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
    model = _skeleton(pangu_config(cfg))
    paddle.seed(int(seed) & 0x7FFFFFFF)   # the skeleton left a tracer there
    key = pw.base_key(seed)
    make_layer = functools.partial(jax.jit, static_argnames=("is_dense",))(
        lambda k, index, is_dense: pw.layer(cfg, k, index,
                                            is_dense=is_dense))
    run = (lambda f, *a, **kw: jax.eval_shape(
        functools.partial(f, **kw), *a)) if abstract \
        else (lambda f, *a, **kw: f(*a, **kw))
    top = run(jax.jit(lambda k: pw.top(cfg, k)), key)
    layers = [run(make_layer, key, jnp.int32(i),
                  is_dense=i < cfg["first_k_dense_replace"])
              for i in range(cfg["num_hidden_layers"])]
    mtp_layer = run(make_layer, key, jnp.int32(pw.MTP_LAYER), is_dense=False)
    join = run(jax.jit(lambda k: pw.mtp_join(cfg, k)), key)
    n_params = 0
    for name, p in model.named_parameters():
        parts = name.split(".")
        # model.layers.<i>.<leaf> | model.mtp.layer.<leaf>
        in_mtp = parts[1] == "mtp"
        leaf = ".".join(parts[3:])
        w = layers[int(parts[2])] if parts[1] == "layers" \
            else mtp_layer if in_mtp and parts[2] == "layer" else None
        want = jnp.dtype(jnp.float32) if leaf in _ZEROS_FLOAT32 else dt
        if name == "model.embed_tokens.weight":
            val = top["embed"]
        elif name == "lm_head.weight":
            val = top["head"]
        elif name == "model.mtp.eh_proj.weight":
            val = join
        elif name in ("model.norm.weight", "model.mtp.enorm.weight",
                      "model.mtp.hnorm.weight", "model.mtp.norm.weight") \
                or (w is not None and leaf in _ONES):
            val = jnp.ones(p.shape, dt)
        elif w is not None and leaf in _ZEROS_FLOAT32:
            val = jnp.zeros(p.shape, jnp.float32)
        elif w is not None and leaf in _ATTN:
            val = w["attn"][_ATTN[leaf]]
        elif w is not None and leaf in _DENSE:
            val = w["dense"][_DENSE[leaf]]
        elif w is not None and leaf in _MOE:
            val = w["moe"][_MOE[leaf]]
        elif w is not None and leaf in _EXPERTS:
            val = w["experts"][_EXPERTS[leaf]]
        else:
            raise KeyError(f"builder pangu_ultra_moe: unknown parameter "
                           f"{name}")
        if tuple(val.shape) != tuple(p.shape):
            raise ValueError(f"{name}: built {val.shape}, model {p.shape}")
        p._value = jax.ShapeDtypeStruct(val.shape, want) if abstract \
            else val.astype(want)
        n_params += int(val.size)
    model.eval()
    return model, n_params

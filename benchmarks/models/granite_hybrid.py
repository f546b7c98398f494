"""Builder: a configuration file of the Granite-4.0-H family (Mamba-2
and NoPE attention layers by `layer_types`, routed experts of which this
chip holds `experts_held`, one shared expert, tied embedding) -> the
program's `GraniteMoeHybridForCausalLM`, holding the benchmark's seeded
weights.

As in `llama_like`, the module tree is built under `jax.eval_shape` (the
program's constructor initialises every parameter in float32, which the
chip cannot hold at these sizes) and every leaf is then replaced by
`lib.granite_weights`, one jitted program a layer, so that the float32
draws of one layer are freed before the next is made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.lib import granite_weights as gw

_MIXER = {"mamba.in_proj.weight": "in_proj", "mamba.conv_weight": "conv_w",
          "mamba.conv_bias": "conv_b", "mamba.dt_bias": "dt_bias",
          "mamba.A_log": "a_log", "mamba.out_proj.weight": "out_proj",
          "self_attn.q_proj.weight": "wq", "self_attn.k_proj.weight": "wk",
          "self_attn.v_proj.weight": "wv", "self_attn.o_proj.weight": "wo"}
_MOE = {"moe.router": "router", "shared_mlp.in_proj.weight": "shared_in",
        "shared_mlp.out_proj.weight": "shared_out"}
_EXPERTS = {"moe.w_in": "w_in", "moe.w_out": "w_out"}
_ONES = ("input_layernorm.weight", "post_attention_layernorm.weight",
         "mamba.norm_weight", "mamba.D")


def granite_config(cfg, **over):
    from paddle_tpu.models import GraniteMoeHybridConfig
    kw = dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        shared_intermediate_size=cfg["shared_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        layer_types=tuple(cfg["layer_types"]),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=gw.sizes(cfg)["head_dim"],
        attention_multiplier=cfg["attention_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        residual_multiplier=cfg["residual_multiplier"],
        rms_norm_eps=cfg["rms_norm_eps"],
        num_experts=cfg["published"]["num_local_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=tuple(cfg["experts_held"]),
        mamba_n_heads=cfg["mamba_n_heads"], mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg["initializer_range"], dtype=cfg["dtype"])
    kw.update(over)
    return GraniteMoeHybridConfig(**kw)


def _skeleton(gcfg):
    from paddle_tpu.models import GraniteMoeHybridForCausalLM
    box = []

    def make():
        box.append(GraniteMoeHybridForCausalLM(gcfg))
        return 0

    jax.eval_shape(make)
    return box[0]


def build(cfg, seed, dtype=None, abstract=False):
    """The program's model for `cfg` with weights from `seed`; returns
    (model, number of parameters held here). `abstract` leaves every
    parameter a `jax.ShapeDtypeStruct`."""
    import paddle_tpu as paddle
    dt = jnp.dtype(dtype or cfg["dtype"])
    if len(cfg["experts_held"]) != cfg["num_local_experts"]:
        raise ValueError("experts_held must list num_local_experts ids")
    model = _skeleton(granite_config(cfg))
    paddle.seed(int(seed) & 0x7FFFFFFF)   # the skeleton left a tracer there
    key = gw.base_key(seed)

    @functools.partial(jax.jit, static_argnames=("kind",))
    def make_layer(k, index, kind):
        # drawn in bfloat16 (the served type); another dtype gets the
        # same values
        return gw.layer(cfg, k, index, kind=kind)

    run = (lambda f, *a, **kw: jax.eval_shape(
        functools.partial(f, **kw), *a)) if abstract \
        else (lambda f, *a, **kw: f(*a, **kw))
    top = run(jax.jit(lambda k: gw.top(cfg, k)), key)
    layers = [run(make_layer, key, jnp.int32(i), kind=kind)
              for i, kind in enumerate(cfg["layer_types"])]
    n_params = 0
    for name, p in model.named_parameters():
        parts = name.split(".")
        if name == "model.embed_tokens.weight":
            val = top["embed"]
        elif name == "model.norm.weight":
            val = jnp.ones(p.shape, dt)
        elif parts[1] == "layers":
            leaf, w = ".".join(parts[3:]), layers[int(parts[2])]
            if leaf in _ONES:
                val = jnp.ones(p.shape, dt)
            elif leaf in _MIXER:
                val = w["mixer"][_MIXER[leaf]]
            elif leaf in _MOE:
                val = w["moe"][_MOE[leaf]]
            else:
                val = w["experts"][_EXPERTS[leaf]]
        else:
            raise KeyError(f"builder granite_hybrid: unknown parameter "
                           f"{name}")
        if tuple(val.shape) != tuple(p.shape):
            raise ValueError(f"{name}: built {val.shape}, model {p.shape}")
        p._value = jax.ShapeDtypeStruct(val.shape, dt) if abstract \
            else val.astype(dt)
        n_params += int(val.size)
    model.eval()
    return model, n_params

"""Builder: a configuration file of the Keye-VL-2.0 language model
(GQA under an attention indexer `sa_config`, routed experts of which
this chip holds `experts_held`, untied head) -> the program's
`KeyeVL2ForCausalLM`, holding the benchmark's seeded weights.

As in `granite_hybrid`, the module tree is built under `jax.eval_shape`
(the program's constructor initialises every parameter in float32) and
every leaf is then replaced by `lib.keye_weights`, one jitted program a
layer, so that the float32 draws of one layer are freed before the next
is made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.lib import keye_weights as kw

_ATTN = {"self_attn.q_proj.weight": "wq", "self_attn.k_proj.weight": "wk",
         "self_attn.v_proj.weight": "wv", "self_attn.o_proj.weight": "wo",
         "self_attn.index_q_proj.weight": "wqi",
         "self_attn.index_k_proj.weight": "wki",
         "self_attn.index_w_proj.weight": "ww", "moe.router": "router"}
_EXPERTS = {"moe.w_in": "w_in", "moe.w_out": "w_out"}
_ONES = ("input_layernorm.weight", "post_attention_layernorm.weight",
         "self_attn.q_norm", "self_attn.k_norm", "self_attn.index_k_norm")
_ZEROS = ("self_attn.index_k_norm_bias",)


def keye_config(cfg, **over):
    from paddle_tpu.models import KeyeVL2Config
    sa = cfg["sa_config"]
    args = dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["published"]["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=tuple(cfg["experts_held"]),
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        mrope_section=tuple(cfg["rope_scaling"]["mrope_section"]),
        indexer_head_dim=sa["indexer_head_dim"],
        indexer_num_heads=sa["indexer_num_heads"], index_topk=sa["topk"],
        q_chunk_size=sa["q_chunk_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        initializer_range=cfg["initializer_range"], dtype=cfg["dtype"])
    args.update(over)
    return KeyeVL2Config(**args)


def _skeleton(kcfg):
    from paddle_tpu.models import KeyeVL2ForCausalLM
    box = []

    def make():
        box.append(KeyeVL2ForCausalLM(kcfg))
        return 0

    jax.eval_shape(make)
    return box[0]


def build(cfg, seed, dtype=None, abstract=False):
    """The program's model for `cfg` with weights from `seed`; returns
    (model, number of parameters held here). `abstract` leaves every
    parameter a `jax.ShapeDtypeStruct`."""
    import paddle_tpu as paddle
    dt = jnp.dtype(dtype or cfg["dtype"])
    if not (len(cfg["experts_held"]) == cfg["num_experts"]
            == cfg["num_local_experts"]):
        raise ValueError("experts_held must list num_experts = "
                         "num_local_experts ids")
    model = _skeleton(keye_config(cfg))
    paddle.seed(int(seed) & 0x7FFFFFFF)   # the skeleton left a tracer there
    key = kw.base_key(seed)
    run = (lambda f, *a: jax.eval_shape(f, *a)) if abstract \
        else (lambda f, *a: f(*a))
    top = run(jax.jit(lambda k: kw.top(cfg, k)), key)
    make_layer = jax.jit(functools.partial(kw.layer, cfg))
    layers = [run(make_layer, key, jnp.int32(i))
              for i in range(cfg["num_hidden_layers"])]
    n_params = 0
    for name, p in model.named_parameters():
        parts = name.split(".")
        leaf = ".".join(parts[3:])
        if name == "model.embed_tokens.weight":
            val = top["embed"]
        elif name == "lm_head.weight":
            val = top["head"]
        elif name == "model.norm.weight" or leaf in _ONES:
            val = jnp.ones(p.shape, dt)
        elif leaf in _ZEROS:
            val = jnp.zeros(p.shape, dt)
        elif leaf in _ATTN:
            val = layers[int(parts[2])]["attn"][_ATTN[leaf]]
        elif leaf in _EXPERTS:
            val = layers[int(parts[2])]["experts"][_EXPERTS[leaf]]
        else:
            raise KeyError(f"builder keye_vl2: unknown parameter {name}")
        if tuple(val.shape) != tuple(p.shape):
            raise ValueError(f"{name}: built {val.shape}, model {p.shape}")
        p._value = jax.ShapeDtypeStruct(val.shape, dt) if abstract \
            else val.astype(dt)
        n_params += int(val.size)
    model.eval()
    return model, n_params

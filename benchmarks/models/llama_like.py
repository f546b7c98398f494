"""Builder: a configuration file of a Llama-shaped decoder (pre-norm
RMSNorm, rotary, SwiGLU, no biases, untied head) -> the program's
`LlamaForCausalLM`, holding the benchmark's seeded weights.

The program's constructor initialises every parameter in float32, which
a 16 GB chip cannot hold at these sizes, so the module tree is built
under `jax.eval_shape` (no array is made) and every leaf is then
replaced: parameters by `lib.weights.make_all`, the rotary tables by
the program's own `rope_freqs`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.lib import weights

_LEAF_OF = {"self_attn.q_proj": "wq", "self_attn.k_proj": "wk",
            "self_attn.v_proj": "wv", "self_attn.o_proj": "wo",
            "mlp.gate_proj": "w_gate", "mlp.up_proj": "w_up",
            "mlp.down_proj": "w_down"}


def llama_config(cfg, **over):
    from paddle_tpu.models import LlamaConfig
    kw = dict(vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
              intermediate_size=cfg["intermediate_size"],
              num_hidden_layers=cfg["num_hidden_layers"],
              num_attention_heads=cfg["num_attention_heads"],
              num_key_value_heads=cfg["num_key_value_heads"],
              max_position_embeddings=cfg["max_position_embeddings"],
              rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
              initializer_range=cfg["initializer_range"],
              tie_word_embeddings=cfg["tie_word_embeddings"],
              tensor_parallel=False, dtype=cfg["dtype"])
    kw.update(over)
    return LlamaConfig(**kw)


def _skeleton(lcfg):
    from paddle_tpu.models import LlamaForCausalLM
    box = []

    def make():
        box.append(LlamaForCausalLM(lcfg))
        return 0

    jax.eval_shape(make)
    return box[0]


def build(cfg, seed, dtype=None, abstract=False):
    """The program's model for `cfg` with weights from `seed`; returns
    (model, number of parameters). `abstract` leaves every parameter a
    `jax.ShapeDtypeStruct` (for compiling without a device to hold it)."""
    import paddle_tpu as paddle
    from paddle_tpu.kernels.rope import rope_freqs
    dt = jnp.dtype(dtype or cfg["dtype"])
    model = _skeleton(llama_config(cfg))
    paddle.seed(int(seed) & 0x7FFFFFFF)   # the skeleton left a tracer there
    # drawn in bfloat16 (the served type); another dtype gets the same values
    w = jax.eval_shape(lambda: weights.make_all(cfg, seed)) if abstract \
        else weights.make_all(cfg, seed)
    ones = jnp.ones((cfg["hidden_size"],), dt)
    n_params = 0
    for name, p in model.named_parameters():
        parts = name.split(".")
        if name == "llama.embed_tokens.weight":
            val = w["top"]["embed"]
        elif name == "lm_head.weight":
            val = w["top"]["head"]
        elif name.endswith("layernorm.weight") or name == "llama.norm.weight":
            val = ones
        elif parts[1] == "layers":
            val = w["layers"][int(parts[2])][_LEAF_OF[".".join(parts[3:5])]]
        else:
            raise KeyError(f"builder llama_like: unknown parameter {name}")
        if tuple(val.shape) != tuple(p.shape):
            raise ValueError(f"{name}: built {val.shape}, model {p.shape}")
        p._value = jax.ShapeDtypeStruct(val.shape, dt) if abstract \
            else val.astype(dt)
        n_params += int(val.size)
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    cos, sin = rope_freqs(d, cfg["max_position_embeddings"],
                          cfg["rope_theta"])
    for name, b in model.named_buffers():
        b._value = {"llama.rope_cos": cos, "llama.rope_sin": sin}[name]
    model.eval()
    return model, n_params

"""Llama family — the flagship pretraining model (driver config #3 /
north star: Llama-2-7B via Fleet sharding-3 + TP at ≥40% MFU).

Ecosystem parity: PaddleNLP paddlenlp/transformers/llama/modeling.py
(LlamaAttention/LlamaMLP/LlamaRMSNorm/LlamaForCausalLM with
fused_rotary_position_embedding + RingFlashAttention recipes).

TPU-native design:
- attention in [B, S, H, D] flash layout feeding the Pallas flash kernel
  (kernels/attention.py); GQA via K/V head broadcast inside the kernel
  wrapper;
- RoPE from kernels/rope.py (XLA-fused elementwise);
- RMSNorm via the fused kernel; SwiGLU MLP;
- TP through fleet's Column/Row/VocabParallel layers (GSPMD specs) so the
  same module runs single-chip or under any mesh;
- sequence dim ready for 'context' sharding (ring attention) — activations
  keep seq on axis 1 throughout.
"""
from __future__ import annotations

import math as pymath
from dataclasses import dataclass, field

import numpy as np
import jax.numpy as jnp

from ..tensor import Tensor
from ..nn.layer_base import Layer
from ..nn.layers_common import Embedding, Linear, LayerList, Dropout
from ..nn import functional as F
from ..nn.initializer import Normal
from ..ops import manipulation as M
from ..ops._dispatch import apply
from ..ops.creation import _coerce
from ..kernels.rope import rope_freqs, apply_rotary_emb
from ..kernels.norm import fused_rms_norm
from ..distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    parallel_matmul, mark_partition)
from ..distributed.fleet.recompute import recompute
from ..generation import GenerationMixin
from ..generation.kv_cache import (StaticCacheEntry, StaticKVCache,
                                   PagedKVCache)


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    use_recompute: bool = False
    tensor_parallel: bool = True
    dtype: str = "float32"

    @staticmethod
    def llama2_7b(**kw):
        # published Llama-2-7B config.json: rms_norm_eps 1e-05 (the
        # dataclass default 1e-6 is Llama-1's)
        kw.setdefault("rms_norm_eps", 1e-5)
        return LlamaConfig(**kw)

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=128, intermediate_size=256,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=4, max_position_embeddings=256)
        base.update(kw)
        return LlamaConfig(**base)


class LlamaRMSNorm(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        from ..nn.initializer import Constant
        self.weight = self.create_parameter(
            [config.hidden_size], default_initializer=Constant(1.0))
        self.variance_epsilon = config.rms_norm_eps

    def forward(self, x):
        return apply(lambda v, w: fused_rms_norm(v, w, self.variance_epsilon),
                     x, self.weight, _name="rms_norm")


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.hidden_size = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        init = Normal(0.0, config.initializer_range)
        LinQ = ColumnParallelLinear if config.tensor_parallel else Linear
        LinO = RowParallelLinear if config.tensor_parallel else Linear
        kw = dict(gather_output=False) if config.tensor_parallel else {}
        okw = dict(input_is_parallel=True) if config.tensor_parallel else {}
        self.q_proj = LinQ(self.hidden_size, self.num_heads * self.head_dim,
                           weight_attr=init, has_bias=False, **kw) \
            if config.tensor_parallel else Linear(
                self.hidden_size, self.num_heads * self.head_dim,
                weight_attr=init, bias_attr=False)
        self.k_proj = LinQ(self.hidden_size, self.num_kv_heads * self.head_dim,
                           weight_attr=init, has_bias=False, **kw) \
            if config.tensor_parallel else Linear(
                self.hidden_size, self.num_kv_heads * self.head_dim,
                weight_attr=init, bias_attr=False)
        self.v_proj = LinQ(self.hidden_size, self.num_kv_heads * self.head_dim,
                           weight_attr=init, has_bias=False, **kw) \
            if config.tensor_parallel else Linear(
                self.hidden_size, self.num_kv_heads * self.head_dim,
                weight_attr=init, bias_attr=False)
        self.o_proj = LinO(self.num_heads * self.head_dim, self.hidden_size,
                           weight_attr=init, has_bias=False, **okw) \
            if config.tensor_parallel else Linear(
                self.num_heads * self.head_dim, self.hidden_size,
                weight_attr=init, bias_attr=False)

    def forward(self, hidden_states, cos, sin, attn_mask=None,
                position_ids=None, past_key_value=None):
        b, s, _ = hidden_states.shape
        q = M.reshape(self.q_proj(hidden_states),
                      [b, s, self.num_heads, self.head_dim])
        k = M.reshape(self.k_proj(hidden_states),
                      [b, s, self.num_kv_heads, self.head_dim])
        v = M.reshape(self.v_proj(hidden_states),
                      [b, s, self.num_kv_heads, self.head_dim])

        def rope_fn(qv, kv, cv, sv):
            return apply_rotary_emb(qv, kv, cv, sv)
        q, k = apply(rope_fn, q, k, cos, sin, _name="fused_rope")

        from ..generation.kv_cache import PagedCacheEntry
        if isinstance(past_key_value, PagedCacheEntry):
            # paged decode cache (serving continuous batching): write the
            # step's K/V into each slot's page and attend via the paged
            # Pallas kernel — shared contract,
            # generation/kv_cache.py paged_cache_update_attend
            from ..generation.kv_cache import paged_cache_update_attend
            out, new_cache = paged_cache_update_attend(
                past_key_value, q, k, v)
            out = M.reshape(out, [b, s, self.num_heads * self.head_dim])
            return self.o_proj(out), new_cache
        if isinstance(past_key_value, StaticCacheEntry):
            # static-shape decode cache: in-place write at `pos` (shared
            # contract — generation/kv_cache.py static_cache_update)
            from ..generation.kv_cache import static_cache_update
            k, v, new_cache = static_cache_update(past_key_value, k, v)
        elif past_key_value is not None:
            k = M.concat([past_key_value[0], k], axis=1)
            v = M.concat([past_key_value[1], v], axis=1)
            new_cache = (k, v)
        else:
            new_cache = (k, v)

        # GQA: kv heads are NOT repeated here — the flash kernel consumes
        # grouped kv natively (kernels/attention.py GQA index maps) and the
        # XLA fallback repeats internally only when it must.
        causal = past_key_value is None
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=causal,
            training=self.training)
        out = M.reshape(out, [b, s, self.num_heads * self.head_dim])
        return self.o_proj(out), new_cache


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        init = Normal(0.0, config.initializer_range)
        tp = config.tensor_parallel
        if tp:
            self.gate_proj = ColumnParallelLinear(
                config.hidden_size, config.intermediate_size,
                weight_attr=init, has_bias=False, gather_output=False)
            self.up_proj = ColumnParallelLinear(
                config.hidden_size, config.intermediate_size,
                weight_attr=init, has_bias=False, gather_output=False)
            self.down_proj = RowParallelLinear(
                config.intermediate_size, config.hidden_size,
                weight_attr=init, has_bias=False, input_is_parallel=True)
        else:
            self.gate_proj = Linear(config.hidden_size,
                                    config.intermediate_size,
                                    weight_attr=init, bias_attr=False)
            self.up_proj = Linear(config.hidden_size,
                                  config.intermediate_size,
                                  weight_attr=init, bias_attr=False)
            self.down_proj = Linear(config.intermediate_size,
                                    config.hidden_size,
                                    weight_attr=init, bias_attr=False)

    def forward(self, x):
        from ..incubate.nn.functional import swiglu
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = LlamaRMSNorm(config)
        self.post_attention_layernorm = LlamaRMSNorm(config)

    def forward(self, hidden_states, cos, sin, attn_mask=None,
                position_ids=None, past_key_value=None):
        residual = hidden_states
        h = self.input_layernorm(hidden_states)
        h, cache = self.self_attn(h, cos, sin, attn_mask, position_ids,
                                  past_key_value)
        h = residual + h
        residual = h
        h2 = self.post_attention_layernorm(h)
        h2 = self.mlp(h2)
        return residual + h2, cache


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        init = Normal(0.0, config.initializer_range)
        if config.tensor_parallel:
            self.embed_tokens = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size, weight_attr=init)
        else:
            self.embed_tokens = Embedding(config.vocab_size,
                                          config.hidden_size,
                                          weight_attr=init)
        self.layers = LayerList(
            [LlamaDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config)
        cos, sin = rope_freqs(config.hidden_size // config.num_attention_heads,
                              config.max_position_embeddings,
                              config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                past_key_values=None, use_cache=False):
        h = self.embed_tokens(input_ids)
        s = input_ids.shape[1]
        static_cache = isinstance(past_key_values,
                                  (StaticKVCache, PagedKVCache))
        if position_ids is not None:
            # per-row positions (left-padded generation): gather trig rows
            cos = apply(lambda c, p: jnp.take(c, p, axis=0),
                        self.rope_cos, position_ids, _name="rope_gather")
            sin = apply(lambda c, p: jnp.take(c, p, axis=0),
                        self.rope_sin, position_ids, _name="rope_gather")
        else:
            past_len = 0
            if (not static_cache and past_key_values is not None
                    and past_key_values[0] is not None):
                past_len = past_key_values[0][0].shape[1]
            cos = self.rope_cos[past_len:past_len + s]
            sin = self.rope_sin[past_len:past_len + s]
        caches = []
        for i, layer in enumerate(self.layers):
            pkv = past_key_values[i] if past_key_values is not None else None
            if self.config.use_recompute and self.training and pkv is None:
                h, cache = recompute(layer.forward, h, cos, sin, attn_mask,
                                     position_ids, None)
            else:
                h, cache = layer(h, cos, sin, attn_mask, position_ids, pkv)
            caches.append(cache)
        h = self.norm(h)
        if use_cache:
            return h, caches
        return h


class LlamaForCausalLM(Layer, GenerationMixin):
    supports_static_cache = True

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        init = Normal(0.0, config.initializer_range)
        if config.tie_word_embeddings:
            self.lm_head = None
        elif config.tensor_parallel:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, weight_attr=init,
                has_bias=False, gather_output=False)
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  weight_attr=init, bias_attr=False)

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                past_key_values=None, use_cache=False):
        out = self.llama(input_ids, attn_mask, position_ids, past_key_values,
                         use_cache)
        if use_cache:
            h, caches = out
        else:
            h = out
        if self.lm_head is None:
            logits = parallel_matmul(h, self.llama.embed_tokens.weight,
                                     transpose_y=True)
        else:
            logits = self.lm_head(h)
        if use_cache:
            return logits, caches
        return logits

    @property
    def backbone(self):
        return self.llama

    def cache_layout(self):
        """What each layer keeps between steps, for the serve loop
        (generation/kv_cache.py `LayerCache`): K/V pages, every layer."""
        from ..generation.kv_cache import LayerCache
        c = self.config
        return [LayerCache("kv", (c.num_key_value_heads,
                                  c.hidden_size // c.num_attention_heads))
                ] * c.num_hidden_layers

    def load_hf_state_dict(self, hf_state_dict):
        """Import HuggingFace Llama weights (ecosystem parity:
        PaddleNLP's convert from transformers checkpoints). Accepts an
        HF model's state_dict (torch tensors or arrays); names map 1:1
        with the `model.` → `llama.` prefix swap and 2-D Linear weights
        transpose to paddle's [in, out] layout. Verified bit-tight
        against transformers (tests/test_hf_parity.py)."""
        from ..tensor import Tensor
        from ._hf_import import hf_tensor_to_numpy as to_np, validate_keys
        import numpy as np
        sd = {}
        for name, p in hf_state_dict.items():
            if name == "lm_head.weight" and self.lm_head is None:
                # tied-embedding checkpoints carry the tied weight under
                # both keys; the tied model reads embed_tokens only
                continue
            a = to_np(p)
            our = name.replace("model.", "llama.", 1)
            if name.endswith(".weight") and a.ndim == 2 \
                    and "embed_tokens" not in name:
                a = a.T
            sd[our] = Tensor(np.ascontiguousarray(a))
        validate_keys(self, sd, "HF Llama")
        self.set_state_dict(sd)
        return self


class LlamaPretrainingCriterion(Layer):
    """Shift-labels causal LM loss (ecosystem parity: PaddleNLP
    LlamaPretrainingCriterion)."""

    def __init__(self, config: LlamaConfig = None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, labels):
        # logits [B, S, V]; labels [B, S] — predict token t+1
        lg = logits[:, :-1, :]
        lb = labels[:, 1:]
        b, s, v = lg.shape
        loss = F.cross_entropy(M.reshape(lg, [b * s, v]),
                               M.reshape(lb, [b * s]),
                               ignore_index=self.ignore_index)
        return loss

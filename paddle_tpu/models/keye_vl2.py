"""Keye-VL-2.0 (`model_type: KeyeVL2`), the language model: grouped-query
attention whose keys are chosen, token by token, by a learned indexer
(the DeepSeek-Sparse-Attention form), and a routed expert layer in every
block. The vision tower is not built: the model is served on token ids.

Written from the published config keys (`sa_config` for the indexer);
what the config does not say is marked (assumed). h in R^hidden, no
bias anywhere, RMSNorm eps `rms_norm_eps`, x = RMSNorm(h):

- projections: `q = W_q x` (heads x head_dim), `k = W_k x`, `v = W_v x`
  (kv heads x head_dim); RMSNorm with a learned gain over head_dim on
  every q head and k head before the rotation (assumed: the base
  family's QK-norm). RoPE over all of head_dim at `rope_theta`, pairs
  (i, i + head_dim / 2); `mrope_section` shares the frequency pairs out
  over a (t, h, w) position triple, which for a text token is (p, p, p):
  the ordinary rotation at p (`mrope_angles`).
- indexer: `qI = W_qI x` (index heads x index dim), `kI =
  LayerNorm(W_kI x)` (ONE key a token), `w = W_w x` (a weight an index
  head); qI and kI rotated at p over the index dim at the same theta
  (assumed). `I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`, s <= t.
- selection: `S_t` = the min(topk, t + 1) keys of largest `I[t, s]`,
  ties to the lower s; one set a token for every head.
- attention: head i of group g: softmax over `S_t` of `q[t, i] . k[s,
  g] / sqrt(head_dim)`, times v; `h += W_o o`.
- experts: y = RMSNorm(h); `r = softmax(W_r y)` over all experts; the
  `num_experts_per_tok` largest, gates `r_e / sum r` over them
  (`norm_topk_prob`), which is the softmax over the selected logits
  that `DroplessMoELayer` computes; no shared expert.
- logits: the untied head on the final RMSNorm.

Float32 whatever the weights' dtype: the norms' statistics, the
rotation, router logits and gates, index scores, the selection, the
attention softmax and the logits the head gives out. Weights,
activations, K, V and index keys follow the parameters' dtype.

The model declares what a layer keeps between steps (`cache_layout()`):
K/V pages and, beside them, a page array of index keys
(`LayerCache.index_dim`). Three calls reach `forward`:

- no `past_key_values`: a whole left-padded batch. `attn_mask` is the
  key-validity mask [B, S] (bool; the serve prefill's) or the additive
  [B, 1, S, S] mask other models take, of which only the validity of
  the keys is read: causality comes from the order of the positions and
  the selection from the indexer, in chunks of `q_chunk_size` queries
  (kernels/sparse_attention.py). With `use_cache` the logits are those
  of the LAST position alone ([B, 1, vocab]: what a prefill continues
  from) and `caches` holds (k, v, kI) a layer.
- a `PagedKVCache`: one decode step a slot through the pages
  (kernels/paged_attention.py `paged_sparse_attention`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn.layer_base import Layer
from ..nn.layers_common import Embedding, Linear, LayerList
from ..nn.initializer import Constant, Normal
from ..ops._dispatch import apply
from ..generation.kv_cache import (LayerCache, LayerCaches, PagedKVCache,
                                   paged_cache_sparse_update_attend)
from ..incubate.distributed.models.moe.dropless import DroplessMoELayer
from ..kernels.sparse_attention import (chunk_key_blocks, chunk_plan,
                                        plan_counts,
                                        sparse_prefill_attention)
from .granite_hybrid import GraniteRMSNorm as RMSNorm

F32 = jnp.float32


@dataclass
class KeyeVL2Config:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128                   # not hidden / heads
    moe_intermediate_size: int = 768      # one routed expert's width
    num_experts: int = 128                # the router's width
    num_experts_per_tok: int = 8
    experts_held: Optional[Tuple[int, ...]] = None   # None: all of them
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    indexer_head_dim: int = 64
    indexer_num_heads: int = 16
    index_topk: int = 2048
    q_chunk_size: int = 512
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        self.mrope_section = tuple(self.mrope_section)
        if sum(self.mrope_section) != self.head_dim // 2:
            raise ValueError(
                f"mrope_section {self.mrope_section} does not share out "
                f"the {self.head_dim // 2} frequency pairs of head_dim "
                f"{self.head_dim}")

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=8, num_key_value_heads=2,
                    head_dim=16, moe_intermediate_size=32, num_experts=8,
                    num_experts_per_tok=2, mrope_section=(2, 3, 3),
                    indexer_head_dim=8, indexer_num_heads=4, index_topk=8,
                    q_chunk_size=8, max_position_embeddings=256)
        base.update(kw)
        return KeyeVL2Config(**base)


def rope_angles(pos, dim, theta, inv_freq=None):
    """pos [...] int -> angles [..., dim / 2] float32: pair i turns by
    pos * theta^(-2i / dim). A model whose frequencies are scaled (YaRN:
    models/xing_moe.py `yarn_frequencies`) hands in its own table
    `inv_freq` [dim / 2] in their place."""
    inv = F32(1.0) / (F32(theta) ** (jnp.arange(0, dim, 2, dtype=F32)
                                     / F32(dim))) if inv_freq is None \
        else jnp.asarray(inv_freq, F32)
    return pos.astype(F32)[..., None] * inv


def mrope_angles(pos3, dim, theta, sections):
    """pos3 [3, ...] (t, h, w) -> angles [..., dim / 2]: the first
    `sections[0]` frequency pairs turn by t, the next by h, the rest by
    w. At t = h = w = p these are `rope_angles(p)`."""
    which = jnp.repeat(jnp.arange(3, dtype=jnp.int32),
                       jnp.asarray(sections, jnp.int32),
                       total_repeat_length=dim // 2)
    every = rope_angles(pos3, dim, theta)                  # [3, ..., d/2]
    return jnp.take_along_axis(
        jnp.moveaxis(every, 0, -1), which.reshape(
            (1,) * (every.ndim - 2) + (-1, 1)), axis=-1)[..., 0]


def rotate(x, angles):
    """x [B, S, heads, D] (or [B, S, D]) by angles [B, S, D / 2], pairs
    (i, i + D / 2), in float32."""
    if x.ndim == 4:
        angles = angles[:, :, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _rms(x, w, eps):
    v = x.astype(F32)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + F32(eps))
    return (v * w.astype(F32)).astype(x.dtype)


def _layer_norm(x, w, b, eps):
    v = x.astype(F32)
    v = v - jnp.mean(v, axis=-1, keepdims=True)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + F32(eps))
    return (v * w.astype(F32) + b.astype(F32)).astype(x.dtype)


class KeyeSparseAttention(Layer):
    """GQA over the keys the layer's indexer selects."""

    def __init__(self, config: KeyeVL2Config):
        super().__init__()
        c = self.config = config
        init = Normal(0.0, c.initializer_range)
        lin = lambda n_in, n_out: Linear(n_in, n_out, weight_attr=init,
                                         bias_attr=False)
        ones = lambda n: self.create_parameter(
            [n], default_initializer=Constant(1.0))
        self.q_proj = lin(c.hidden_size, c.num_attention_heads * c.head_dim)
        self.k_proj = lin(c.hidden_size, c.num_key_value_heads * c.head_dim)
        self.v_proj = lin(c.hidden_size, c.num_key_value_heads * c.head_dim)
        self.o_proj = lin(c.num_attention_heads * c.head_dim, c.hidden_size)
        self.q_norm = ones(c.head_dim)
        self.k_norm = ones(c.head_dim)
        self.index_q_proj = lin(c.hidden_size,
                                c.indexer_num_heads * c.indexer_head_dim)
        self.index_k_proj = lin(c.hidden_size, c.indexer_head_dim)
        self.index_w_proj = lin(c.hidden_size, c.indexer_num_heads)
        self.index_k_norm = ones(c.indexer_head_dim)
        self.index_k_norm_bias = self.create_parameter(
            [c.indexer_head_dim], default_initializer=Constant(0.0))

    def _weights(self):
        return [self.q_proj.weight, self.k_proj.weight, self.v_proj.weight,
                self.o_proj.weight, self.q_norm, self.k_norm,
                self.index_q_proj.weight, self.index_k_proj.weight,
                self.index_w_proj.weight, self.index_k_norm,
                self.index_k_norm_bias]

    def _project(self, x, pos, wq, wk, wv, gq, gk, wqi, wki, ww, gi, bi):
        """x [B, S, hidden], pos [B, S] -> q, k, v, qI, w, kI, rotated."""
        c = self.config
        b, s, _ = x.shape
        heads = lambda a, n, d: a.reshape(b, s, n, d)
        ang = mrope_angles(jnp.broadcast_to(pos, (3,) + pos.shape),
                           c.head_dim, c.rope_theta, c.mrope_section)
        q = heads(jnp.dot(x, wq), c.num_attention_heads, c.head_dim)
        k = heads(jnp.dot(x, wk), c.num_key_value_heads, c.head_dim)
        v = heads(jnp.dot(x, wv), c.num_key_value_heads, c.head_dim)
        q = rotate(_rms(q, gq, c.rms_norm_eps), ang)
        k = rotate(_rms(k, gk, c.rms_norm_eps), ang)
        iang = rope_angles(pos, c.indexer_head_dim, c.rope_theta)
        qi = rotate(heads(jnp.dot(x, wqi), c.indexer_num_heads,
                          c.indexer_head_dim), iang)
        ki = rotate(_layer_norm(jnp.dot(x, wki), gi, bi, c.rms_norm_eps),
                    iang)
        w = jnp.dot(x, ww, preferred_element_type=F32)
        return q, k, v, qi, w, ki

    def _whole(self, x, pos, valid, plan, blocks, wq, wk, wv, wo, *rest):
        c = self.config
        q, k, v, qi, w, ki = self._project(x, pos, wq, wk, wv, *rest)
        out = sparse_prefill_attention(
            q, k, v, qi, w, ki, valid, topk=c.index_topk,
            scale=c.head_dim ** -0.5, chunk=c.q_chunk_size,
            plan=(plan, blocks))
        return jnp.dot(out.reshape(x.shape[:2] + (-1,)), wo), k, v, ki

    def forward(self, x, pos, valid=None, cache=None, plan=None):
        """x [B, S, hidden]; pos [B, S] int32. Without `cache`: the
        whole batch from nothing, `valid` [B, S] its real positions and
        `plan` what each chunk of queries has to do and which key
        blocks it visits (`chunk_plan`, `chunk_key_blocks`: the model
        computes the pair once for all layers); returns (out, (k, v,
        kI)). With a `PagedCacheEntry` (S == 1): one decode step;
        returns (out, entry, counts [B] = keys each slot's token
        attended to)."""
        c = self.config
        if cache is None:
            out, k, v, ki = apply(self._whole, x, pos, valid, *plan,
                                  *self._weights(), _name="sparse_attention")
            return out, (k, v, ki)
        if x.shape[1] != 1:
            raise NotImplementedError(
                "a layer with an indexer takes one token a slot a step: a "
                "query span (chunked prefill, speculative verify) would "
                "select a set for each of its positions")
        wo = self.o_proj.weight
        q, k, v, qi, w, ki = apply(
            lambda xv, pv, wq, wk, wv, _wo, *rest: self._project(
                xv, pv, wq, wk, wv, *rest),
            x, pos, *self._weights(), _name="sparse_attention_project")
        out, entry, n_sel = paged_cache_sparse_update_attend(
            cache, q, k, v, qi, w, ki, c.index_topk, c.head_dim ** -0.5)
        out = apply(lambda o, wv: jnp.dot(o.reshape(o.shape[:2] + (-1,)),
                                          wv), out, wo, _name="o_proj")
        return out, entry, n_sel


class KeyeDecoderLayer(Layer):
    def __init__(self, config: KeyeVL2Config):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.self_attn = KeyeSparseAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        self.moe = DroplessMoELayer(
            config.hidden_size, config.moe_intermediate_size,
            config.num_experts, config.num_experts_per_tok,
            held=config.experts_held,
            initializer_range=config.initializer_range)

    def forward(self, h, pos, valid, cache, plan):
        x, *kept = self.self_attn(self.input_layernorm(h), pos, valid, cache,
                                  plan)
        h = h + x
        routed, counts = self.moe(self.post_attention_layernorm(h), valid)
        return h + routed, kept, counts


class KeyeVL2Model(Layer):
    def __init__(self, config: KeyeVL2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))
        self.layers = LayerList([KeyeDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)


class KeyeVL2ForCausalLM(Layer):
    """`forward(ids, attn_mask, position_ids, past_key_values,
    use_cache) -> logits | (logits, caches)`, the call the serve
    programs make (the module's docstring says what each argument may
    be)."""

    # the serve loop's prefill hands over the keys' validity, not a
    # dense mask, and takes the last position's logits (inference/
    # __init__.py, "the long prefill")
    long_prefill = True

    def __init__(self, config: KeyeVL2Config):
        super().__init__()
        self.config = config
        self.model = KeyeVL2Model(config)
        self.lm_head = Linear(
            config.hidden_size, config.vocab_size, bias_attr=False,
            weight_attr=Normal(0.0, config.initializer_range))

    def cache_layout(self):
        """What each layer keeps between steps (generation/kv_cache.py
        `LayerCache`): K/V pages and a page array of index keys."""
        c = self.config
        return [LayerCache("kv", (c.num_key_value_heads, c.head_dim),
                           c.indexer_head_dim)] * c.num_hidden_layers

    def step_counters(self):
        """What the vectors in `caches.counters` count, element by
        element: {key: [(metric, labels)]} (docs/OBSERVABILITY.md). A
        decode step gives the first two of "dsa" and a prefill all
        nine, the first two zero: it counts what its query chunks did
        (`kernels.sparse_attention.plan_counts`)."""
        c = self.config
        held = range(c.num_experts) if c.experts_held is None \
            else c.experts_held
        return {"dsa": [("dsa.keys_live", {}), ("dsa.keys_selected", {})]
                + [("dsa.prefill_chunks", {"kind": kind})
                   for kind in ("padding", "dense", "selected")]
                + [("dsa.prefill_keys_counted", {}),
                   ("dsa.prefill_keys_bucket", {})]
                + [("dsa.prefill_key_blocks", {"kind": kind})
                   for kind in ("attended", "bucket")],
                "moe": [("moe.assignments", {}),
                        ("moe.assignments_local", {})]
                + [("moe.expert_tokens", {"expert": str(e)}) for e in held]}

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                past_key_values=None, use_cache=False):
        paged = past_key_values is not None
        if paged and not isinstance(past_key_values, PagedKVCache):
            raise NotImplementedError(
                "KeyeVL2ForCausalLM continues only from the serve loop's "
                "caches (PagedKVCache of page entries with index pages)")
        if position_ids is not None and len(position_ids.shape) != 2:
            raise NotImplementedError(
                "position_ids: text positions [batch, sequence] are served; "
                "a multimodal (t, h, w) triple needs the vision tower's "
                "grid, which is not built")
        m = self.model
        h = m.embed_tokens(input_ids)
        b, s = input_ids.shape
        if position_ids is None:
            position_ids = apply(lambda ids: jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), (b, s)), input_ids,
                _name="positions")
        valid = None
        if paged:
            if past_key_values.active is not None:
                valid = apply(lambda a: a[:, None], past_key_values.active,
                              _name="active")
        elif attn_mask is None:
            valid = apply(lambda ids: jnp.ones(ids.shape, jnp.bool_),
                          input_ids, _name="valid_positions")
        else:
            # additive [B, 1, S, S]: a key is real where the last query
            # may see it
            valid = apply(lambda mk: mk if mk.ndim == 2
                          else mk[:, 0, -1, :] > -1.0, attn_mask,
                          _name="valid_positions")
        c = self.config
        # what a prefill's chunks of queries have to do and which key
        # blocks they visit, once for all layers
        rep = c.num_attention_heads // c.num_key_value_heads
        plan = None if paged else apply(
            lambda ok: (chunk_plan(ok, c.q_chunk_size, c.index_topk),
                        chunk_key_blocks(ok, c.q_chunk_size, rep)), valid,
            _name="prefill_plan")
        caches, moe, dsa = [], None, None
        for i, layer in enumerate(m.layers):
            cache = past_key_values[i] if paged else None
            h, kept, n = layer(h, position_ids, valid, cache, plan)
            caches.append(kept[0])
            moe = n if moe is None else moe + n
            if paged:
                dsa = kept[1] if dsa is None else dsa + kept[1]
        if use_cache and not paged:
            h = h[:, -1:]       # a prefill continues from its last position
        # float32 logits from the bfloat16 operands (the MXU accumulates
        # so anyway): rounded to bfloat16, logits near 4 lie 1/64 apart,
        # wider than most gaps between a token's two best
        logits = apply(lambda x, w: jnp.dot(x, w, preferred_element_type=F32),
                       m.norm(h), self.lm_head.weight, _name="lm_head")
        if not use_cache:
            return logits
        n_layers = len(m.layers)

        def step_counts(n_sel, ctx, *on):
            """[keys the step's tokens could see, keys they attended
            to], over the layers and the slots that carry a request."""
            live = (ctx.astype(jnp.int32) + 1) * jnp.int32(n_layers)
            if on:
                live = jnp.where(on[0][:, 0], live, 0)
                n_sel = jnp.where(on[0][:, 0], n_sel, 0)
            return jnp.stack([jnp.sum(live, dtype=jnp.int32),
                              jnp.sum(n_sel, dtype=jnp.int32)])

        if paged:
            dsa = apply(step_counts, dsa, past_key_values[0].context_lens,
                        *(() if valid is None else (valid,)),
                        _name="dsa_counts")
        else:       # keys are counted by decode steps: one query a slot
            dsa = apply(lambda p, blocks: jnp.pad(plan_counts(
                p, blocks, c.q_chunk_size, s) * jnp.int32(n_layers), (2, 0)),
                *plan, _name="dsa_counts")
        return logits, LayerCaches(caches, {"dsa": dsa, "moe": moe})

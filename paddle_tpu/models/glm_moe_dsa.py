"""GLM-5 (`model_type: glm_moe_dsa`), the language model: multi-head
latent attention in every layer, its keys chosen token by token by a
learned indexer (the DeepSeek-Sparse-Attention form) that scores ONE
index key a token and selects among the latent rows; the query is
compressed to a latent that feeds the heads and the indexer alike; dense
SwiGLU in the first `first_k_dense_replace` layers and, after them, a
routed expert layer with sigmoid, bias-corrected routing and a shared
expert. The multi-token-prediction layer is not built.

Written from the published config keys, DeepSeek-V2 (MLA,
arXiv:2405.04434), DeepSeek-V3 (routing, arXiv:2412.19437) and the
DeepSeek-V3.2 description of the indexer; what the config has no key for
is marked (assumed). h in R^hidden, no bias but the index key's
LayerNorm, RMSNorm eps `rms_norm_eps`, pre-norm block `h +=
attn(norm(h))`, `h += ffn(norm(h))`, x = norm(h):

- query: `c_q = RMSNorm(W_qa x)` (`q_lora_rank`); `q = W_qb c_q` -> H x
  [nope | rope].
- keys: `[c | k_r] = W_kva x`; `c <- RMSNorm(c)`; `q_rope` and `k_r`
  rotated at the token's position, interleaved pairs (2i, 2i + 1),
  `rope_theta` (`k_r` shared by the heads); `[k_nope,h | v_h] = W_kvb,h
  c`. Kept a token: `[c | k_r]`, ONE row for all heads, and the index
  key.
- indexer: `qI = W_iq c_q` (J heads x Di, from the COMPRESSED query),
  `kI = LayerNorm(W_ik x)` (one key a token; eps `index_norm_eps`,
  assumed), the first `qk_rope_head_dim` numbers of each rotated as
  above (assumed: which part), `w = W_iw x / sqrt(J Di)`; `I[t, s] =
  sum_j w[t, j] relu(qI[t, j] . kI[s])`, s <= t: `kernels/
  sparse_attention.py`'s rule with the published scales folded into w.
- selection: `S_t` = the min(index_topk, t + 1) keys of largest `I[t,
  s]`, ties to the lower s; one set a token for every head.
- attention: scores `(q_nope . k_nope + q_rope . k_r) / sqrt(nope +
  rope)`, softmax over `S_t`, `o_h = sum p v_h`; `h += W_o o`. A prompt
  is computed decompressed (k and v formed for every head, the masked
  flash kernel of kernels/sparse_attention.py at `rep` 1); a decode step
  absorbs `W_kvb`: `q^_h = W_kvbK,h^T q_nope,h` against `c`, `o_h =
  W_kvbV,h (sum p c)` (kernels/latent_attention.py).
- experts: `s = sigmoid(W_r y)` float32; choice by `s + b`
  (`n_group` = `topk_group` = 1: no group is closed), the
  `num_experts_per_tok` largest; gates the chosen `s` over their sum,
  times `routed_scaling_factor` (`group_limited_sigmoid_route`); plus
  the shared expert, ungated.
- logits: the untied head on the final RMSNorm, float32.

Float32 whatever the weights' dtype: the norms' statistics, the
rotation, the router's scores and gates, index weights and scores, the
selection, every softmax and the logits.

The model declares what a layer keeps between steps (`cache_layout()`):
latent pages with an index key a token beside them, and no K/V
anywhere. It asks for the serve loop's long prefill (`long_prefill`).
Two calls reach `forward`:

- no `past_key_values`: a whole left-padded batch. `attn_mask` is the
  key-validity mask [B, S] (bool; the serve prefill's) or the additive
  [B, 1, S, S] mask other models take, of which only the validity of the
  keys is read. A prompt's queries are made, scored, selected for and
  attended `q_chunk_size` at a time, so nothing of [heads, prompt] or
  [prompt, prompt] extent outlives a chunk but the keys and values.
  With `use_cache` the logits are those of the LAST position alone and
  `caches` holds (latent rows, index keys) a layer.
- a `PagedKVCache`: one decode step a slot.
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
                                   paged_cache_sparse_latent_update_attend)
from ..kernels.sparse_attention import (PADDING, SELECTED, chunk_key_blocks,
                                        chunk_plan, plan_counts,
                                        prefill_index_scores, select_topk,
                                        selected_attention)
from .granite_hybrid import GraniteRMSNorm as RMSNorm
from .keye_vl2 import _layer_norm, _rms, rope_angles
from .ling_hybrid import LingMLP, LingSparseMoE, rotate_interleaved

F32 = jnp.float32


@dataclass
class GlmMoeDsaConfig:
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288        # the dense layers' width
    moe_intermediate_size: int = 2048     # one routed expert's width
    num_hidden_layers: int = 78
    first_k_dense_replace: int = 3
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    index_norm_eps: float = 1e-6          # (assumed) the index key's LayerNorm
    q_chunk_size: int = 512               # a prompt's queries a pass
    n_routed_experts: int = 256           # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    experts_held: Optional[Tuple[int, ...]] = None   # None: all of them
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 202752
    initializer_range: float = 0.02
    dtype: str = "float32"

    # the names `LingSparseMoE` reads
    @property
    def num_experts(self):
        return self.n_routed_experts

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_hidden_layers=3,
                    first_k_dense_replace=1, num_attention_heads=4,
                    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=24,
                    qk_rope_head_dim=8, v_head_dim=32, index_n_heads=4,
                    index_head_dim=16, index_topk=8, q_chunk_size=8,
                    n_routed_experts=16, num_experts_per_tok=4,
                    max_position_embeddings=256)
        base.update(kw)
        return GlmMoeDsaConfig(**base)


class GlmSparseLatentAttention(Layer):
    """Latent attention over the rows the layer's indexer selects."""

    def __init__(self, config: GlmMoeDsaConfig):
        super().__init__()
        c = self.config = config
        init = Normal(0.0, c.initializer_range)
        lin = lambda n_in, n_out: Linear(n_in, n_out, weight_attr=init,
                                         bias_attr=False)
        ones = lambda n: self.create_parameter(
            [n], default_initializer=Constant(1.0))
        nh = c.num_attention_heads
        self.q_a_proj = lin(c.hidden_size, c.q_lora_rank)
        self.q_a_norm = ones(c.q_lora_rank)
        self.q_b_proj = lin(c.q_lora_rank, nh * c.qk_head_dim)
        self.kv_a_proj = lin(c.hidden_size, c.latent_width)
        self.kv_a_norm = ones(c.kv_lora_rank)
        self.kv_b_proj = lin(c.kv_lora_rank,
                             nh * (c.qk_nope_head_dim + c.v_head_dim))
        self.o_proj = lin(nh * c.v_head_dim, c.hidden_size)
        self.index_q_proj = lin(c.q_lora_rank,
                                c.index_n_heads * c.index_head_dim)
        self.index_k_proj = lin(c.hidden_size, c.index_head_dim)
        self.index_w_proj = lin(c.hidden_size, c.index_n_heads)
        self.index_k_norm = ones(c.index_head_dim)
        self.index_k_norm_bias = self.create_parameter(
            [c.index_head_dim], default_initializer=Constant(0.0))

    def _weights(self):
        return [self.q_a_proj.weight, self.q_a_norm, self.q_b_proj.weight,
                self.kv_a_proj.weight, self.kv_a_norm, self.kv_b_proj.weight,
                self.o_proj.weight, self.index_q_proj.weight,
                self.index_k_proj.weight, self.index_w_proj.weight,
                self.index_k_norm, self.index_k_norm_bias]

    def _rotate_index(self, x, ang):
        """The first `qk_rope_head_dim` numbers of index vectors."""
        dr = self.config.qk_rope_head_dim
        return jnp.concatenate([rotate_interleaved(x[..., :dr], ang),
                                x[..., dr:]], axis=-1)

    def _keys(self, x, pos, wqa, gqa, wkva, gkv, wik, wiw, gi, bi):
        """What a token gives whatever queries it: x [B, S, hidden], pos
        [B, S] -> the compressed query c_q [B, S, q rank], the rotation
        angles, the row [c | k_r] (c normed, k_r rotated), the index key
        (normed, rotated) and the index heads' weights (float32, the
        scales folded in)."""
        c = self.config
        r = c.kv_lora_rank
        ang = rope_angles(pos, c.qk_rope_head_dim, c.rope_theta)
        c_q = _rms(jnp.dot(x, wqa), gqa, c.rms_norm_eps)
        ckr = jnp.dot(x, wkva)
        row = jnp.concatenate(
            [_rms(ckr[..., :r], gkv, c.rms_norm_eps),
             rotate_interleaved(ckr[..., r:], ang)], axis=-1)
        ki = self._rotate_index(
            _layer_norm(jnp.dot(x, wik), gi, bi, c.index_norm_eps), ang)
        w = jnp.dot(x, wiw, preferred_element_type=F32) * F32(
            (c.index_n_heads * c.index_head_dim) ** -0.5)
        return c_q, ang, row, ki, w

    def _queries(self, c_q, ang, wqb):
        """c_q [B, S, q rank] -> q_nope [B, S, H, nope], q_rope [B, S,
        H, rope] (rotated)."""
        c = self.config
        q = jnp.dot(c_q, wqb).reshape(c_q.shape[:2] + (
            c.num_attention_heads, c.qk_head_dim))
        return q[..., :c.qk_nope_head_dim], \
            rotate_interleaved(q[..., c.qk_nope_head_dim:], ang)

    def _index_queries(self, c_q, ang, wiq):
        c = self.config
        return self._rotate_index(jnp.dot(c_q, wiq).reshape(
            c_q.shape[:2] + (c.index_n_heads, c.index_head_dim)), ang)

    def _whole(self, x, pos, valid, plan, blocks, wqa, gqa, wqb, wkva, gkv,
               wkvb, wo, wiq, *index):
        """Decompressed: every head's keys and values are formed from
        the latent once, head-major; the queries, the index scores, the
        selection and the masked attention (`chunk_plan` says which of
        them a chunk needs, `chunk_key_blocks` which key blocks it
        visits) and the output projection run a chunk of queries at a
        time."""
        c = self.config
        n, s_real, hidden = x.shape
        ch = min(c.q_chunk_size, s_real)
        tail = -s_real % ch
        if tail:        # whole chunks: the tail's keys are seen by no query
            x, pos, valid = (jnp.pad(a, [(0, 0), (0, tail)]
                                     + [(0, 0)] * (a.ndim - 2))
                             for a in (x, pos, valid))
        s = s_real + tail
        nh, dn, r = c.num_attention_heads, c.qk_nope_head_dim, c.kv_lora_rank
        c_q, ang, row, ki, w = self._keys(x, pos, wqa, gqa, wkva, gkv, *index)
        w_kv = wkvb.reshape(r, nh, dn + c.v_head_dim)
        k = jnp.concatenate(
            [jnp.einsum("nsc,chd->nhsd", row[..., :r], w_kv[..., :dn]),
             jnp.broadcast_to(row[:, None, :, r:],
                              (n, nh, s, c.qk_rope_head_dim))], axis=-1)
        v = jnp.einsum("nsc,chd->nhsd", row[..., :r], w_kv[..., dn:])
        kpos = jnp.arange(s, dtype=jnp.int32)

        def one(at):
            start, kind, tab = at
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, ch, axis=1)
            qpos = start + jnp.arange(ch, dtype=jnp.int32)

            def real(_):
                cq, a = cut(c_q), cut(ang)
                q = jnp.concatenate(self._queries(cq, a, wqb), axis=-1)
                seen = valid[:, None, :] \
                    & (kpos[None, None, :] <= qpos[None, :, None])

                def selected(_):
                    qi = self._index_queries(cq, a, wiq)
                    with jax.named_scope("dsa.indexer"):
                        scores = prefill_index_scores(qi, cut(w), ki, tab)
                    with jax.named_scope("dsa.select"):
                        return select_topk(scores, seen, c.index_topk)

                keep = jax.lax.cond(kind == SELECTED, selected,
                                    lambda _: seen, None)
                with jax.named_scope("mla.attend"):
                    o = selected_attention(q, k, v, keep, tab,
                                           c.qk_head_dim ** -0.5)
                return jnp.dot(o.reshape(n, ch, -1), wo)

            return jax.lax.cond(kind != PADDING, real,
                                lambda _: jnp.zeros((n, ch, hidden), x.dtype),
                                None)

        out = jax.lax.map(one, (jnp.arange(0, s, ch, dtype=jnp.int32), plan,
                                blocks))
        out = jnp.moveaxis(out, 0, 1).reshape(n, s, hidden)
        return out[:, :s_real], row[:, :s_real], ki[:, :s_real]

    def forward(self, x, pos, valid=None, cache=None, plan=None):
        """x [B, S, hidden]; pos [B, S] int32. Without `cache`: the
        whole batch from nothing, `valid` [B, S] its real positions and
        `plan` what each chunk of queries has to do and which key blocks
        it visits (the pair `chunk_plan`, `chunk_key_blocks`);
        returns (out, (rows, index keys)). With a `LatentCacheEntry`
        that carries index pages (S == 1): one decode step in absorbed
        form; returns (out, entry, counts [B] = rows each slot's token
        attended to)."""
        c = self.config
        if cache is None:
            out, row, ki = apply(self._whole, x, pos, valid, *plan,
                                 *self._weights(),
                                 _name="sparse_latent_attention")
            return out, (row, ki)
        if x.shape[1] != 1:
            raise NotImplementedError(
                "a latent-attention layer with an indexer takes one token "
                "a slot a step: a query span (chunked prefill, speculative "
                "verify) would select a set for each of its positions")
        nh, dn, r = c.num_attention_heads, c.qk_nope_head_dim, c.kv_lora_rank

        def absorb(xv, pv, wqa, gqa, wqb, wkva, gkv, wkvb, _wo, wiq, *index):
            c_q, ang, row, ki, w = self._keys(xv, pv, wqa, gqa, wkva, gkv,
                                              *index)
            q_nope, q_rope = self._queries(c_q, ang, wqb)
            with jax.named_scope("mla.absorb"):
                w_k = wkvb.reshape(r, nh, dn + c.v_head_dim)[..., :dn]
                q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w_k,
                                   preferred_element_type=F32)
            return (jnp.concatenate([q_lat.astype(xv.dtype), q_rope],
                                    axis=-1), row,
                    self._index_queries(c_q, ang, wiq), w, ki)

        q, row, qi, w, ki = apply(absorb, x, pos, *self._weights(),
                                  _name="sparse_latent_absorb")
        summed, entry, n_sel = paged_cache_sparse_latent_update_attend(
            cache, q, row, qi, w, ki, c.index_topk, c.qk_head_dim ** -0.5)

        def expand(o_lat, wkvb, wo):
            with jax.named_scope("mla.absorb"):
                w_v = wkvb.reshape(r, nh, dn + c.v_head_dim)[..., dn:]
                o = jnp.einsum("bshc,chd->bshd", o_lat[..., :r], w_v,
                               preferred_element_type=F32)
            return jnp.dot(o.astype(wo.dtype).reshape(o.shape[:2] + (-1,)),
                           wo)

        out = apply(expand, summed, self.kv_b_proj.weight,
                    self.o_proj.weight, _name="sparse_latent_expand")
        return out, entry, n_sel


class GlmDecoderLayer(Layer):
    def __init__(self, config: GlmMoeDsaConfig, index):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.self_attn = GlmSparseLatentAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        self.dense = index < config.first_k_dense_replace
        if self.dense:
            self.mlp = LingMLP(config, config.intermediate_size)
        else:
            self.moe = LingSparseMoE(config)
            self.shared_mlp = LingMLP(
                config,
                config.moe_intermediate_size * config.n_shared_experts)

    def forward(self, h, pos, valid, cache, plan):
        x, *kept = self.self_attn(self.input_layernorm(h), pos, valid, cache,
                                  plan)
        h = h + x
        x = self.post_attention_layernorm(h)
        if self.dense:
            return h + self.mlp(x), kept, None
        routed, counts = self.moe(x, valid)
        return h + routed + self.shared_mlp(x), kept, counts


class GlmMoeDsaModel(Layer):
    def __init__(self, config: GlmMoeDsaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))
        self.layers = LayerList([GlmDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)


class GlmMoeDsaForCausalLM(Layer):
    """`forward(ids, attn_mask, position_ids, past_key_values,
    use_cache) -> logits | (logits, caches)`, the call the serve
    programs make (the module's docstring says what each argument may
    be)."""

    # the serve loop's prefill hands over the keys' validity, not a
    # dense mask, and takes the last position's logits (inference/
    # __init__.py, "the long prefill")
    long_prefill = True
    # one prompt a program: a prompt's decompressed keys and values are
    # 0.54 GB each a layer at 16384 tokens, and a second row would wait
    # for its first token twice as long (the work is compute-bound)
    long_prefill_rows = 1

    def __init__(self, config: GlmMoeDsaConfig):
        super().__init__()
        self.config = config
        self.model = GlmMoeDsaModel(config)
        self.lm_head = Linear(
            config.hidden_size, config.vocab_size, bias_attr=False,
            weight_attr=Normal(0.0, config.initializer_range))

    def cache_layout(self):
        """What each layer keeps between steps (generation/kv_cache.py
        `LayerCache`): one latent row a token and one index key a
        token."""
        c = self.config
        return [LayerCache("latent", (c.latent_width,),
                           c.index_head_dim)] * c.num_hidden_layers

    def step_counters(self):
        """What the vectors in `caches.counters` count, element by
        element: {key: [(metric, labels)]} (docs/OBSERVABILITY.md). A
        decode step gives the first two of "dsa" and a prefill all
        nine, the first two zero, and zero for "mla": those count what
        decode steps touch."""
        c = self.config
        held = range(c.n_routed_experts) if c.experts_held is None \
            else c.experts_held
        return {"dsa": [("dsa.keys_live", {}), ("dsa.keys_selected", {})]
                + [("dsa.prefill_chunks", {"kind": kind})
                   for kind in ("padding", "dense", "selected")]
                + [("dsa.prefill_keys_counted", {}),
                   ("dsa.prefill_keys_bucket", {})]
                + [("dsa.prefill_key_blocks", {"kind": kind})
                   for kind in ("attended", "bucket")],
                "mla": [("mla.keys_live", {})],
                "moe": [("moe.assignments", {}),
                        ("moe.assignments_local", {})]
                + [("moe.expert_tokens", {"expert": str(e)}) for e in held]}

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                past_key_values=None, use_cache=False):
        paged = past_key_values is not None
        if paged and not isinstance(past_key_values, PagedKVCache):
            raise NotImplementedError(
                "GlmMoeDsaForCausalLM continues only from the serve loop's "
                "caches (PagedKVCache of latent entries with index pages)")
        c = self.config
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
        # what a prefill's chunks of queries have to do and which key
        # blocks they visit (every head has keys of its own: `rep` 1),
        # once for all layers
        plan = None if paged else apply(
            lambda ok: (chunk_plan(ok, c.q_chunk_size, c.index_topk),
                        chunk_key_blocks(ok, c.q_chunk_size, 1)), valid,
            _name="prefill_plan")
        caches, moe, dsa = [], None, None
        for i, layer in enumerate(m.layers):
            cache = past_key_values[i] if paged else None
            h, kept, n = layer(h, position_ids, valid, cache, plan)
            if not paged:
                # a layer's temporaries end with the layer: without the
                # barrier the compiler keeps 0.8 GB a layer alive at a
                # 16384-token prompt (tests/test_chip_compile.py)
                h = apply(jax.lax.optimization_barrier, h, _name="layer_end")
            caches.append(kept[0])
            if n is not None:
                moe = n if moe is None else moe + n
            if paged:
                dsa = kept[1] if dsa is None else dsa + kept[1]
        if use_cache and not paged:
            h = h[:, -1:]       # a prefill continues from its last position
        # float32 logits from the parameters' dtype (models/keye_vl2.py)
        logits = apply(lambda x, w: jnp.dot(x, w, preferred_element_type=F32),
                       m.norm(h), self.lm_head.weight, _name="lm_head")
        if not use_cache:
            return logits
        n_layers = len(m.layers)
        if moe is None:     # no expert layer among these
            n_held = c.n_routed_experts if c.experts_held is None \
                else len(c.experts_held)
            moe = apply(lambda ids: jnp.zeros((2 + n_held,), jnp.int32),
                        input_ids, _name="moe_counts")

        def step_counts(n_sel, ctx, *on):
            """[rows the step's tokens could see, rows they attended
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
            mla = apply(lambda d: d[:1], dsa, _name="mla_counts")
        else:       # rows are counted by decode steps: one query a slot
            dsa = apply(lambda p, blocks: jnp.pad(plan_counts(
                p, blocks, c.q_chunk_size, s) * jnp.int32(n_layers), (2, 0)),
                *plan, _name="dsa_counts")
            mla = apply(lambda ids: jnp.zeros((1,), jnp.int32), input_ids,
                        _name="mla_counts")
        return logits, LayerCaches(caches, {"dsa": dsa, "mla": mla,
                                            "moe": moe})

"""openPangu-Ultra-MoE (`model_type: pangu_ultra_moe`): multi-head latent
attention with a compressed query in every layer, four norms a layer
(sandwich norm), dense SwiGLU in the first `first_k_dense_replace`
layers and, after them, sigmoid-routed experts with a shared expert; an
untied head; and ONE multi-token-prediction (MTP) module, which is
built: it is the model's own drafter, and the serve loop runs it every
decode tick (inference/__init__.py, "the self-drafting tick").

Written from the published config keys, the Pangu Ultra MoE report
(arXiv:2505.04519: sandwich norm), DeepSeek-V2 (MLA, arXiv:2405.04434)
and DeepSeek-V3 (routing; the MTP module, section 2.2,
arXiv:2412.19437); what the config has no key for is marked (assumed).
h in R^hidden, no bias, RMSNorm eps `rms_norm_eps`:

- block: `h += N_post_attn(Attn(N_in(h)))`, `h += N_post_ffn(FFN(
  N_pre_ffn(h)))` (assumed: the placement; every gain is 1 at the
  start, the report's depth-scaled gains are a training matter).
- query: `c_q = RMSNorm(W_qa x)` (`q_lora_rank`); `q = W_qb c_q` -> H x
  [nope | rope].
- keys: `[c | k_r] = W_kva x`; `c <- RMSNorm(c)`; `q_rope` and `k_r`
  rotated at the token's position, interleaved pairs (2i, 2i + 1;
  assumed), `rope_theta`, no scaling; `[k_nope,h | v_h] = W_kvb,h c`.
  Kept a token: `[c | k_r]`, ONE row for all heads.
- attention: scores `(q_nope . k_nope + q_rope . k_r) / sqrt(nope +
  rope)`, causal softmax over ALL earlier tokens, `o_h = sum p v_h`,
  `W_o`. A prompt is computed decompressed (k and v formed for every
  head, the flash kernel); a decode step and a verify span absorb
  `W_kvb`: `q^_h = W_kvbK,h^T q_nope,h` against `c`, `o_h = W_kvbV,h
  (sum p c)` (kernels/latent_attention.py).
- experts: `s = sigmoid(W_r y)` float32, the `num_experts_per_tok`
  largest, gates the chosen `s` over their sum times
  `routed_scaling_factor` (assumed: sigmoid scores, no choice bias, no
  groups: `group_limited_sigmoid_route` with b = 0 and one group); plus
  the shared expert, ungated. The layer is told which experts it holds
  (`experts_held`), routes over all of them and adds its own experts'
  part.
- logits: the untied head on the final RMSNorm, float32.
- MTP (depth 1): for position i with the trunk's last-layer output
  `h_i` (assumed: before the final norm) and the NEXT token `x_{i+1}`:
  `u_i = W_eh [RMSNorm_e(Emb(x_{i+1})) ; RMSNorm_h(h_i)]` (assumed:
  this order); `g_i` = one further decoder layer of the expert kind
  (assumed) over `u_0..u_i` at position i (assumed), with latent rows of
  ITS OWN; draft logits `W_head RMSNorm_m(g_i)` predict `x_{i+2}`.
  Embedding and head are the trunk's.

Float32 whatever the weights' dtype: the norms' statistics, the
rotation, the router's scores and gates, every softmax and the logits.

The model declares what a layer keeps between steps (`cache_layout()`):
one latent row a token a layer, the MTP layer's as one more layer of
the pool; and its drafter (`drafter()`: depth 1, the pool's last
layer). It asks for the serve loop's long prefill (`long_prefill`).
Three calls reach it:

- `forward` without `past_key_values`: a whole left-padded batch.
  `attn_mask` is the key-validity mask [B, S] (bool; the serve
  prefill's) or the additive [B, 1, S, S] mask other models take, of
  which only the validity of the keys is read. With `use_cache` the
  logits are those of the LAST position alone, the MTP module runs over
  every position (`x_{i+1}` the prompt's next token, and the argmax of
  those logits at the last), `caches` holds the rows of every layer and
  of the MTP layer, and `caches.draft` the first drafted token.
- `forward` with a `PagedKVCache`: one decode token a slot, or a span
  (S > 1: a token and the tokens drafted after it) through the trunk;
  `caches.hidden` is what the drafter reads, and the MTP layer's entry
  passes through untouched.
- `draft`: the MTP module over a step's positions, from the trunk's
  `hidden` and the tokens that followed.
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
from ..generation.kv_cache import (Drafter, LayerCache, LayerCaches,
                                   PagedKVCache,
                                   paged_cache_latent_span_update_attend,
                                   paged_cache_latent_update_attend)
from ..kernels.attention import flash_attention_jax
from .granite_hybrid import GraniteRMSNorm as RMSNorm
from .keye_vl2 import _rms, rope_angles
from .ling_hybrid import LingMLP, LingSparseMoE, rotate_interleaved

F32 = jnp.float32


@dataclass
class OpenPanguMoEConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432        # the dense layers' width
    moe_intermediate_size: int = 2048     # one routed expert's width
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25.6e6
    n_routed_experts: int = 256           # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    experts_held: Optional[Tuple[int, ...]] = None   # None: all of them
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    dtype: str = "float32"

    # the names `LingSparseMoE` reads: one group, none closed
    n_group = 1
    topk_group = 1

    def __post_init__(self):
        if self.num_nextn_predict_layers != 1:
            raise ValueError(
                f"num_nextn_predict_layers="
                f"{self.num_nextn_predict_layers}: one multi-token-"
                f"prediction module is built (draft depth 1)")

    @property
    def num_experts(self):
        return self.n_routed_experts

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim


class PanguLatentAttention(Layer):
    """Latent attention over all earlier tokens, the query compressed."""

    def __init__(self, config: OpenPanguMoEConfig):
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

    def _weights(self):
        return [self.q_a_proj.weight, self.q_a_norm, self.q_b_proj.weight,
                self.kv_a_proj.weight, self.kv_a_norm, self.kv_b_proj.weight,
                self.o_proj.weight]

    def _angles(self, pos):
        """pos [B, S] -> the rotation's angles [B, S, rope / 2]: plain
        `rope_theta` here; a model with scaled frequencies overrides
        this and `_scale` (models/xing_moe.py)."""
        c = self.config
        return rope_angles(pos, c.qk_rope_head_dim, c.rope_theta)

    def _scale(self):
        """What the scores are multiplied by."""
        return self.config.qk_head_dim ** -0.5

    def _project(self, x, pos, wqa, gqa, wqb, wkva, gkv):
        """x [B, S, hidden], pos [B, S] -> q_nope [B, S, H, nope],
        q_rope [B, S, H, rope] (rotated) and the row [c | k_r] [B, S,
        latent width] (c normed, k_r rotated)."""
        c = self.config
        ang = self._angles(pos)
        c_q = _rms(jnp.dot(x, wqa), gqa, c.rms_norm_eps)
        q = jnp.dot(c_q, wqb).reshape(x.shape[:2] + (
            c.num_attention_heads, c.qk_head_dim))
        ckr = jnp.dot(x, wkva)
        row = jnp.concatenate(
            [_rms(ckr[..., :c.kv_lora_rank], gkv, c.rms_norm_eps),
             rotate_interleaved(ckr[..., c.kv_lora_rank:], ang)], axis=-1)
        return q[..., :c.qk_nope_head_dim], \
            rotate_interleaved(q[..., c.qk_nope_head_dim:], ang), row

    def _whole(self, x, pos, valid, wqa, gqa, wqb, wkva, gkv, wkvb, wo):
        """Decompressed: every head's keys and values are formed from
        the latent, and the flash kernel attends (causal, the padding
        masked as a row of key validity: no [S, S] array)."""
        c = self.config
        b, s, _ = x.shape
        nh, dn, dv = c.num_attention_heads, c.qk_nope_head_dim, c.v_head_dim
        q_nope, q_rope, row = self._project(x, pos, wqa, gqa, wqb, wkva, gkv)
        kv = jnp.dot(row[..., :c.kv_lora_rank], wkvb).reshape(
            b, s, nh, dn + dv)
        k_rope = jnp.broadcast_to(row[:, :, None, c.kv_lora_rank:],
                                  (b, s, nh, c.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :dn], k_rope], axis=-1)
        # the flash kernels take one head size: v on zeros up to q's
        v = jnp.pad(kv[..., dn:], [(0, 0)] * 3 + [(0, c.qk_head_dim - dv)])
        with jax.named_scope("mla.attend"):
            o = flash_attention_jax(q, k, v, causal=True,
                                    scale=self._scale(),
                                    mask=valid[:, None, None, :])[..., :dv]
        return jnp.dot(o.reshape(b, s, nh * dv), wo), row

    def forward(self, x, pos, valid=None, cache=None):
        """x [B, S, hidden]; pos [B, S] int32. Without `cache`: the
        whole batch from nothing, `valid` [B, S] its real positions;
        returns (out, (rows [B, S, latent width],)). With a
        `LatentCacheEntry`: one decode token a slot (S == 1) or a span
        of S tokens at positions `context_lens` .. + S - 1, in absorbed
        form; returns (out, entry)."""
        c = self.config
        if cache is None:
            out, row = apply(self._whole, x, pos, valid, *self._weights(),
                             _name="mla_attention")
            return out, (row,)
        nh, dn, dv = c.num_attention_heads, c.qk_nope_head_dim, c.v_head_dim
        r = c.kv_lora_rank

        def absorb(xv, pv, wqa, gqa, wqb, wkva, gkv, wkvb, _wo):
            q_nope, q_rope, row = self._project(xv, pv, wqa, gqa, wqb, wkva,
                                                gkv)
            with jax.named_scope("mla.absorb"):
                w_k = wkvb.reshape(r, nh, dn + dv)[..., :dn]
                q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w_k,
                                   preferred_element_type=F32)
            return jnp.concatenate([q_lat.astype(xv.dtype), q_rope],
                                   axis=-1), row

        q, row = apply(absorb, x, pos, *self._weights(), _name="mla_absorb")
        attend = paged_cache_latent_update_attend if x.shape[1] == 1 \
            else paged_cache_latent_span_update_attend
        summed, entry = attend(cache, q, row, self._scale())

        def expand(o_lat, wkvb, wo):
            with jax.named_scope("mla.absorb"):
                w_v = wkvb.reshape(r, nh, dn + dv)[..., dn:]
                o = jnp.einsum("bshc,chd->bshd", o_lat[..., :r], w_v,
                               preferred_element_type=F32)
            return jnp.dot(o.astype(wo.dtype).reshape(o.shape[:2] + (-1,)),
                           wo)

        out = apply(expand, summed, self.kv_b_proj.weight,
                    self.o_proj.weight, _name="mla_expand")
        return out, entry


class PanguDecoderLayer(Layer):
    """Sandwich norm: a norm before and a norm after each of the two
    sublayers, the residual taken around both."""

    def __init__(self, config: OpenPanguMoEConfig, dense):
        super().__init__()
        norm = lambda: RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.input_layernorm = norm()
        self.self_attn = PanguLatentAttention(config)
        self.post_attention_layernorm = norm()
        self.pre_mlp_layernorm = norm()
        self.dense = bool(dense)
        if self.dense:
            self.mlp = LingMLP(config, config.intermediate_size)
        else:
            self.moe = LingSparseMoE(config)
            self.shared_mlp = LingMLP(
                config,
                config.moe_intermediate_size * config.n_shared_experts)
        self.post_mlp_layernorm = norm()

    def forward(self, h, pos, valid, cache):
        x, kept = self.self_attn(self.input_layernorm(h), pos, valid, cache)
        h = h + self.post_attention_layernorm(x)
        x = self.pre_mlp_layernorm(h)
        if self.dense:
            return h + self.post_mlp_layernorm(self.mlp(x)), kept, None
        routed, counts = self.moe(x, valid)
        return h + self.post_mlp_layernorm(routed + self.shared_mlp(x)), \
            kept, counts


class PanguMTPModule(Layer):
    """The multi-token-prediction module: the next token's embedding and
    the trunk's output, each normed, joined and projected back to the
    hidden size, through one decoder layer of the expert kind and a norm
    of its own. The head (and the embedding) are the trunk's."""

    def __init__(self, config: OpenPanguMoEConfig):
        super().__init__()
        norm = lambda: RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.enorm = norm()
        self.hnorm = norm()
        self.eh_proj = Linear(
            2 * config.hidden_size, config.hidden_size, bias_attr=False,
            weight_attr=Normal(0.0, config.initializer_range))
        self.layer = PanguDecoderLayer(config, dense=False)
        self.norm = norm()

    def forward(self, hidden, emb_next, pos, valid, cache):
        """hidden, emb_next [B, S, hidden] -> (normed output [B, S,
        hidden], what the layer keeps, routing counts)."""
        e, h = self.enorm(emb_next), self.hnorm(hidden)
        u = apply(lambda ev, hv, w: jnp.dot(
            jnp.concatenate([ev, hv], axis=-1), w), e, h,
            self.eh_proj.weight, _name="mtp_join")
        g, kept, counts = self.layer(u, pos, valid, cache)
        return self.norm(g), kept, counts


class OpenPanguMoEModel(Layer):
    def __init__(self, config: OpenPanguMoEConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))
        self.layers = LayerList([
            PanguDecoderLayer(config, i < config.first_k_dense_replace)
            for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mtp = PanguMTPModule(config)


class OpenPanguMoEForCausalLM(Layer):
    """`forward(ids, attn_mask, position_ids, past_key_values,
    use_cache) -> logits | (logits, caches)`, the call the serve
    programs make, and `draft`, the call of the self-drafting tick (the
    module's docstring says what each argument may be)."""

    # the serve loop's prefill hands over the keys' validity, not a
    # dense mask, and takes the last position's logits (inference/
    # __init__.py, "the long prefill")
    long_prefill = True
    # one prompt a program: a round seldom holds two prompts of one
    # bucket at a chat rate, and each row count is one more program a
    # bucket to compile before serving
    long_prefill_rows = 1

    def __init__(self, config: OpenPanguMoEConfig):
        super().__init__()
        self.config = config
        self.model = OpenPanguMoEModel(config)
        self.lm_head = Linear(
            config.hidden_size, config.vocab_size, bias_attr=False,
            weight_attr=Normal(0.0, config.initializer_range))

    def cache_layout(self):
        """What each layer keeps between steps (generation/kv_cache.py
        `LayerCache`): one latent row a token a layer, the MTP layer's
        after the trunk's."""
        c = self.config
        return [LayerCache("latent", (c.latent_width,))] \
            * (c.num_hidden_layers + 1)

    def drafter(self):
        """The MTP module drafts one token a tick; its layer's rows are
        the pool's last layer (generation/kv_cache.py `Drafter`)."""
        return Drafter(depth=1, layer=self.config.num_hidden_layers)

    def step_counters(self):
        """What the vectors in `caches.counters` count, element by
        element: {key: [(metric, labels)]} (docs/OBSERVABILITY.md).
        "mla": the rows a step's tokens could see, the trunk's layers
        and the MTP layer's; a prefill gives zero. "mtp": what the
        self-drafting tick's verify decided, on the device; a prefill
        and a plain decode step give zeros."""
        c = self.config
        held = range(c.n_routed_experts) if c.experts_held is None \
            else c.experts_held
        return {"mla": [("mla.keys_live", {})],
                "moe": [("moe.assignments", {}),
                        ("moe.assignments_local", {})]
                + [("moe.expert_tokens", {"expert": str(e)}) for e in held],
                "mtp": [("mtp.drafts_proposed", {}),
                        ("mtp.drafts_accepted", {}),
                        ("mtp.tokens_committed", {})]}

    def _logits(self, h):
        # float32 logits from the parameters' dtype (models/keye_vl2.py)
        return apply(lambda x, w: jnp.dot(x, w, preferred_element_type=F32),
                     h, self.lm_head.weight, _name="lm_head")

    def _keys_live(self, entry, span, valid, layers):
        """[rows the step's `span` tokens a slot could see], over
        `layers` layers and the positions of `valid` [B, span]."""
        def live(ctx, ok):
            seen = ctx.astype(jnp.int32)[:, None] + 1 \
                + jnp.arange(span, dtype=jnp.int32)[None, :]
            return jnp.sum(jnp.where(ok, seen, 0),
                           dtype=jnp.int32)[None] * jnp.int32(layers)
        return apply(live, entry.context_lens, valid, _name="mla_counts")

    def draft(self, hidden, next_ids, position_ids, entry, valid):
        """The MTP module over a step's S positions a slot: `hidden` [B,
        S, hidden] the trunk's output there (`caches.hidden`),
        `next_ids` [B, S] the token that FOLLOWED each, `entry` the MTP
        layer's `LatentCacheEntry`, `valid` [B, S] bool the positions
        that count. Returns (draft logits [B, S, vocab] float32: row i
        drafts the token after `next_ids[:, i]`; the updated entry;
        {"mla", "moe"} counts to add to the step's)."""
        with jax.named_scope("mtp.draft"):
            g, new, moe = self.model.mtp(
                hidden, self.model.embed_tokens(next_ids), position_ids,
                valid, entry)
            return self._logits(g), new, {
                "mla": self._keys_live(entry, next_ids.shape[1], valid, 1),
                "moe": moe}

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                past_key_values=None, use_cache=False):
        paged = past_key_values is not None
        if paged and not isinstance(past_key_values, PagedKVCache):
            raise NotImplementedError(
                "OpenPanguMoEForCausalLM continues only from the serve "
                "loop's caches (PagedKVCache of latent entries)")
        c = self.config
        m = self.model
        n_layers = len(m.layers)
        h = m.embed_tokens(input_ids)
        b, s = input_ids.shape
        if position_ids is None:
            position_ids = apply(lambda ids: jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), (b, s)), input_ids,
                _name="positions")
        if paged:
            # every position of an occupied slot's span is a token
            valid = apply(lambda ids: jnp.ones(ids.shape, jnp.bool_),
                          input_ids, _name="valid_positions") \
                if past_key_values.active is None else apply(
                    lambda a: jnp.broadcast_to(a[:, None], (b, s)),
                    past_key_values.active, _name="active")
        elif attn_mask is None:
            valid = apply(lambda ids: jnp.ones(ids.shape, jnp.bool_),
                          input_ids, _name="valid_positions")
        else:
            # additive [B, 1, S, S]: a key is real where the last query
            # may see it
            valid = apply(lambda mk: mk if mk.ndim == 2
                          else mk[:, 0, -1, :] > -1.0, attn_mask,
                          _name="valid_positions")
        caches, moe = [], None
        for i, layer in enumerate(m.layers):
            h, kept, n = layer(h, position_ids, valid,
                               past_key_values[i] if paged else None)
            caches.append(kept)
            if n is not None:
                moe = n if moe is None else moe + n
        if not use_cache:
            return self._logits(m.norm(h))
        if moe is None:     # no expert layer among the trunk's
            n_held = c.n_routed_experts if c.experts_held is None \
                else len(c.experts_held)
            moe = apply(lambda ids: jnp.zeros((2 + n_held,), jnp.int32),
                        input_ids, _name="moe_counts")
        mtp = apply(lambda ids: jnp.zeros((3,), jnp.int32), input_ids,
                    _name="mtp_counts")
        if paged:
            # the MTP layer's entry passes through: `draft` advances it
            caches.append(past_key_values[n_layers])
            return self._logits(m.norm(h)), LayerCaches(
                caches, {"mla": self._keys_live(past_key_values[0], s, valid,
                                                n_layers),
                         "moe": moe, "mtp": mtp}, hidden=h)
        # a prefill continues from its last position: its logits give
        # the first token, and the MTP module, over every position with
        # the token that followed it, the first draft
        logits = self._logits(m.norm(h[:, -1:]))
        nxt = apply(lambda ids, lg: jnp.concatenate(
            [ids[:, 1:], jnp.argmax(lg[:, -1], axis=-1).astype(
                ids.dtype)[:, None]], axis=1), input_ids, logits,
            _name="next_tokens")
        with jax.named_scope("mtp.draft"):
            g, kept, n = m.mtp(h, m.embed_tokens(nxt), position_ids, valid,
                               None)
            draft = apply(lambda lg: jnp.argmax(lg[:, -1], axis=-1).astype(
                jnp.int32), self._logits(g[:, -1:]), _name="first_draft")
        caches.append(kept)
        return logits, LayerCaches(
            caches, {"mla": apply(lambda ids: jnp.zeros((1,), jnp.int32),
                                  input_ids, _name="mla_counts"),
                     "moe": moe + n, "mtp": mtp}, draft=draft)

"""Ling-3.0-flash (`model_type: bailing_hybrid`), the language model: a
decoder whose mixers are Kimi Delta Attention (a delta-rule linear
attention with a state matrix a head) with a multi-head latent attention
layer closing every group of `layer_group_size`, dense SwiGLU in the
first `first_k_dense_replace` layers and, after them, a routed expert
layer with sigmoid, bias-corrected, group-limited routing and one shared
expert. The multi-token-prediction layer is not built.

Written from the published config keys, the KDA paper (arXiv:2510.26692),
DeepSeek-V2 (MLA, arXiv:2405.04434) and DeepSeek-V3 (routing,
arXiv:2412.19437); what the config has no key for is marked (assumed).
h in R^hidden, no bias anywhere, RMSNorm eps `rms_norm_eps`, pre-norm
block `h += mixer(norm(h))`, `h += ffn(norm(h))`:

- layer i is MLA when `(i + 1) % layer_group_size == 0`, else KDA
  (assumed: the Bailing linear family's rule); its FFN is dense for
  `i < first_k_dense_replace`, else the expert layer.
- KDA (H heads, d_k = d_v = head_dim): `q, k, v = W_q x, W_k x, W_v x`;
  a causal depthwise convolution of `short_conv_kernel_size` taps on
  each, then SiLU; a head `q <- q / |q| / sqrt(d_k)`, `k <- k / |k|`
  (`use_qk_norm`, assumed to be this L2 norm); `beta = sigmoid(W_b x)`
  a head; log decay a head AND channel `g = kda_lower_bound *
  sigmoid(exp(A_log_h) (W_f x + dt_bias))` (the safe gate, assumed form:
  g in (lower bound, 0)); state `S` [d_k, d_v] float32 a head:
  `S' = diag(exp(g)) S`, `S = S' + beta k (v - S'^T k)^T`, `o = S^T q`
  (kernels/kda.py); out `W_o (RMSNorm_head(o) * sigmoid(W_g x)_head)`,
  the gate one number a head (`head_wise`). Kept a slot: the last
  taps - 1 inputs of the three convolutions and `S`.
- MLA (`q_lora_rank` null): `q = W_q x` -> H x [nope | rope]; `[c | k_r]
  = W_a x`; `c <- RMSNorm(c)`; `q_rope` and `k_r` rotated at the token's
  position, interleaved pairs (2i, 2i + 1), `rope_theta` (`k_r` shared
  by the heads); `[k_nope,h | v_h] = W_b,h c`; scores `(q_nope . k_nope
  + q_rope . k_r) / sqrt(nope + rope)`, causal softmax, `o_h = sum p
  v_h`; out `W_o (o * sigmoid(W_g x)_head)`. Kept a token: `[c | k_r]`,
  ONE row for all heads. A prompt is computed decompressed (k and v
  formed for every head, the flash kernel); a decode step absorbs `W_b`:
  `q^_h = W_bK,h^T q_nope,h` against `c`, `o_h = W_bV,h (sum p c)`
  (kernels/latent_attention.py).
- experts: `s = sigmoid(W_r y)` float32; choice by `s + b`, 8 groups, a
  group's score the sum of its two largest, the `topk_group` best groups
  stay, the `num_experts_per_tok` largest among them; gates the chosen
  `s` over their sum, times `routed_scaling_factor`
  (`group_limited_sigmoid_route`); plus the shared expert, ungated.
- logits: the untied head on the final RMSNorm, float32.

Float32 whatever the weights' dtype: the norms' statistics, the
rotation, the router's scores and gates, the KDA state, its decays,
`beta` and the chunks' triangular system, every softmax, and the logits.

The model declares what a layer keeps between steps (`cache_layout()`):
a state row a slot for a KDA layer, latent pages for an MLA layer, and
no K/V anywhere. It asks for the serve loop's long prefill
(`long_prefill`). Two calls reach `forward`:

- no `past_key_values`: a whole left-padded batch. `attn_mask` is the
  key-validity mask [B, S] (bool; the serve prefill's) or the additive
  [B, 1, S, S] mask other models take, of which only the validity of the
  keys is read. Pad positions are zeroed before the KDA projections and
  take no decay and no update, so they leave state and real positions
  untouched. With `use_cache` the logits are those of the LAST position
  alone and `caches` holds (conv window, state) or (latent rows,) a
  layer.
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
                                   StateCacheEntry,
                                   paged_cache_latent_update_attend)
from ..incubate.distributed.models.moe.dropless import (
    DroplessMoELayer, dropless_moe, group_limited_sigmoid_route)
from ..kernels.attention import flash_attention_jax
from ..kernels.kda import kda_chunked, kda_gate, kda_step
from .granite_hybrid import GraniteRMSNorm as RMSNorm
from .keye_vl2 import _rms, rope_angles

F32 = jnp.float32
L2_EPS = 1e-6       # (assumed) under the root of the q / k L2 norm


@dataclass
class LingHybridConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144         # the dense layers' width
    moe_intermediate_size: int = 768      # one routed expert's width
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    layer_group_size: int = 6
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    head_dim: int = 128                   # KDA's d_k = d_v
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk_size: int = 64
    kda_sub_chunk_size: int = 16
    kda_segment_size: int = 1024          # a prompt's tokens a scan step
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    num_experts: int = 512                # the router's width
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    experts_held: Optional[Tuple[int, ...]] = None   # None: all of them
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_experts % self.n_group:
            raise ValueError(f"{self.num_experts} experts do not lie in "
                             f"{self.n_group} equal groups")
        if self.kda_chunk_size % self.kda_sub_chunk_size \
                or self.kda_segment_size % self.kda_chunk_size:
            raise ValueError("a KDA segment must be whole chunks, and a "
                             "chunk whole sub-chunks")

    @property
    def layer_kinds(self):
        return tuple("mla" if (i + 1) % self.layer_group_size == 0 else "kda"
                     for i in range(self.num_hidden_layers))

    @property
    def kda_width(self):
        return self.num_attention_heads * self.head_dim

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32,
                    moe_shared_expert_intermediate_size=32,
                    num_hidden_layers=4, layer_group_size=3,
                    first_k_dense_replace=1, num_attention_heads=4,
                    head_dim=16, kda_chunk_size=8, kda_sub_chunk_size=4,
                    kda_segment_size=16,
                    kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, num_experts=16,
                    num_experts_per_tok=4, n_group=4, topk_group=2,
                    max_position_embeddings=256)
        base.update(kw)
        return LingHybridConfig(**base)


def rotate_interleaved(x, angles):
    """x [..., D] by angles [..., D / 2], pairs (2i, 2i + 1), in
    float32 (x [B, S, heads, D] takes angles [B, S, D / 2])."""
    if x.ndim == angles.ndim + 1:
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    v = x.astype(F32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    x1, x2 = v[..., 0], v[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _l2(x):
    v = x.astype(F32)
    return v * jax.lax.rsqrt(jnp.sum(v * v, axis=-1, keepdims=True)
                             + F32(L2_EPS))


class LingKDAMixer(Layer):
    def __init__(self, config: LingHybridConfig):
        super().__init__()
        c = self.config = config
        init = Normal(0.0, c.initializer_range)
        h, w, nh = c.hidden_size, c.kda_width, c.num_attention_heads
        # [q | k | v | decay] and [beta | output gate] of the normed input
        self.in_proj = Linear(h, 4 * w, weight_attr=init, bias_attr=False)
        self.head_proj = Linear(h, 2 * nh, weight_attr=init, bias_attr=False)
        # [tap, channel] over q, k, v: tap j multiplies the input
        # taps - 1 - j back
        self.conv_weight = self.create_parameter(
            [c.short_conv_kernel_size, 3 * w], default_initializer=init)
        self.A_log = self.create_parameter(
            [nh], default_initializer=Constant(0.0))
        self.dt_bias = self.create_parameter(
            [nh, c.head_dim], default_initializer=Constant(0.0))
        self.norm_weight = self.create_parameter(
            [c.head_dim], default_initializer=Constant(1.0))
        self.out_proj = Linear(w, h, weight_attr=init, bias_attr=False)

    def forward(self, u, valid=None, cache=None):
        """u [b, s, hidden]. With `cache` (a StateCacheEntry, s == 1)
        one step of the recurrence over the pool's rows; without, the
        chunked form from a zero state, `valid` [b, s] marking the real
        positions of a left-padded batch. Returns (out, the updated
        entry | (conv window [b, taps - 1, channels], state))."""
        args = [u, self.in_proj.weight, self.head_proj.weight,
                self.conv_weight, self.A_log, self.dt_bias,
                self.norm_weight, self.out_proj.weight]
        if cache is not None:
            if u.shape[1] != 1:
                raise NotImplementedError(
                    "a recurrent layer takes one token a slot a step: "
                    "spans (chunked prefill, speculative verify) need "
                    "state snapshots")
            extra = () if valid is None else (valid,)
            out, conv, state = apply(self._step, *args, cache.conv,
                                     cache.ssm, *extra, _name="kda_step")
            return out, StateCacheEntry(conv, state)
        extra = () if valid is None else (valid,)
        out, conv, state = apply(self._scan, *args, *extra, _name="kda_scan")
        return out, (conv, state)

    def _project(self, u, w_in, w_head):
        c = self.config
        w, nh = c.kda_width, c.num_attention_heads
        wide = jnp.dot(u, w_in)
        small = jnp.dot(u, w_head, preferred_element_type=F32)
        return (wide[..., :3 * w], wide[..., 3 * w:],
                jax.nn.sigmoid(small[..., :nh]),
                jax.nn.sigmoid(small[..., nh:]))

    def _heads(self, qkv, a, a_log, dt_bias):
        """The convolved q | k | v and the decay projection -> q, k
        (normed), v by heads and the log decay g."""
        c = self.config
        nh, d = c.num_attention_heads, c.head_dim
        by_head = lambda x: x.reshape(x.shape[:-1] + (nh, d))
        q, k, v = (by_head(x) for x in jnp.split(qkv, 3, axis=-1))
        q = (_l2(q) * F32(d ** -0.5)).astype(qkv.dtype)
        k = _l2(k).astype(qkv.dtype)
        return q, k, v, kda_gate(by_head(a), a_log, dt_bias,
                                 c.kda_lower_bound)

    def _finish(self, o, gate, w_norm, w_out):
        c = self.config
        o = _rms(o, w_norm, c.rms_norm_eps).astype(F32) * gate[..., None]
        return jnp.dot(o.astype(w_out.dtype).reshape(
            o.shape[:-2] + (c.kda_width,)), w_out)

    def _scan(self, u, w_in, w_head, w_conv, a_log, dt_bias, w_norm, w_out,
              valid=None):
        """The prompt a segment of `kda_segment_size` tokens at a time
        (`lax.scan` carrying the conv window and the state): nothing of
        the mixer outlives a segment, so its temporaries do not grow
        with the prompt. Padding goes in front, where the serve loop
        puts it."""
        c = self.config
        b, s, hidden = u.shape
        taps, seg = c.short_conv_kernel_size, min(c.kda_segment_size, s)
        if valid is None:
            valid = jnp.ones((b, s), jnp.bool_)
        lead = -s % seg
        u = jnp.pad(jnp.where(valid[..., None], u, 0),
                    [(0, 0), (lead, 0), (0, 0)])
        valid = jnp.pad(valid, [(0, 0), (lead, 0)])

        def segment(carry, xs):
            window, state = carry
            u_s, ok = xs
            qkv, a, beta, gate = self._project(u_s, w_in, w_head)
            with jax.named_scope("kda.conv"):
                window = jnp.concatenate([window, qkv], axis=1)
                conv = sum(w_conv[j].astype(F32)[None, None, :]
                           * window[:, j:j + seg].astype(F32)
                           for j in range(taps))
                qkv_c = jax.nn.silu(conv).astype(u.dtype)
            q, k, v, g = self._heads(qkv_c, a, a_log, dt_bias)
            # padding: no decay and no update
            g = jnp.where(ok[..., None, None], g, 0)
            beta = jnp.where(ok[..., None], beta, 0)
            o, state = kda_chunked(q, k, v, g, beta, state,
                                   chunk=c.kda_chunk_size,
                                   sub=c.kda_sub_chunk_size)
            return (window[:, seg:], state), \
                self._finish(o, gate, w_norm, w_out)

        by_seg = lambda x: jnp.moveaxis(
            x.reshape((b, -1, seg) + x.shape[2:]), 1, 0)
        nh, d = c.num_attention_heads, c.head_dim
        start = (jnp.zeros((b, taps - 1, 3 * c.kda_width), u.dtype),
                 jnp.zeros((b, nh, d, d), F32))
        (window, state), out = jax.lax.scan(segment, start,
                                            (by_seg(u), by_seg(valid)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, -1, hidden)[:, lead:]
        return out, window, state

    def _step(self, u, w_in, w_head, w_conv, a_log, dt_bias, w_norm, w_out,
              conv_state, state, active=None):
        """The pool has one row more than the batch, and the step's
        tensors are padded to it, so the update runs over the donated
        arrays as they lie. `active` [b, 1] says which slots carry a
        request: the state kernel visits those rows alone (without it,
        or on the XLA path, every row advances; an empty slot's row
        holds don't-care values either way)."""
        b = u.shape[0]
        rows = state.shape[0]
        qkv, a, beta, gate = self._project(u[:, 0], w_in, w_head)
        pad = lambda x: jnp.pad(x, [(0, rows - b)] + [(0, 0)] * (x.ndim - 1))
        with jax.named_scope("kda.conv"):
            window = jnp.concatenate(
                [conv_state, pad(qkv)[:, None].astype(conv_state.dtype)],
                axis=1)
            conv = jnp.sum(w_conv.astype(F32)[None] * window.astype(F32),
                           axis=1)
            qkv_c = jax.nn.silu(conv).astype(u.dtype)
        q, k, v, g = self._heads(qkv_c, pad(a), a_log, dt_bias)
        state, o = kda_step(state, q, k, v, g, pad(beta),
                            None if active is None else pad(active[:, 0]))
        out = self._finish(o[:b], gate, w_norm, w_out)
        return out[:, None], window[:, 1:], state


class LingMLAMixer(Layer):
    def __init__(self, config: LingHybridConfig):
        super().__init__()
        c = self.config = config
        init = Normal(0.0, c.initializer_range)
        lin = lambda n_in, n_out: Linear(n_in, n_out, weight_attr=init,
                                         bias_attr=False)
        nh = c.num_attention_heads
        self.qk_dim = c.qk_nope_head_dim + c.qk_rope_head_dim
        self.q_proj = lin(c.hidden_size, nh * self.qk_dim)
        self.kv_a_proj = lin(c.hidden_size, c.latent_width)
        self.kv_a_norm = self.create_parameter(
            [c.kv_lora_rank], default_initializer=Constant(1.0))
        self.kv_b_proj = lin(c.kv_lora_rank,
                             nh * (c.qk_nope_head_dim + c.v_head_dim))
        self.g_proj = lin(c.hidden_size, nh)
        self.o_proj = lin(nh * c.v_head_dim, c.hidden_size)

    def _weights(self):
        return [self.q_proj.weight, self.kv_a_proj.weight, self.kv_a_norm,
                self.kv_b_proj.weight, self.g_proj.weight,
                self.o_proj.weight]

    def _project(self, x, pos, wq, wa, ga, wg):
        """x [B, S, hidden], pos [B, S] -> q_nope [B, S, H, nope],
        q_rope [B, S, H, rope] (rotated), the row [c | k_r] [B, S,
        latent width] (c normed, k_r rotated) and the heads' gates."""
        c = self.config
        b, s, _ = x.shape
        ang = rope_angles(pos, c.qk_rope_head_dim, c.rope_theta)
        q = jnp.dot(x, wq).reshape(b, s, c.num_attention_heads, self.qk_dim)
        q_nope = q[..., :c.qk_nope_head_dim]
        q_rope = rotate_interleaved(q[..., c.qk_nope_head_dim:], ang)
        ckr = jnp.dot(x, wa)
        row = jnp.concatenate(
            [_rms(ckr[..., :c.kv_lora_rank], ga, c.rms_norm_eps),
             rotate_interleaved(ckr[..., c.kv_lora_rank:], ang)], axis=-1)
        gate = jax.nn.sigmoid(jnp.dot(x, wg, preferred_element_type=F32))
        return q_nope, q_rope, row, gate

    def _finish(self, o, gate, wo):
        o = (o.astype(F32) * gate[..., None]).astype(wo.dtype)
        return jnp.dot(o.reshape(o.shape[:-2] + (-1,)), wo)

    def _whole(self, x, pos, valid, wq, wa, ga, wb, wg, wo):
        """Decompressed: every head's keys and values are formed from
        the latent, and the flash kernel attends (causal, the padding
        masked as a row of key validity: no [S, S] array)."""
        c = self.config
        b, s, _ = x.shape
        nh, dn, dv = c.num_attention_heads, c.qk_nope_head_dim, c.v_head_dim
        q_nope, q_rope, row, gate = self._project(x, pos, wq, wa, ga, wg)
        kv = jnp.dot(row[..., :c.kv_lora_rank], wb).reshape(b, s, nh, dn + dv)
        k_rope = jnp.broadcast_to(row[:, :, None, c.kv_lora_rank:],
                                  (b, s, nh, c.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :dn], k_rope], axis=-1)
        # the flash kernels take one head size: v on zeros up to q's
        v = jnp.pad(kv[..., dn:], [(0, 0)] * 3 + [(0, self.qk_dim - dv)])
        with jax.named_scope("mla.attend"):
            o = flash_attention_jax(q, k, v, causal=True,
                                    scale=self.qk_dim ** -0.5,
                                    mask=valid[:, None, None, :])[..., :dv]
        return self._finish(o, gate, wo), row

    def forward(self, x, pos, valid=None, cache=None):
        """x [B, S, hidden]; pos [B, S] int32. Without `cache`: the
        whole batch from nothing, `valid` [B, S] its real positions;
        returns (out, (rows [B, S, latent width],)). With a
        `LatentCacheEntry` (S == 1): one decode step in absorbed form;
        returns (out, entry)."""
        c = self.config
        if cache is None:
            out, row = apply(self._whole, x, pos, valid, *self._weights(),
                             _name="mla_attention")
            return out, (row,)
        if x.shape[1] != 1:
            raise NotImplementedError(
                "a latent-attention layer takes one token a slot a step: "
                "the span programs (chunked prefill, speculative verify) "
                "carry K and V arrays")
        nh, dn, dv = c.num_attention_heads, c.qk_nope_head_dim, c.v_head_dim
        r = c.kv_lora_rank

        def absorb(xv, pv, wq, wa, ga, wb, wg, _wo):
            q_nope, q_rope, row, gate = self._project(xv, pv, wq, wa, ga, wg)
            with jax.named_scope("mla.absorb"):
                w_k = wb.reshape(r, nh, dn + dv)[..., :dn]
                q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w_k,
                                   preferred_element_type=F32)
            return (jnp.concatenate([q_lat.astype(xv.dtype), q_rope],
                                    axis=-1), row, gate)

        q, row, gate = apply(absorb, x, pos, *self._weights(),
                             _name="mla_absorb")
        summed, entry = paged_cache_latent_update_attend(
            cache, q, row, self.qk_dim ** -0.5)

        def expand(o_lat, gv, wb, wo):
            with jax.named_scope("mla.absorb"):
                w_v = wb.reshape(r, nh, dn + dv)[..., dn:]
                o = jnp.einsum("bshc,chd->bshd", o_lat[..., :r], w_v,
                               preferred_element_type=F32)
            return self._finish(o, gv, wo)

        out = apply(expand, summed, gate, self.kv_b_proj.weight,
                    self.o_proj.weight, _name="mla_expand")
        return out, entry


class LingMLP(Layer):
    """SwiGLU at `width`: the dense layers' FFN and the shared expert."""

    def __init__(self, config: LingHybridConfig, width):
        super().__init__()
        init = Normal(0.0, config.initializer_range)
        self.width = width
        self.in_proj = Linear(config.hidden_size, 2 * width,
                              weight_attr=init, bias_attr=False)
        self.out_proj = Linear(width, config.hidden_size,
                               weight_attr=init, bias_attr=False)

    def forward(self, x):
        def fn(v, wi, wo):
            up = jnp.dot(v, wi)
            act = jax.nn.silu(up[..., :self.width].astype(F32)) \
                * up[..., self.width:].astype(F32)
            return jnp.dot(act.astype(v.dtype), wo)
        return apply(fn, x, self.in_proj.weight, self.out_proj.weight,
                     _name="swiglu")


class LingSparseMoE(DroplessMoELayer):
    """The routed experts this chip holds, under the group-limited
    sigmoid rule: `DroplessMoELayer`'s router and banks, and the
    router's float32 choice bias. `forward(x, valid)` returns the held
    experts' part of the layer's result and the routing counts."""

    def __init__(self, config: LingHybridConfig):
        c = self.config = config
        super().__init__(c.hidden_size, c.moe_intermediate_size,
                         c.num_experts, c.num_experts_per_tok,
                         held=c.experts_held,
                         initializer_range=c.initializer_range)
        self.expert_bias = self.create_parameter(
            [c.num_experts], default_initializer=Constant(0.0))

    def forward(self, x, valid=None):
        c = self.config

        def fn(xv, rw, bias, wi, wo, *ok):
            route = lambda logits: group_limited_sigmoid_route(
                logits, bias, c.num_experts_per_tok, c.n_group,
                c.topk_group, c.routed_scaling_factor, c.norm_topk_prob)
            return dropless_moe(xv, ok[0] if ok else None, rw, wi, wo,
                                held=self.held, top_k=c.num_experts_per_tok,
                                route=route)
        extra = () if valid is None else (valid,)
        return apply(fn, x, self.router, self.expert_bias, self.w_in,
                     self.w_out, *extra, _name="dropless_moe")


class LingDecoderLayer(Layer):
    def __init__(self, config: LingHybridConfig, index):
        super().__init__()
        self.kind = config.layer_kinds[index]
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        if self.kind == "kda":
            self.kda = LingKDAMixer(config)
        else:
            self.self_attn = LingMLAMixer(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        self.dense = index < config.first_k_dense_replace
        if self.dense:
            self.mlp = LingMLP(config, config.intermediate_size)
        else:
            self.moe = LingSparseMoE(config)
            self.shared_mlp = LingMLP(
                config, config.moe_shared_expert_intermediate_size)

    def forward(self, h, pos, valid, cache):
        x = self.input_layernorm(h)
        if self.kind == "kda":
            x, kept = self.kda(x, valid, cache)
        else:
            x, kept = self.self_attn(x, pos, valid, cache)
        h = h + x
        x = self.post_attention_layernorm(h)
        if self.dense:
            return h + self.mlp(x), kept, None
        routed, counts = self.moe(x, valid)
        return h + routed + self.shared_mlp(x), kept, counts


class LingHybridModel(Layer):
    def __init__(self, config: LingHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))
        self.layers = LayerList([LingDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)


class LingHybridForCausalLM(Layer):
    """`forward(ids, attn_mask, position_ids, past_key_values,
    use_cache) -> logits | (logits, caches)`, the call the serve
    programs make (the module's docstring says what each argument may
    be)."""

    # the serve loop's prefill hands over the keys' validity, not a
    # dense mask, and takes the last position's logits (inference/
    # __init__.py, "the long prefill")
    long_prefill = True

    def __init__(self, config: LingHybridConfig):
        super().__init__()
        self.config = config
        self.model = LingHybridModel(config)
        self.lm_head = Linear(
            config.hidden_size, config.vocab_size, bias_attr=False,
            weight_attr=Normal(0.0, config.initializer_range))

    def cache_layout(self):
        """What each layer keeps between steps (generation/kv_cache.py
        `LayerCache`), in layer order: a state row a slot for a KDA
        layer, one latent row a token for an MLA layer."""
        c = self.config
        state = LayerCache("state", (
            (c.short_conv_kernel_size - 1, 3 * c.kda_width),
            (c.num_attention_heads, c.head_dim, c.head_dim)))
        latent = LayerCache("latent", (c.latent_width,))
        return [state if kind == "kda" else latent for kind in c.layer_kinds]

    def step_counters(self):
        """What the vectors in `caches.counters` count, element by
        element: {key: [(metric, labels)]} (docs/OBSERVABILITY.md). A
        prefill gives zeros for "kda" and "mla": they count what decode
        steps touch."""
        c = self.config
        held = range(c.num_experts) if c.experts_held is None \
            else c.experts_held
        return {"kda": [("kda.rows_live", {})],
                "mla": [("mla.keys_live", {})],
                "moe": [("moe.assignments", {}),
                        ("moe.assignments_local", {})]
                + [("moe.expert_tokens", {"expert": str(e)}) for e in held]}

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                past_key_values=None, use_cache=False):
        paged = past_key_values is not None
        if paged and not isinstance(past_key_values, PagedKVCache):
            raise NotImplementedError(
                "LingHybridForCausalLM continues only from the serve "
                "loop's caches (PagedKVCache of state and latent entries)")
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
        caches, moe = [], None
        for i, layer in enumerate(m.layers):
            cache = past_key_values[i] if paged else None
            h, kept, n = layer(h, position_ids, valid, cache)
            caches.append(kept)
            if n is not None:
                moe = n if moe is None else moe + n
        if use_cache and not paged:
            h = h[:, -1:]       # a prefill continues from its last position
        # float32 logits from the parameters' dtype (models/keye_vl2.py)
        logits = apply(lambda x, w: jnp.dot(x, w, preferred_element_type=F32),
                       m.norm(h), self.lm_head.weight, _name="lm_head")
        if not use_cache:
            return logits
        kinds = c.layer_kinds
        n_kda, n_mla = kinds.count("kda"), kinds.count("mla")
        if moe is None:     # no expert layer among these
            n_held = c.num_experts if c.experts_held is None \
                else len(c.experts_held)
            moe = apply(lambda ids: jnp.zeros((2 + n_held,), jnp.int32),
                        input_ids, _name="moe_counts")

        def live(ctx, *on):
            """[state rows advanced for a request], [latent rows its
            tokens could see], over the layers of each kind."""
            rows = jnp.ones(ctx.shape, jnp.int32)
            keys = ctx.astype(jnp.int32) + 1
            if on:
                rows = jnp.where(on[0][:, 0], rows, 0)
                keys = jnp.where(on[0][:, 0], keys, 0)
            return (jnp.sum(rows, dtype=jnp.int32)[None] * jnp.int32(n_kda),
                    jnp.sum(keys, dtype=jnp.int32)[None] * jnp.int32(n_mla))

        if paged:
            ctx = next(e.context_lens for e in past_key_values
                       if not isinstance(e, StateCacheEntry))
            kda, mla = apply(live, ctx, *(() if valid is None else (valid,)),
                             _name="live_counts")
        else:       # rows and keys are counted by decode steps
            kda, mla = apply(lambda ids: (jnp.zeros((1,), jnp.int32),) * 2,
                             input_ids, _name="live_counts")
        return logits, LayerCaches(caches, {"kda": kda, "mla": mla,
                                            "moe": moe})

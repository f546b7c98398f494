"""Granite-4.0-H (`model_type: granitemoehybrid`): a decoder whose layers
are mostly Mamba-2 mixers with a full-attention layer among every few,
and a routed expert layer plus one shared expert after every mixer.

Written from the published config keys and the Mamba-2 paper
(arXiv:2405.21060, the SSD form):

- block: `h += r * mixer(RMSNorm(h))`, then
  `h += r * (experts(RMSNorm(h)) + shared(RMSNorm(h)))`, r =
  `residual_multiplier`; embeddings times `embedding_multiplier`;
  logits = tied embedding on the final RMSNorm, over `logits_scaling`.
- Mamba-2 mixer: `[z | xBC | dt] = W_in u`; `xBC' = silu(causal
  depthwise conv(xBC) + b)`; `[x | B | C] = xBC'`; `D_t = softplus(dt +
  dt_bias)`; `A = -exp(A_log)`; a head `H_t = exp(D_t A) H_{t-1} + D_t
  x_t (x) B_t`, `y_t = H_t C_t + D x_t`; out = `W_out RMSNorm_w(y *
  silu(z))`. A prompt is computed by chunks of `mamba_chunk_size`
  (`ssd_chunked`), a decode step by one step of the recurrence
  (`ssd_step`).
- attention: causal GQA with NO positional embedding and the softmax
  scale `attention_multiplier`.

What stays float32 whatever the weights' dtype: router logits and
gates, softplus/exp of the recurrence and its decays, the SSM state,
the softmaxes and the norms' statistics. Weights, activations and the
convolution window follow the parameters' dtype.

The model declares what each layer keeps between steps
(`cache_layout()`): the serve loop holds K/V pages for the attention
layers and a row a slot of (conv window, SSM state) for the Mamba ones
(generation/kv_cache.py). A prompt comes left-padded: pad positions are
zeroed before `W_in` and after the convolution, so they leave both
states and every real position untouched.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn.layer_base import Layer
from ..nn.layers_common import Embedding, Linear, LayerList
from ..nn.initializer import Constant, Normal
from ..ops import manipulation as M
from ..ops._dispatch import apply
from ..kernels.norm import fused_rms_norm
from ..kernels.attention import flash_attention_bshd
from ..generation.kv_cache import (LayerCache, LayerCaches, PagedKVCache,
                                   StateCacheEntry,
                                   paged_cache_update_attend)
from ..incubate.distributed.models.moe.dropless import DroplessMoELayer

F32 = jnp.float32


@dataclass
class GraniteMoeHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    intermediate_size: int = 768          # one routed expert's width
    shared_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = ()     # "mamba" | "attention" a layer
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None        # hidden / heads when not given
    attention_multiplier: float = 0.0078125
    embedding_multiplier: float = 12.0
    logits_scaling: float = 16.0
    residual_multiplier: float = 0.22
    rms_norm_eps: float = 1e-5
    num_experts: int = 72                 # the router's width
    num_experts_per_tok: int = 10
    experts_held: Optional[Tuple[int, ...]] = None   # None: all of them
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = ("mamba",) * self.num_hidden_layers
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers or not all(
                t in ("mamba", "attention") for t in self.layer_types):
            raise ValueError(
                f"layer_types must name 'mamba' or 'attention' for each of "
                f"{self.num_hidden_layers} layers, got {self.layer_types}")
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self):
        return self.mamba_d_inner \
            + 2 * self.mamba_n_groups * self.mamba_d_state

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                    shared_intermediate_size=48, num_hidden_layers=4,
                    layer_types=("mamba", "attention", "mamba", "mamba"),
                    num_attention_heads=4, num_key_value_heads=2,
                    attention_multiplier=0.2, embedding_multiplier=3.0,
                    logits_scaling=2.0, residual_multiplier=0.5,
                    num_experts=8, num_experts_per_tok=2,
                    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                    mamba_chunk_size=8, max_position_embeddings=256)
        base.update(kw)
        return GraniteMoeHybridConfig(**base)


# ---------------------------------------------------------------------------
# Mamba-2, in jax. x [b, l, h, p]; dt [b, l, h] float32 (after softplus);
# a_log, d_skip [h]; bm, cm [b, l, g, n] (a group of h / g heads shares
# its B and C). The state is [b, h, p, n] float32.
# ---------------------------------------------------------------------------

def ssd_sequential(x, dt, a_log, bm, cm, d_skip, state=None):
    """The recurrence as written, one token at a time (`lax.scan`): the
    form the chunked scan is tested against."""
    b, _, h, p = x.shape
    if state is None:
        state = jnp.zeros((b, h, p, bm.shape[3]), F32)

    def step(hs, inp):
        xt, dtt, bt, ct = inp
        return ssd_step(hs, xt, dtt, a_log, bt, ct, d_skip)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm))
    state, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), state


def _recur(hs, x, dt, a, bh, ch, d_skip):
    """One token: hs [b, h, p, n], x [b, h, p], dt [b, h], a [h],
    bh/ch [b, h, n] (all float32) -> (new state, y [b, h, p])."""
    decay = jnp.exp(dt * a[None, :])
    hs = hs * decay[..., None, None] \
        + (dt[..., None] * x)[..., None] * bh[:, :, None, :]
    y = jnp.sum(hs * ch[:, :, None, :], axis=-1) + d_skip[None, :, None] * x
    return hs, y


def ssd_step(state, x, dt, a_log, bm, cm, d_skip):
    """One decode step for every row of the state pool. state [r, h, p,
    n] float32; x [r, h, p]; dt [r, h] float32; bm, cm [r, g, n]."""
    rep = x.shape[1] // bm.shape[1]
    state, y = _recur(state, x.astype(F32), dt,
                      -jnp.exp(a_log.astype(F32)),
                      jnp.repeat(bm.astype(F32), rep, axis=1),
                      jnp.repeat(cm.astype(F32), rep, axis=1),
                      d_skip.astype(F32))
    return state, y.astype(x.dtype)


def ssd_chunked(x, dt, a_log, bm, cm, d_skip, chunk):
    """The same recurrence from a zero state, by chunks of `chunk`
    tokens (the SSD form): inside a chunk the outputs are a masked,
    decayed (C B^T) X matmul; between chunks only the [h, p, n] states
    are carried. Decays are float32, the matmuls take the activations'
    dtype and accumulate in float32. Returns (y like x, final state
    [b, h, p, n] float32)."""
    b, l, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    r = h // g
    q = min(int(chunk), l)
    pad = -l % q
    if pad:     # dt = 0 and x = 0: the tail moves neither state nor output
        x, dt, bm, cm = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                         for v in (x, dt, bm, cm))
    c = (l + pad) // q
    dtype = x.dtype
    a = dt * (-jnp.exp(a_log.astype(F32)))[None, None, :]       # [b, l, h]
    xdt = (x.astype(F32) * dt[..., None])
    a = a.reshape(b, c, q, g, r)
    xdt = xdt.reshape(b, c, q, g, r, p)
    bm = bm.reshape(b, c, q, g, n)
    cm = cm.reshape(b, c, q, g, n)
    acs = jnp.cumsum(a, axis=2)                                 # inclusive
    # inside a chunk: y_i += sum_{j <= i} exp(acs_i - acs_j) (C_i.B_j) xdt_j
    cb = jnp.einsum("bcign,bcjgn->bcgij", cm, bm,
                    preferred_element_type=F32)
    acs_t = acs.transpose(0, 1, 3, 4, 2)                # [b, c, g, r, q]
    diff = acs_t[..., :, None] - acs_t[..., None, :]    # [b, c, g, r, i, j]
    tril = jnp.tril(jnp.ones((q, q), jnp.bool_))
    decay = jnp.exp(jnp.where(tril, diff, -jnp.inf))
    mat = (cb[:, :, :, None] * decay).astype(dtype)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", mat, xdt.astype(dtype),
                   preferred_element_type=F32)
    # the state each chunk adds by its end, and the carry between chunks
    to_end = jnp.exp(acs[:, :, -1:] - acs)                      # [b,c,q,g,r]
    added = jnp.einsum("bcjgn,bcjgrp->bcgrpn", bm,
                       (xdt * to_end[..., None]).astype(dtype),
                       preferred_element_type=F32)
    whole = jnp.exp(acs[:, :, -1])                              # [b, c, g, r]

    def carry(hs, inp):
        add_c, whole_c = inp
        return hs * whole_c[..., None, None] + add_c, hs

    last, starts = jax.lax.scan(
        carry, jnp.zeros((b, g, r, p, n), F32),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                   # [b, c, g, r, p, n]
    # what the state at a chunk's start gives its tokens
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", cm, starts.astype(dtype),
                       preferred_element_type=F32) \
        * jnp.exp(acs)[..., None]
    y = y.reshape(b, c * q, h, p)[:, :l] \
        + d_skip.astype(F32)[None, None, :, None] * x[:, :l].astype(F32)
    return y.astype(dtype), last.reshape(b, h, p, n)


def _gated_norm(y, z, w, eps, groups):
    """RMSNorm_w(y * silu(z)) over each of `groups` parts of the last
    axis (the gate comes BEFORE the norm)."""
    v = y.astype(F32) * jax.nn.silu(z.astype(F32))
    shp = v.shape
    v = v.reshape(shp[:-1] + (groups, shp[-1] // groups))
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + F32(eps))
    return (v.reshape(shp) * w.astype(F32)).astype(y.dtype)


class GraniteRMSNorm(Layer):
    def __init__(self, width, eps):
        super().__init__()
        self.weight = self.create_parameter(
            [width], default_initializer=Constant(1.0))
        self.eps = eps

    def forward(self, x):
        return apply(lambda v, w: fused_rms_norm(v, w, self.eps),
                     x, self.weight, _name="rms_norm")


class GraniteMamba2Mixer(Layer):
    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        c = self.config = config
        init = Normal(0.0, c.initializer_range)
        d_in, conv = c.mamba_d_inner, c.mamba_conv_dim
        self.in_proj = Linear(c.hidden_size, d_in + conv + c.mamba_n_heads,
                              weight_attr=init, bias_attr=False)
        # [tap, channel]: tap k multiplies the input d_conv - 1 - k back
        self.conv_weight = self.create_parameter(
            [c.mamba_d_conv, conv], default_initializer=init)
        self.conv_bias = self.create_parameter(
            [conv], default_initializer=Constant(0.0))
        self.dt_bias = self.create_parameter(
            [c.mamba_n_heads], default_initializer=Constant(0.0))
        self.A_log = self.create_parameter(
            [c.mamba_n_heads], default_initializer=Constant(0.0))
        self.D = self.create_parameter(
            [c.mamba_n_heads], default_initializer=Constant(1.0))
        self.norm_weight = self.create_parameter(
            [d_in], default_initializer=Constant(1.0))
        self.out_proj = Linear(d_in, c.hidden_size, weight_attr=init,
                               bias_attr=False)

    def _split(self, xbc, lead):
        c = self.config
        d_in, gn = c.mamba_d_inner, c.mamba_n_groups * c.mamba_d_state
        x = xbc[..., :d_in].reshape(lead + (c.mamba_n_heads, c.mamba_d_head))
        bm = xbc[..., d_in:d_in + gn].reshape(
            lead + (c.mamba_n_groups, c.mamba_d_state))
        cm = xbc[..., d_in + gn:].reshape(
            lead + (c.mamba_n_groups, c.mamba_d_state))
        return x, bm, cm

    def forward(self, u, valid=None, cache=None):
        """u [b, s, hidden]. With `cache` (a StateCacheEntry, s == 1)
        one step of the recurrence over the pool's rows; without, the
        chunked scan from a zero state, `valid` [b, s] marking the real
        positions of a left-padded batch. Returns (out, the updated
        entry | (conv window [b, d_conv - 1, channels], state))."""
        c = self.config
        args = [u, self.in_proj.weight, self.conv_weight, self.conv_bias,
                self.dt_bias, self.A_log, self.D, self.norm_weight,
                self.out_proj.weight]
        if cache is not None:
            if u.shape[1] != 1:
                raise NotImplementedError(
                    "a recurrent layer takes one token a slot a step: "
                    "spans (chunked prefill, speculative verify) need "
                    "state snapshots")
            out, conv, ssm = apply(self._step, *args, cache.conv, cache.ssm,
                                   _name="mamba2_step")
            return out, StateCacheEntry(conv, ssm)
        extra = () if valid is None else (valid,)
        out, conv, ssm = apply(self._scan, *args, *extra,
                               _name="mamba2_scan")
        return out, (conv, ssm)

    def _project(self, u, w_in):
        c = self.config
        d_in, conv = c.mamba_d_inner, c.mamba_conv_dim
        zxd = jnp.dot(u, w_in)
        return (zxd[..., :d_in], zxd[..., d_in:d_in + conv],
                zxd[..., d_in + conv:])

    def _finish(self, y, z, w_norm, w_out):
        c = self.config
        y = _gated_norm(y.reshape(y.shape[:-2] + (c.mamba_d_inner,)), z,
                        w_norm, c.rms_norm_eps, c.mamba_n_groups)
        return jnp.dot(y, w_out)

    def _scan(self, u, w_in, w_conv, b_conv, dt_bias, a_log, d_skip,
              w_norm, w_out, valid=None):
        c = self.config
        with jax.named_scope("mamba.mixer"):
            b, s, _ = u.shape
            k = c.mamba_d_conv
            if valid is not None:
                u = jnp.where(valid[..., None], u, 0)
            z, xbc, dt = self._project(u, w_in)
            window = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
            conv = b_conv.astype(F32)[None, None, :] + sum(
                w_conv[j].astype(F32)[None, None, :]
                * window[:, j:j + s].astype(F32) for j in range(k))
            xbc_c = jax.nn.silu(conv).astype(u.dtype)
            dt = jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32))
            if valid is not None:
                xbc_c = jnp.where(valid[..., None], xbc_c, 0)
                dt = jnp.where(valid[..., None], dt, 0)
            x, bm, cm = self._split(xbc_c, (b, s))
            y, state = ssd_chunked(x, dt, a_log, bm, cm, d_skip,
                                   c.mamba_chunk_size)
            out = self._finish(y, z, w_norm, w_out)
            return out, window[:, s:], state

    def _step(self, u, w_in, w_conv, b_conv, dt_bias, a_log, d_skip,
              w_norm, w_out, conv_state, ssm_state):
        """Every row of the pool advances (the pool has one row more
        than the batch; the step's tensors are padded to it, so the
        update is one elementwise pass over the donated arrays)."""
        c = self.config
        with jax.named_scope("mamba.mixer"):
            b = u.shape[0]
            rows = ssm_state.shape[0]
            z, xbc, dt = self._project(u[:, 0], w_in)
            xbc = jnp.pad(xbc, [(0, rows - b), (0, 0)])
            dt = jnp.pad(dt, [(0, rows - b), (0, 0)])
            window = jnp.concatenate(
                [conv_state, xbc[:, None].astype(conv_state.dtype)], axis=1)
            conv = b_conv.astype(F32)[None, :] + jnp.sum(
                w_conv.astype(F32)[None] * window.astype(F32), axis=1)
            xbc_c = jax.nn.silu(conv).astype(u.dtype)
            dt = jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32))
            x, bm, cm = self._split(xbc_c, (rows,))
            ssm_state, y = ssd_step(ssm_state, x, dt, a_log, bm, cm, d_skip)
            out = self._finish(y[:b], z, w_norm, w_out)
            return out[:, None], window[:, 1:], ssm_state


class GraniteAttention(Layer):
    """Causal GQA without positional embedding, scale
    `attention_multiplier`."""

    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        c = self.config = config
        init = Normal(0.0, c.initializer_range)
        self.q_proj = Linear(c.hidden_size, c.num_attention_heads * c.head_dim,
                             weight_attr=init, bias_attr=False)
        self.k_proj = Linear(c.hidden_size, c.num_key_value_heads * c.head_dim,
                             weight_attr=init, bias_attr=False)
        self.v_proj = Linear(c.hidden_size, c.num_key_value_heads * c.head_dim,
                             weight_attr=init, bias_attr=False)
        self.o_proj = Linear(c.num_attention_heads * c.head_dim, c.hidden_size,
                             weight_attr=init, bias_attr=False)

    def forward(self, h, attn_mask=None, cache=None):
        c = self.config
        b, s, _ = h.shape
        q = M.reshape(self.q_proj(h), [b, s, c.num_attention_heads, c.head_dim])
        k = M.reshape(self.k_proj(h), [b, s, c.num_key_value_heads, c.head_dim])
        v = M.reshape(self.v_proj(h), [b, s, c.num_key_value_heads, c.head_dim])
        if cache is not None:
            out, new_cache = paged_cache_update_attend(
                cache, q, k, v, scale=c.attention_multiplier)
        else:
            out = flash_attention_bshd(
                q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None,
                training=False, scale=c.attention_multiplier)
            new_cache = (k, v)
        out = M.reshape(out, [b, s, c.num_attention_heads * c.head_dim])
        return self.o_proj(out), new_cache


class GraniteSharedMLP(Layer):
    """The shared expert: SwiGLU at `shared_intermediate_size`, ungated,
    added to every token."""

    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        init = Normal(0.0, config.initializer_range)
        self.width = config.shared_intermediate_size
        self.in_proj = Linear(config.hidden_size, 2 * self.width,
                              weight_attr=init, bias_attr=False)
        self.out_proj = Linear(self.width, config.hidden_size,
                               weight_attr=init, bias_attr=False)

    def forward(self, x):
        def fn(v, wi, wo):
            up = jnp.dot(v, wi)
            act = jax.nn.silu(up[..., :self.width].astype(F32)) \
                * up[..., self.width:].astype(F32)
            return jnp.dot(act.astype(v.dtype), wo)
        return apply(fn, x, self.in_proj.weight, self.out_proj.weight,
                     _name="shared_expert")


class GraniteDecoderLayer(Layer):
    def __init__(self, config: GraniteMoeHybridConfig, kind):
        super().__init__()
        self.kind = kind
        self.residual = float(config.residual_multiplier)
        self.input_layernorm = GraniteRMSNorm(config.hidden_size,
                                              config.rms_norm_eps)
        if kind == "mamba":
            self.mamba = GraniteMamba2Mixer(config)
        else:
            self.self_attn = GraniteAttention(config)
        self.post_attention_layernorm = GraniteRMSNorm(config.hidden_size,
                                                       config.rms_norm_eps)
        self.moe = DroplessMoELayer(
            config.hidden_size, config.intermediate_size, config.num_experts,
            config.num_experts_per_tok, held=config.experts_held,
            initializer_range=config.initializer_range)
        self.shared_mlp = GraniteSharedMLP(config)

    def forward(self, h, attn_mask, valid, cache):
        x = self.input_layernorm(h)
        if self.kind == "mamba":
            x, new_cache = self.mamba(x, valid, cache)
        else:
            x, new_cache = self.self_attn(x, attn_mask, cache)
        h = h + x * self.residual
        x = self.post_attention_layernorm(h)
        routed, counts = self.moe(x, valid)
        h = h + (routed + self.shared_mlp(x)) * self.residual
        return h, new_cache, counts


class GraniteMoeHybridModel(Layer):
    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))
        self.layers = LayerList([GraniteDecoderLayer(config, kind)
                                 for kind in config.layer_types])
        self.norm = GraniteRMSNorm(config.hidden_size, config.rms_norm_eps)


class GraniteMoeHybridForCausalLM(Layer):
    """`forward(ids, attn_mask, position_ids, past_key_values,
    use_cache) -> logits | (logits, caches)`, the call the serve
    programs make. Without `past_key_values` the whole (left-padded)
    batch is computed from empty state and `caches` holds, a layer,
    (k, v) or (conv window, SSM state); with a `PagedKVCache` every row
    takes one decode step against its entries. `position_ids` is
    accepted and unused: the model has no positional embedding, and the
    Mamba layers take order from the sequence itself."""

    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        self.config = config
        self.model = GraniteMoeHybridModel(config)

    def cache_layout(self):
        """What each layer keeps between steps (generation/kv_cache.py
        `LayerCache`), in layer order."""
        c = self.config
        kv = LayerCache("kv", (c.num_key_value_heads, c.head_dim))
        state = LayerCache("state", (
            (c.mamba_d_conv - 1, c.mamba_conv_dim),
            (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state)))
        return [state if t == "mamba" else kv for t in c.layer_types]

    def step_counters(self):
        """What the vectors in `caches.counters` count, element by
        element: {key: [(metric, labels)]} (docs/OBSERVABILITY.md)."""
        c = self.config
        held = range(c.num_experts) if c.experts_held is None \
            else c.experts_held
        return {"moe": [("moe.assignments", {}),
                        ("moe.assignments_local", {})]
                + [("moe.expert_tokens", {"expert": str(e)}) for e in held]}

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                past_key_values=None, use_cache=False):
        c = self.config
        if past_key_values is not None \
                and not isinstance(past_key_values, PagedKVCache):
            raise NotImplementedError(
                "GraniteMoeHybridForCausalLM continues only from the serve "
                "loop's caches (PagedKVCache of page and state entries)")
        m = self.model
        h = m.embed_tokens(input_ids) * float(c.embedding_multiplier)
        valid = None
        if past_key_values is not None:
            valid = past_key_values.active
            if valid is not None:
                valid = apply(lambda a: a[:, None], valid, _name="active")
        elif attn_mask is not None:
            # a position is real where the last query may see it
            valid = apply(lambda mk: mk[:, 0, -1, :] > -1.0, attn_mask,
                          _name="valid_positions")
        caches, counts = [], None
        for i, layer in enumerate(m.layers):
            cache = past_key_values[i] if past_key_values is not None \
                else None
            h, new_cache, n = layer(h, attn_mask, valid, cache)
            caches.append(new_cache)
            counts = n if counts is None else counts + n
        h = m.norm(h)
        logits = apply(
            lambda v, e: jnp.einsum("bsh,vh->bsv", v, e)
            / jnp.asarray(c.logits_scaling, v.dtype),
            h, m.embed_tokens.weight, _name="tied_logits")
        if use_cache:
            return logits, LayerCaches(caches, {"moe": counts})
        return logits

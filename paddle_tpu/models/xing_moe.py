"""Xing4.0 (`model_type: xing4_0`): a DeepSeek-V2/V3-shaped decoder
(multi-head latent attention with a compressed query in every layer,
YaRN-scaled rotary frequencies, dense SwiGLU in the first
`first_k_dense_replace` layers, then sigmoid-routed experts with a
choice bias and a shared expert, an untied head) whose residual path is
`hc_mult` streams wide: every sublayer reads a learned, input-dependent
mix of a token's streams and writes back through two more maps, one of
them projected onto the doubly stochastic matrices by Sinkhorn's sweeps
(manifold-constrained hyper-connections). The multi-token-prediction
layer is not built: nothing published says how it meets the streams.

Written from the published config keys, mHC (arXiv:2512.24880, the
parameterisation of its section 4) over hyper-connections
(arXiv:2409.19606), DeepSeek-V2 (MLA and its YaRN, arXiv:2405.04434)
and DeepSeek-V3 (routing, arXiv:2412.19437); what the config has no key
for is marked (assumed). C = hidden, n = `hc_mult`, no bias, RMSNorm eps
`rms_norm_eps`:

- streams: `X_0 = [Emb(x)] x n` (the embedding copied into every
  stream); after the last layer `h = sum_j X_L[j]` (assumed: the row
  sum, no learned collapse), the final RMSNorm, the head.
- a sublayer `F` (attention, or FFN; each with its one pre-norm inside
  and mHC parameters of its own, assumed): `u = sum_j H_pre[j] X[j]`,
  `X' = H_res X + H_post^T F(u)`, the three maps from the token's
  streams as kernels/hyper_connections.py says (`hc_sinkhorn_iters`
  sweeps, columns then rows, `hc_eps` in the denominators, assumed; the
  clamp `mhc_h_res_clamp_min/max` before the exp).
- attention: `c_q = RMSNorm(W_qa u)`; `q = W_qb c_q` -> H x [nope |
  rope]; `[c | k_r] = W_kva u`, `c <- RMSNorm(c)`; `[k_nope,h | v_h] =
  W_kvb,h c`; `W_o`. Kept a token: `[c | k_r]`, ONE row for all heads.
  A prompt is computed decompressed (the flash kernel), a decode step
  absorbed (kernels/latent_attention.py): `PanguLatentAttention`'s two
  forms with this model's angles and scale.
- YaRN (`rope_scaling`, static: at every length): `yarn_frequencies`;
  cos and sin times `m(mscale) / m(mscale_all_dim)`, the scores times
  `m(mscale_all_dim)^2 / sqrt(nope + rope)`, `m(s) = 0.1 s ln(factor)
  + 1`. Interleaved pairs (assumed).
- experts: `s = sigmoid(W_r y)` float32; the `num_experts_per_tok`
  largest of `s + b` (`noaux_tc`, one group); gates the chosen `s` over
  their sum times `routed_scaling_factor`; plus the shared expert,
  ungated (`group_limited_sigmoid_route`). The layer holds the whole
  bank unless told its share (`experts_held`).

Float32 whatever the weights' dtype: the norms' statistics, the
rotation, the router's scores and gates, every softmax, the logits and
all of the mHC coefficient arithmetic. The streams keep the weights'
dtype between sublayers, as `[tokens, n x C]` (stream j on columns jC
.. (j + 1)C: kernels/hyper_connections.py says why).

The model declares what a layer keeps between steps (`cache_layout()`:
one latent row a token a layer) and NO drafter, and asks for the serve
loop's long prefill. Two calls reach `forward`: without
`past_key_values` a whole left-padded batch (`attn_mask` the keys'
validity [B, S], or the additive [B, 1, S, S] of which only that is
read; with `use_cache` the logits of the LAST position alone), and with
a `PagedKVCache` one decode token a slot.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..nn.layer_base import Layer
from ..nn.layers_common import Embedding, Linear, LayerList
from ..nn.initializer import Constant, Normal
from ..ops._dispatch import apply
from ..generation.kv_cache import LayerCache, LayerCaches, PagedKVCache
from ..kernels.hyper_connections import mhc_post, mhc_pre, unpack
from .granite_hybrid import GraniteRMSNorm as RMSNorm
from .keye_vl2 import rope_angles
from .ling_hybrid import LingMLP, LingSparseMoE
from .openpangu_moe import PanguLatentAttention

F32 = jnp.float32


def yarn_range(dim, theta, original, beta_fast, beta_slow):
    """(lo, hi): the frequency pairs between which YaRN blends. Pair i
    turns `original / (2 pi theta^(2i / dim))` times over the original
    context; `dim(t)` is the (real) index of the pair that turns t
    times."""
    at = lambda turns: dim * math.log(original / (2 * math.pi * turns)) \
        / (2 * math.log(theta))
    return max(math.floor(at(beta_fast)), 0), \
        min(math.ceil(at(beta_slow)), dim - 1)


def yarn_frequencies(dim, theta, factor, original, beta_fast, beta_slow):
    """The `dim / 2` frequencies YaRN rotates by (float32): pair i keeps
    `theta^(-2i / dim)` below `lo`, takes a `factor`-th of it above
    `hi`, and a linear blend between."""
    lo, hi = yarn_range(dim, theta, original, beta_fast, beta_slow)
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - lo)
                   / max(hi - lo, 1e-3), 0.0, 1.0)
    return (f * (1.0 - ramp) + f / factor * ramp).astype(np.float32)


def yarn_mscale(factor, mscale):
    """`m(s) = 0.1 s ln(factor) + 1` (1 without scaling)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


@dataclass
class XingMoEConfig:
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216         # the dense layers' width
    moe_intermediate_size: int = 1024     # one routed expert's width
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=lambda: dict(
        type="yarn", factor=64, original_max_position_embeddings=4096,
        beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1))
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    experts_held: Optional[Tuple[int, ...]] = None   # None: all of them
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        kind = self.rope_scaling.get("type", self.rope_scaling.get(
            "rope_type"))
        if kind != "yarn":
            raise ValueError(f"rope_scaling type {kind!r}: YaRN is built")
        if self.rotation_scale != 1.0:
            raise ValueError(
                f"rope_scaling mscale / mscale_all_dim gives cos and sin a "
                f"factor of {self.rotation_scale}: served is 1 (the two "
                f"equal, as published)")

    # the names `LingSparseMoE` and `PanguLatentAttention` read
    @property
    def num_experts(self):
        return self.n_routed_experts

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def stream_width(self):
        return self.hc_mult * self.hidden_size

    @property
    def hc_maps(self):
        """phi's columns: H_pre, H_post, then H_res row-major."""
        return 2 * self.hc_mult + self.hc_mult ** 2

    @functools.cached_property
    def inv_freq(self):
        s = self.rope_scaling
        return yarn_frequencies(
            self.qk_rope_head_dim, self.rope_theta, s["factor"],
            s["original_max_position_embeddings"], s["beta_fast"],
            s["beta_slow"])

    @property
    def rotation_scale(self):
        """What cos and sin are multiplied by."""
        s = self.rope_scaling
        return yarn_mscale(s["factor"], s["mscale"]) \
            / yarn_mscale(s["factor"], s["mscale_all_dim"])

    @property
    def softmax_scale(self):
        """What the scores are multiplied by."""
        s = self.rope_scaling
        return yarn_mscale(s["factor"], s["mscale_all_dim"]) ** 2 \
            / math.sqrt(self.qk_head_dim)


class XingLatentAttention(PanguLatentAttention):
    """`PanguLatentAttention` at YaRN's frequencies and scale."""

    def _angles(self, pos):
        c = self.config
        return rope_angles(pos, c.qk_rope_head_dim, c.rope_theta,
                           inv_freq=c.inv_freq)

    def _scale(self):
        return self.config.softmax_scale


class XingHyperConnection(Layer):
    """One sublayer's three maps: `phi` [n C, 2n + n^2] in the model's
    dtype, the scalars `a` [3] and the biases `b` [2n + n^2] float32.
    `pre` gives what the sublayer reads and the maps as one packed row a
    token, `post` the streams after it (kernels/hyper_connections.py)."""

    def __init__(self, config: XingMoEConfig):
        super().__init__()
        c = self.config = config
        self.phi = self.create_parameter(
            [c.stream_width, c.hc_maps],
            default_initializer=Normal(0.0, c.stream_width ** -0.5))
        # float32 whatever the model's dtype; their start is a training
        # matter (the paper's a = 0.01): a checkpoint or the benchmark's
        # builder sets them
        self.alpha = self.create_parameter(
            [3], dtype="float32", default_initializer=Constant(0.01))
        self.beta = self.create_parameter(
            [c.hc_maps], dtype="float32", default_initializer=Constant(0.0))

    def pre(self, streams):
        c = self.config
        return apply(lambda x, phi, a, b: mhc_pre(
            x, phi, a, b, n=c.hc_mult, iters=c.hc_sinkhorn_iters,
            eps=c.rms_norm_eps, hc_eps=c.hc_eps,
            clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max)),
            streams, self.phi, self.alpha, self.beta, _name="mhc_pre")

    def post(self, streams, out, coef):
        n = self.config.hc_mult
        return apply(lambda x, f, cf: mhc_post(
            x, f.reshape(x.shape[0], -1), cf, n=n), streams, out, coef,
            _name="mhc_post")


def map_stats(coef, valid, n):
    """[largest |row sum - 1|, largest |column sum - 1| of the H_res of
    every row of `coef`; the off-diagonal mass of the H_res of the rows
    in `valid` [T], summed; how many those are], float32."""
    _, _, res = unpack(coef, n)
    off = jnp.sum(res, axis=(1, 2)) - jnp.trace(res, axis1=1, axis2=2)
    return jnp.stack([
        jnp.max(jnp.abs(jnp.sum(res, axis=2) - F32(1.0))),
        jnp.max(jnp.abs(jnp.sum(res, axis=1) - F32(1.0))),
        jnp.sum(jnp.where(valid, off, F32(0.0))) / F32(n),
        jnp.sum(valid, dtype=F32)])


def add_stats(a, b):
    """Two `map_stats`: the larger errors, the sums added."""
    return jnp.concatenate([jnp.maximum(a[:2], b[:2]), a[2:] + b[2:]])


class XingDecoderLayer(Layer):
    """Two sublayers, each with its one pre-norm and its own maps; the
    residual is the streams', mixed by `H_res`."""

    def __init__(self, config: XingMoEConfig, dense):
        super().__init__()
        self.config = config
        norm = lambda: RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.attn_hc = XingHyperConnection(config)
        self.input_layernorm = norm()
        self.self_attn = XingLatentAttention(config)
        self.mlp_hc = XingHyperConnection(config)
        self.post_attention_layernorm = norm()
        self.dense = bool(dense)
        if self.dense:
            self.mlp = LingMLP(config, config.intermediate_size)
        else:
            self.moe = LingSparseMoE(config)
            self.shared_mlp = LingMLP(
                config,
                config.moe_intermediate_size * config.n_shared_experts)

    def forward(self, streams, shape, pos, valid, cache):
        """streams [B x S, n C]; `shape` = (B, S) -> (streams, what the
        layer keeps, routing counts or None, `map_stats` of its two
        sublayers)."""
        n = self.config.hc_mult
        rows = lambda t: apply(lambda v: v.reshape(shape + (-1,)), t,
                               _name="rows")
        u, coef = self.attn_hc.pre(streams)
        x, kept = self.self_attn(self.input_layernorm(rows(u)), pos, valid,
                                 cache)
        streams = self.attn_hc.post(streams, x, coef)
        u, coef2 = self.mlp_hc.pre(streams)
        y = self.post_attention_layernorm(rows(u))
        counts = None
        if self.dense:
            f = self.mlp(y)
        else:
            routed, counts = self.moe(y, valid)
            f = routed + self.shared_mlp(y)
        streams = self.mlp_hc.post(streams, f, coef2)
        stats = apply(lambda a, b, ok: add_stats(
            map_stats(a, ok.reshape(-1), n), map_stats(b, ok.reshape(-1), n)),
            coef, coef2, valid, _name="mhc_stats")
        return streams, kept, counts, stats


class XingMoEModel(Layer):
    def __init__(self, config: XingMoEConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))
        self.layers = LayerList([
            XingDecoderLayer(config, i < config.first_k_dense_replace)
            for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)


class XingMoEForCausalLM(Layer):
    """`forward(ids, attn_mask, position_ids, past_key_values,
    use_cache) -> logits | (logits, caches)`, the call the serve
    programs make (the module's docstring says what each argument may
    be)."""

    # the serve loop's prefill hands over the keys' validity, not a
    # dense mask, and takes the last position's logits (inference/
    # __init__.py, "the long prefill")
    long_prefill = True
    # one prompt a program: the stream array of a row is n times a
    # hidden state's, and each row count is one more program a bucket
    long_prefill_rows = 1

    def __init__(self, config: XingMoEConfig):
        super().__init__()
        self.config = config
        self.model = XingMoEModel(config)
        self.lm_head = Linear(
            config.hidden_size, config.vocab_size, bias_attr=False,
            weight_attr=Normal(0.0, config.initializer_range))

    def cache_layout(self):
        """What each layer keeps between steps (generation/kv_cache.py
        `LayerCache`): one latent row a token a layer. The streams are
        a token's own and are kept nowhere."""
        c = self.config
        return [LayerCache("latent", (c.latent_width,))] \
            * c.num_hidden_layers

    def step_counters(self):
        """What the vectors in `caches.counters` hold, element by
        element: {key: [(metric, labels[, "max"])]}
        (docs/OBSERVABILITY.md). "mhc": of every H_res a step formed,
        the largest row and column error (kept as maxima) and, over the
        tokens that belong to a request, the off-diagonal mass and how
        many maps. "mla": the rows a decode step's tokens could see; a
        prefill gives zero."""
        c = self.config
        held = range(c.n_routed_experts) if c.experts_held is None \
            else c.experts_held
        return {"mhc": [("mhc.sinkhorn_row_err_max", {}, "max"),
                        ("mhc.sinkhorn_col_err_max", {}, "max"),
                        ("mhc.offdiag_mass", {}), ("mhc.maps", {})],
                "mla": [("mla.keys_live", {})],
                "moe": [("moe.assignments", {}),
                        ("moe.assignments_local", {})]
                + [("moe.expert_tokens", {"expert": str(e)}) for e in held]}

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                past_key_values=None, use_cache=False):
        paged = past_key_values is not None
        if paged and not isinstance(past_key_values, PagedKVCache):
            raise NotImplementedError(
                "XingMoEForCausalLM continues only from the serve loop's "
                "caches (PagedKVCache of latent entries)")
        c = self.config
        m = self.model
        n, n_layers = c.hc_mult, len(m.layers)
        b, s = input_ids.shape
        if position_ids is None:
            position_ids = apply(lambda ids: jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), (b, s)), input_ids,
                _name="positions")
        if paged:
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
        # the embedding, copied into every stream
        streams = apply(lambda e: jnp.concatenate(
            [e.reshape(b * s, -1)] * n, axis=1), m.embed_tokens(input_ids),
            _name="streams")
        caches, moe, stats = [], None, None
        for i, layer in enumerate(m.layers):
            streams, kept, counts, st = layer(
                streams, (b, s), position_ids, valid,
                past_key_values[i] if paged else None)
            if not paged:
                # a layer's temporaries end with the layer (models/
                # glm_moe_dsa.py)
                streams = apply(jax.lax.optimization_barrier, streams,
                                _name="layer_end")
            caches.append(kept)
            if counts is not None:
                moe = counts if moe is None else moe + counts
            stats = st if stats is None else apply(add_stats, stats, st,
                                                   _name="mhc_stats")
        last = use_cache and not paged     # a prefill continues from its
        # last position: the streams' sum there, in float32

        def collapse(x):
            x = x.reshape(b, s, -1)
            if last:
                x = x[:, -1:]
            width = x.shape[-1] // n
            return sum(x[..., j * width:(j + 1) * width].astype(F32)
                       for j in range(n)).astype(x.dtype)

        h = apply(collapse, streams, _name="stream_sum")
        # float32 logits from the parameters' dtype (models/keye_vl2.py)
        logits = apply(lambda x, w: jnp.dot(x, w, preferred_element_type=F32),
                       m.norm(h), self.lm_head.weight, _name="lm_head")
        if not use_cache:
            return logits
        if moe is None:     # no expert layer among these
            n_held = c.n_routed_experts if c.experts_held is None \
                else len(c.experts_held)
            moe = apply(lambda ids: jnp.zeros((2 + n_held,), jnp.int32),
                        input_ids, _name="moe_counts")
        if paged:
            mla = apply(lambda ctx, ok: jnp.sum(jnp.where(
                ok[:, 0], ctx.astype(jnp.int32) + 1, 0),
                dtype=jnp.int32)[None] * jnp.int32(n_layers),
                past_key_values[0].context_lens, valid, _name="mla_counts")
        else:       # rows are counted by decode steps
            mla = apply(lambda ids: jnp.zeros((1,), jnp.int32), input_ids,
                        _name="mla_counts")
        return logits, LayerCaches(caches, {"mhc": stats, "mla": mla,
                                            "moe": moe})

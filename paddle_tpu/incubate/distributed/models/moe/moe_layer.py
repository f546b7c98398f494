"""Mixture-of-Experts layer with expert parallelism.

Reference parity: python/paddle/incubate/distributed/models/moe/moe_layer.py
(`MoELayer`: gate + alltoall dispatch/combine of tokens to per-rank experts
via the GlobalScatter/GlobalGather collective ops,
paddle/fluid/operators/collective/global_scatter_op*).

TPU-native design (SURVEY.md §2.3 EP row): GShard-style static-shape dense
dispatch. Routing produces a combine tensor [N, E, C] (differentiable
through the gate probs) and a boolean dispatch mask; token movement is two
einsums. Experts live as a STACKED weight bank [E, ...] sharded over the
mesh 'expert' axis, so under jit XLA lowers the dispatch einsum to the
same all-to-all the reference codes by hand (GlobalScatter ≡ sharded
einsum in, GlobalGather ≡ sharded einsum out) and the expert FFN to a
grouped (batched) matmul per expert shard. Capacity gives static shapes —
no ragged tensors, jit-friendly.

A LayerList of arbitrary per-expert Layers is also accepted for API
parity; it runs as an unrolled loop (no expert-axis sharding benefit).
"""
from __future__ import annotations

import math as pymath

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .....nn.layer_base import Layer
from .....nn import functional as F
from .....nn.initializer import XavierUniform
from .....ops._dispatch import apply
from .....ops.creation import _coerce
from .....ops.math import einsum
from .....distributed.mesh import get_mesh, axis_size
from .gate import build_gate, BaseGate, load_balance_loss


def _routing_sparse(probs, *, top_k, capacity, norm_topk):
    """probs [N, E] f32 -> (topi [N,k] i32 expert per slot, pos [N,k] i32
    position in the expert queue, keep [N,k] bool survived-capacity,
    topv [N,k] f32 combine weights, aux_loss scalar). The sparse routing
    state both dispatch paths derive from; static shapes."""
    n, e = probs.shape
    topv, topi = jax.lax.top_k(probs, top_k)              # [N, k]
    masks = jax.nn.one_hot(topi, e, dtype=jnp.int32)      # [N, k, E]

    # position of each (token, slot) within its expert queue; slot-major
    # priority (all slot-0 assignments rank before slot-1), token order
    # within a slot — the GShard policy.
    flat = masks.transpose(1, 0, 2).reshape(top_k * n, e)
    pos_flat = jnp.cumsum(flat, axis=0) - flat
    pos = pos_flat.reshape(top_k, n, e).transpose(1, 0, 2)  # [N, k, E]
    keep = ((pos < capacity) & (masks > 0)).any(-1)         # [N, k]
    pos_in_e = jnp.sum(pos * masks, axis=-1)                # [N, k]

    aux = load_balance_loss(probs, masks[:, 0])

    if norm_topk:
        # normalize over ALL top-k probs BEFORE capacity dropping (the
        # reference norm_topk_prob semantics) so an overflow-dropped slot
        # does not inflate the surviving slots' weights
        denom = jnp.sum(topv, axis=-1, keepdims=True)
        topv = topv / jnp.maximum(denom, 1e-9)
    return topi, pos_in_e, keep, topv, aux


def _routing_jax(probs, *, top_k, capacity, norm_topk):
    """Dense GShard routing tensors (combine [N, E, C] f32, dispatch
    [N, E, C] bool, aux) built from the sparse state — the einsum
    fallback path; overflow tokens drop (position >= capacity maps to
    the all-zero one-hot row)."""
    n, e = probs.shape
    topi, pos_in_e, keep, topv, aux = _routing_sparse(
        probs, top_k=top_k, capacity=capacity, norm_topk=norm_topk)
    comb = jnp.zeros((n, e, capacity), jnp.float32)
    for slot in range(top_k):
        slot_pos = jnp.where(keep[:, slot], pos_in_e[:, slot], capacity)
        oh_c = jax.nn.one_hot(slot_pos, capacity, dtype=jnp.float32)
        # dropped slots route their expert one-hot to the sentinel row e
        # (all-zero), building m in one one_hot instead of mask-multiply
        m = jax.nn.one_hot(
            jnp.where(keep[:, slot], topi[:, slot], e), e,
            dtype=jnp.float32)
        comb = comb + (m[:, :, None] * oh_c[:, None, :]
                       * topv[:, slot][:, None, None])
    disp = comb > 0.0
    return comb, disp, aux


def _dispatch_scatter(tokens, topi, pos, keep, capacity, num_experts):
    """Sort-free sparse dispatch: place each surviving (token, slot)
    directly at its (expert, queue position) via one scatter — O(N·k·d)
    instead of the dense einsum's O(N·E·C·d) (VERDICT r4: dispatch cost
    must not be dense in E×capacity; megablox-style sorted dispatch with
    capacity-static shapes). Dropped slots scatter out of bounds
    (mode='drop'). Queue positions are unique per expert by construction
    (cumsum), so no collisions."""
    n, d = tokens.shape
    k = topi.shape[1]
    dest_p = jnp.where(keep, pos, capacity)               # capacity = drop
    toks = jnp.broadcast_to(tokens[:, None, :], (n, k, d)).reshape(n * k, d)
    out = jnp.zeros((num_experts, capacity, d), tokens.dtype)
    return out.at[topi.reshape(-1), dest_p.reshape(-1)].set(
        toks, mode="drop")


def _combine_gather(expert_out, topi, pos, keep, topv):
    """Sparse combine: gather each slot's expert output row and weight
    it — O(N·k·d); dropped slots read 0 (mode='fill')."""
    capacity = expert_out.shape[1]
    dest_p = jnp.where(keep, pos, capacity)
    gath = expert_out.at[topi, dest_p].get(mode="fill", fill_value=0)
    return jnp.sum(topv[..., None].astype(expert_out.dtype) * gath, axis=1)


class ExpertMLP(Layer):
    """Stacked expert FFN bank: weights [E, d, h] / [E, h, d], sharded on
    the 'expert' mesh axis — the grouped-matmul execution path."""

    def __init__(self, num_experts, d_model, d_hidden, activation="gelu"):
        super().__init__()
        if activation not in ("gelu", "silu"):
            raise ValueError(f"unsupported expert activation {activation!r}; "
                             "expected 'gelu' or 'silu'")
        self.num_experts = num_experts
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.activation = activation
        self.w1 = self.create_parameter(
            [num_experts, d_model, d_hidden],
            default_initializer=XavierUniform())
        self.b1 = self.create_parameter(
            [num_experts, 1, d_hidden], is_bias=True)
        self.w2 = self.create_parameter(
            [num_experts, d_hidden, d_model],
            default_initializer=XavierUniform())
        self.b2 = self.create_parameter(
            [num_experts, 1, d_model], is_bias=True)
        for p in (self.w1, self.b1, self.w2, self.b2):
            p._partition_spec = PartitionSpec("expert")

    def forward(self, x):
        """x: [E, C, d] -> [E, C, d] (batched per-expert matmul)."""
        h = einsum("ecd,edh->ech", x, self.w1) + self.b1
        h = F.gelu(h) if self.activation == "gelu" else F.silu(h)
        return einsum("ech,ehd->ecd", h, self.w2) + self.b2


def _expert_constrain(t):
    mesh = get_mesh()
    if mesh is None or axis_size("expert", mesh) <= 1:
        return t
    # inside another shard_map (e.g. a pipeline stage body) the
    # constraint must be expressed over the context abstract mesh, whose
    # already-manual axes are typed Manual
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty and ctx.manual_axes:
        mesh = ctx
    sh = NamedSharding(mesh, PartitionSpec("expert"))
    return apply(lambda v: jax.lax.with_sharding_constraint(v, sh),
                 _coerce(t))


class MoELayer(Layer):
    """paddle.incubate.distributed.models.moe.MoELayer parity.

    experts: ExpertMLP bank (fast path) or a LayerList of per-expert
    Layers (parity path); gate: BaseGate / dict / str (see gate.py).
    """

    def __init__(self, d_model, experts=None, gate=None, moe_group=None,
                 mp_group=None, recompute_interval=0, num_experts=None,
                 d_hidden=None, capacity_factor=1.25, norm_topk_prob=False,
                 dispatch_mode="scatter", **kw):
        super().__init__()
        if dispatch_mode not in ("scatter", "dense"):
            raise ValueError(
                f"dispatch_mode must be 'scatter' or 'dense', got "
                f"{dispatch_mode!r}")
        # 'scatter' (default): O(N·k·d) sparse placement/gather;
        # 'dense': the GShard one-hot einsum fallback, O(N·E·C·d)
        self.dispatch_mode = dispatch_mode
        self.d_model = d_model
        if experts is None:
            if num_experts is None or d_hidden is None:
                raise ValueError(
                    "MoELayer needs `experts` or (num_experts, d_hidden)")
            experts = ExpertMLP(num_experts, d_model, d_hidden)
        self.experts = experts
        if isinstance(experts, ExpertMLP):
            self.num_experts = experts.num_experts
        else:
            self.num_experts = len(experts)
        self.gate = build_gate(gate, d_model, self.num_experts)
        self.capacity_factor = capacity_factor
        self.norm_topk_prob = norm_topk_prob
        self.moe_group = moe_group

    def _capacity(self, n_tokens):
        c = int(pymath.ceil(
            self.gate.top_k * n_tokens / self.num_experts
            * self.capacity_factor))
        return max(c, 4)

    def forward(self, x):
        orig_shape = list(_coerce(x).shape)
        d = orig_shape[-1]
        n = 1
        for s in orig_shape[:-1]:
            n *= s
        tokens = x.reshape([n, d])

        logits = self.gate(tokens)                       # [N, E]
        probs = F.softmax(logits.astype("float32"), axis=-1)
        cap = self._capacity(n)

        if self.dispatch_mode == "scatter":
            topi, pos, keep, topv, aux = apply(
                lambda p: _routing_sparse(
                    p, top_k=self.gate.top_k, capacity=cap,
                    norm_topk=self.norm_topk_prob),
                _coerce(probs), _name="moe_routing")
            if self.gate.has_aux_loss:
                self.gate.aux_loss = aux
            expert_in = apply(
                lambda t, ti, po, kp: _dispatch_scatter(
                    t, ti, po, kp, cap, self.num_experts),
                tokens, topi, pos, keep, _name="moe_dispatch")
        else:
            comb, disp, aux = apply(
                lambda p: _routing_jax(
                    p, top_k=self.gate.top_k, capacity=cap,
                    norm_topk=self.norm_topk_prob),
                _coerce(probs), _name="moe_routing")
            if self.gate.has_aux_loss:
                self.gate.aux_loss = aux
            expert_in = einsum("nec,nd->ecd", disp.astype(tokens.dtype),
                               tokens)
        expert_in = _expert_constrain(expert_in)

        if isinstance(self.experts, ExpertMLP):
            expert_out = self.experts(expert_in)
        else:
            from .....ops.manipulation import stack
            outs = [self.experts[e](expert_in[e])
                    for e in range(self.num_experts)]
            expert_out = stack(outs, axis=0)
        expert_out = _expert_constrain(expert_out)

        if self.dispatch_mode == "scatter":
            out = apply(_combine_gather, expert_out, topi, pos, keep,
                        topv, _name="moe_combine")
            out = out.astype(tokens.dtype)
        else:
            out = einsum("nec,ecd->nd", comb.astype(tokens.dtype),
                         expert_out)
        return out.reshape(orig_shape)

"""DistTrainStep — the hybrid-parallel compiled train step.

This is the TPU-native core of Fleet (SURVEY.md §2.3 "hybrid composition"):
one pjit-compiled program whose sharding specs encode the strategy.

    DP          batch sharded P('data'); grad psum inserted by XLA
    ZeRO-1/2    opt state sharded over 'data' (XLA sharded weight update)
    ZeRO-3      params sharded over 'data' (FSDP allgather by XLA)
    TP/SP       params tagged by mp_layers with P(..., 'model') + activation
                constraints inside the layers
    recompute   jax.checkpoint inside the model (fleet.recompute)

Pipeline ('stage' axis) lives in PipelineTrainStep below: a shard_map over
the stage axis with ppermute handoff, differentiated by jax.grad.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable, List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ...tensor import Tensor
from ...framework.random import default_generator
from ..mesh import get_mesh, ensure_mesh, mesh_scope, axis_size
from ...jit.bridge import _clip_grads_functional
from ...observability import enabled as _obs_enabled
from ...observability import tracing as _tracing
from ...observability.train_metrics import StepTelemetry, batch_tokens
from ...kernels._common import kernel_partition_scope


@contextlib.contextmanager
def _step_scope(mesh):
    """What every trace and dispatch of the step runs under: the
    process mesh the TP layers read, and the mesh the Pallas kernels are
    partitioned over by hand (kernels._common.partitioned)."""
    with mesh_scope(mesh), kernel_partition_scope(mesh):
        yield


def _partition_spec_for(p, stage3: bool, mesh: Mesh):
    """Final NamedSharding for a parameter: layer-tagged TP spec, plus
    ZeRO-3 'data' sharding on the first still-replicated, divisible dim."""
    base = list(getattr(p, "_partition_spec", PartitionSpec()) or ())
    shape = tuple(p._value.shape)
    base = base + [None] * (len(shape) - len(base))
    if stage3:
        dsize = mesh.shape["data"]
        if dsize > 1:
            for i, (dim, cur) in enumerate(zip(shape, base)):
                if cur is None and dim % dsize == 0 and dim >= dsize:
                    base[i] = "data"
                    break
    # drop axes absent from mesh or of size 1 (cleaner HLO)
    spec = [s if (s is None or mesh.shape.get(s, 1) > 1) else None
            for s in base]
    return NamedSharding(mesh, PartitionSpec(*spec))


def _opt_state_sharding(p_sharding, state_leaf_shape, stage, mesh,
                        param_shape):
    """Opt-state leaves mirror the param sharding; with ZeRO>=1 also shard
    over 'data' if the param itself isn't."""
    spec = list(p_sharding.spec) + [None] * (len(state_leaf_shape)
                                             - len(p_sharding.spec))
    if tuple(state_leaf_shape) != tuple(param_shape):
        # scalar step counters etc. — replicate
        return NamedSharding(mesh, PartitionSpec())
    if stage >= 1 and "data" not in spec:
        dsize = mesh.shape["data"]
        for i, (dim, cur) in enumerate(zip(state_leaf_shape, spec)):
            if cur is None and dsize > 1 and dim % dsize == 0 and dim >= dsize:
                spec[i] = "data"
                break
    return NamedSharding(mesh, PartitionSpec(*spec))


class DistTrainStep:
    """Compiled hybrid-parallel train step (DP/ZeRO/TP/SP composition).

    loss_fn(model_out, *labels) -> scalar. Batch dim 0 is sharded over
    'data'. Returns the (replicated) loss as a Tensor; model params,
    buffers and optimizer state stay device-sharded between steps.
    """

    def __init__(self, model, optimizer, loss_fn: Callable,
                 n_model_inputs: int = 1, sharding_stage: Optional[int] = None,
                 mesh: Optional[Mesh] = None, batch_specs=None,
                 donate_state: bool = True, scaler=None,
                 weight_update_sharding: Optional[bool] = None,
                 runtime_config=None, grad_accum_steps: int = 1):
        from ...framework.runtime_config import RuntimeConfig
        # gradient-comm knobs (bucket bytes, int8 comm, default ZeRO
        # stage) come from the typed RuntimeConfig; absent one, the
        # FLAGS-sourced default preserves the flag-driven behavior
        # (framework/runtime_config)
        self._rc = runtime_config if runtime_config is not None \
            else RuntimeConfig.from_flags()
        self._model = model
        self._opt = optimizer
        self._loss_fn = loss_fn
        self._n_in = n_model_inputs
        self._scaler = scaler if (scaler is not None
                                  and scaler.is_enable()) else None
        self._mesh = mesh or ensure_mesh()
        stage = sharding_stage
        if stage is None:
            stage = getattr(model, "_sharding_stage", None)
        if stage is None:
            stage = getattr(optimizer, "_sharding_stage", None)
        if stage is None:
            # the RuntimeConfig knob (tools/autotune.py proposes it from
            # mem.opt_state_bytes pressure) is the default of last resort
            stage = int(getattr(self._rc, "zero_stage", 0) or 0)
        self._stage = int(stage or 0)
        self._batch_specs = batch_specs
        self._donate = donate_state
        wus = weight_update_sharding
        if wus is None:
            # ZeRO stages 1 and 2 ARE weight-update sharding (opt state
            # over 'data'); stage 2 additionally keeps persistent grad
            # shards (grad_accum_steps > 1)
            wus = bool(getattr(optimizer, "_weight_update_sharding",
                               False)) or self._stage in (1, 2)
        dsize = self._mesh.shape.get("data", 1)
        # ZeRO-3 already shards the params themselves; ZeRO-1/2-style
        # weight-update sharding is meaningful for stage <= 2 with a
        # real data axis
        self._wus = bool(wus) and dsize > 1 and self._stage < 3
        self._accum_n = max(1, int(grad_accum_steps))
        self._micro = 0
        if self._accum_n > 1 and self._scaler is not None:
            raise NotImplementedError(
                "grad_accum_steps > 1 with a GradScaler is not "
                "supported: loss-scale adaptation is per-update while "
                "the accumulated grads span several micro-steps — use "
                "grad_accum_steps=1 with the scaler, or drop the "
                "scaler (bf16 training needs none) to accumulate")

        self._named_p = [(n, p) for n, p in model.named_parameters()
                         if not p.stop_gradient]
        self._named_b = [(n, b) for n, b in model.named_buffers()]
        self._p = [p for _, p in self._named_p]
        self._b = [b for _, b in self._named_b]
        self._p_names = [n for n, _ in self._named_p]

        mesh_ = self._mesh
        self._p_sh = [_partition_spec_for(p, self._stage >= 3, mesh_)
                      for p in self._p]
        self._b_sh = [NamedSharding(mesh_, PartitionSpec()) for _ in self._b]

        self._plan_fused_update()
        rest = self._rest_idx

        # init + place per-param opt state (the non-fused subset) with
        # its shardings
        raw_state = optimizer._fn_init_all(
            [self._p[i]._value for i in rest],
            [self._p_names[i] for i in rest], [self._p[i] for i in rest])
        pp_sh = []
        placed_state = []
        for j, st in zip(rest, raw_state):
            p, psh = self._p[j], self._p_sh[j]
            leaf_sh = {k: _opt_state_sharding(psh, v.shape, self._stage,
                                              mesh_, p._value.shape)
                       for k, v in (st.items() if isinstance(st, dict) else [])}
            if isinstance(st, dict):
                placed_state.append({k: jax.device_put(v, leaf_sh[k])
                                     for k, v in st.items()})
                pp_sh.append(leaf_sh)
            else:
                placed_state.append(st)
                pp_sh.append(NamedSharding(mesh_, PartitionSpec()))

        if self._fused is None:
            self._opt_state = placed_state
            self._s_sh = pp_sh
        else:
            fz_state, fz_sh = self._init_fused_state()
            self._opt_state = {"per_param": placed_state, "fused": fz_state}
            self._s_sh = {"per_param": pp_sh, "fused": fz_sh}
            self._register_fused_sync()

        # place params/buffers
        for p, sh in zip(self._p, self._p_sh):
            p._value = jax.device_put(p._value, sh)
        for b, sh in zip(self._b, self._b_sh):
            b._value = jax.device_put(b._value, sh)

        self._compiled = {}
        self._analysis = {}     # cost_analysis programs for AOT-loaded
        self._comm_by_sig = {}  # per-sig comm accounting (data+model)
        self._apply_compiled = None
        self._grad_state = None
        if self._accum_n > 1:
            self._init_grad_accum()
        self._record_opt_state_gauges()
        self._record_param_gauges()

        # -- telemetry: analytic per-step accounting of the collectives
        # XLA inserts for the declared shardings (the facade in
        # distributed/collective.py accounts explicit SPMD calls; the
        # grad psum / ZeRO-3 gathers / weight-update-sharding
        # scatter+gather of this step are compiler-inserted, so they are
        # accounted here from the param set)
        self._obs = None
        self._obs_boundary_comm = []
        if _obs_enabled():
            dsize = mesh_.shape.get("data", 1)
            comm = []
            if dsize > 1:
                fused_ids = set(self._fused["idx"]) if self._fused else set()
                rest_p = [p for i, p in enumerate(self._p)
                          if i not in fused_ids]
                grad_b = sum(int(np.prod(p._value.shape))
                             * p._value.dtype.itemsize for p in rest_p)
                if self._stage >= 3:
                    # FSDP: params all-gathered at use (fwd + bwd),
                    # grads reduce-scattered
                    comm.append(("all_gather", "data",
                                 2 * len(rest_p), 2 * grad_b))
                    comm.append(("reduce_scatter", "data",
                                 len(rest_p), grad_b))
                elif rest_p:
                    comm.append(("all_reduce", "data",
                                 len(rest_p), grad_b))
                if self._fused is not None:
                    fz = self._fused
                    fb = sum(b.padded_size * np.dtype(m["cdtype"]).itemsize
                             for b, m in zip(fz["bucketer"].buckets,
                                             fz["meta"]))
                    nb = len(fz["bucketer"].buckets)
                    if self._wus:
                        # ZeRO-1/2: reduce-scatter grads, all-gather
                        # the updated flat params — per bucket. Under
                        # grad accumulation the param all-gather runs
                        # ONLY in the boundary apply program, so it is
                        # tagged boundary-only (micro-steps must not
                        # charge phantom gather traffic)
                        comm.append(("reduce_scatter", "data", nb, fb))
                        ag = ("all_gather", "data", nb, fb)
                        comm.append(ag)
                        self._obs_boundary_comm.append(ag)
                    else:
                        comm.append(("all_reduce", "data", nb, fb))
            n_params = sum(int(np.prod(p._value.shape)) for p in self._p)
            dtype = (str(self._p[0]._value.dtype) if self._p
                     else "float32")
            flops_fn = None
            from ...framework.flags import flag_value
            try:
                use_xla_mfu = bool(flag_value("obs_xla_mfu"))
            except KeyError:
                use_xla_mfu = False
            if use_xla_mfu:
                def flops_fn():
                    ca = self._last_cost_analysis()
                    return float((ca or {}).get("flops", 0.0))
            self._obs_use_xla_mfu = use_xla_mfu
            self._obs_flops_fn = flops_fn
            # data-axis entries are batch-independent; the model-axis
            # (TP activation) entries are appended per batch signature
            # in __call__ (_model_axis_comm needs the token count)
            self._obs_base_comm = list(comm)
            self._obs = StepTelemetry(
                n_params=n_params, dtype=dtype,
                n_devices=mesh_.devices.size, comm_per_step=comm,
                flops_fn=flops_fn)

    # ---------------------------------------------- fused weight update --
    def _plan_fused_update(self):
        """Decide which params take the fused flat-bucket update inside
        step_fn (and, with weight_update_sharding, the ZeRO-1 sharded
        variant: reduce-scatter grads over 'data', update only the local
        flat shard, all-gather updated params — arXiv:2004.13336).

        Only params with a fully-replicated partition spec fuse (TP/FSDP-
        sharded params keep the per-param path); the optimizer must be
        one of the fusible kinds with elementwise-expressible
        hyperparameters."""
        from ...framework.flags import flag_value
        from ...optimizer import fused as _fz
        self._fused = None
        self._rest_idx = list(range(len(self._p)))
        try:
            flag_on = bool(flag_value("fused_optimizer"))
        except KeyError:
            flag_on = False
        if not (self._wus or (flag_on and self._stage == 0)):
            return
        if _fz._kind_of(self._opt) is None:
            return
        cand = [i for i, sh in enumerate(self._p_sh)
                if all(s is None for s in (sh.spec or ()))]
        if not cand:
            return
        params = [self._p[i] for i in cand]
        coeffs = _fz.bucket_coeffs(self._opt, params,
                                   [self._p_names[i] for i in cand])
        if coeffs is None or coeffs["wd_dynamic"]:
            # Tensor-valued AdamW wd would bake a stale constant into
            # the compiled step; keep the per-param path for that case
            return
        if not _fz.steps_consistent(self._opt, params):
            # per-param step counters disagree (partial restore): one
            # bucket scalar cannot represent them
            return
        from ...distributed.collective import bucketer_for
        dsize = self._mesh.shape.get("data", 1)
        bucketer = bucketer_for(
            [tuple(p._value.shape) for p in params],
            [np.dtype(p._value.dtype) for p in params],
            bucket_bytes=int(self._rc.grad_bucket_bytes),
            pad_multiple=dsize if self._wus else 1)
        # int8 grad comm only makes sense where the comm pattern is
        # restructured (wus); applying it to a plain fused stage-0
        # update would add quantization noise for zero benefit
        quant = bool(self._rc.quantized_grad_comm) and self._wus
        meta = []
        for b in bucketer.buckets:
            mp = self._opt._mp_active(params[b.idx[0]]._value)
            cdtype = jnp.float32 if mp else params[b.idx[0]]._value.dtype
            meta.append({
                "mp": mp, "cdtype": cdtype,
                "dtype": params[b.idx[0]]._value.dtype,
                "coeffs": _fz.dist_bucket_coeffs(
                    coeffs, b.idx, b.sizes, b.padded_size, cdtype),
            })
        self._fused = {"kind": coeffs["kind"], "idx": cand,
                       "bucketer": bucketer, "meta": meta,
                       "quant": quant,
                       "wd_dynamic": coeffs["wd_dynamic"]}
        fused_set = set(cand)
        self._rest_idx = [i for i in range(len(self._p))
                          if i not in fused_set]

    def _init_fused_state(self):
        """Flat per-bucket optimizer state + shardings. With
        weight_update_sharding the 1-D buffers shard over 'data' — each
        replica holds 1/dsize of the moments (and f32 master weights),
        which is where the ZeRO-1 memory saving comes from."""
        from ...optimizer import fused as _fz
        fz = self._fused
        mesh_ = self._mesh
        params = [self._p[i] for i in fz["idx"]]
        vec_sh = NamedSharding(mesh_, PartitionSpec("data")) if self._wus \
            else NamedSharding(mesh_, PartitionSpec())
        repl = NamedSharding(mesh_, PartitionSpec())
        states, shardings = [], []
        for b, m in zip(fz["bucketer"].buckets, fz["meta"]):
            st = _fz.init_dist_flat_state(
                self._opt, params, b, fz["kind"], m["mp"], m["cdtype"],
                quantized=fz["quant"])
            sh = {k: (repl if getattr(v, "ndim", 0) == 0 else vec_sh)
                  for k, v in st.items()}
            states.append({k: jax.device_put(v, sh[k])
                           for k, v in st.items()})
            shardings.append(sh)
        return states, shardings

    def _register_fused_sync(self):
        """state_dict/checkpoint interop: unflatten the fused flat state
        into the optimizer's per-param accumulators on demand (the same
        _deferred_sync protocol the pipeline engine and the eager fused
        path use)."""
        opt = self._opt
        step_ref = self

        def _sync():
            fz = step_ref._fused
            if fz is None:
                return
            params = [step_ref._p[i] for i in fz["idx"]]
            fused_states = step_ref._opt_state["fused"]
            store_root = opt.__dict__.get("_accums")
            if store_root is None:
                store_root = opt._accumulators
            for b, st in zip(fz["bucketer"].buckets, fused_states):
                for name, flat in st.items():
                    if name == "ef_residual":
                        continue
                    store = store_root.setdefault(name, {})
                    if getattr(flat, "ndim", 0) == 0:
                        for i in b.idx:
                            # copy per param: per-param kernels donate
                            # their step operand
                            store[id(params[i])] = jnp.array(flat)
                        continue
                    for k, i in enumerate(b.idx):
                        off = int(b.offsets[k])
                        store[id(params[i])] = flat[
                            off:off + b.sizes[k]].reshape(b.shapes[k])

        def _invalidate():
            # set_state_dict loaded fresh accumulator values: reseed the
            # fused flat buffers from them, otherwise the next _sync
            # would clobber the restore with pre-restore flat state
            # (same protocol as the pipeline engine / eager FusedPlan)
            if step_ref._fused is None or \
                    not isinstance(step_ref._opt_state, dict):
                return
            states, _ = step_ref._init_fused_state()
            step_ref._opt_state["fused"] = states
        opt._deferred_sync = _sync
        opt._deferred_invalidate = _invalidate

    def _record_opt_state_gauges(self):
        """mem.opt_state_bytes{scope=global|per_replica}: analytic
        optimizer-state footprint. per_replica divides 'data'-sharded
        flat buffers by the axis size — the acceptance signal for
        weight-update sharding. Always computed (footprint()
        consumers); gauge emission gated on the telemetry switch."""
        dsize = self._mesh.shape.get("data", 1)

        def leaf_bytes(leaf, sharded):
            n = int(np.prod(getattr(leaf, "shape", ()) or (1,)))
            nb = n * np.dtype(leaf.dtype).itemsize
            return nb, nb // dsize if sharded else nb

        total = per_replica = 0
        if isinstance(self._opt_state, dict):
            pp, fused = self._opt_state["per_param"], \
                self._opt_state["fused"]
        else:
            pp, fused = self._opt_state, []
        for st in pp:
            for k, v in (st.items() if isinstance(st, dict) else []):
                nb = int(np.prod(v.shape or (1,))) * np.dtype(
                    v.dtype).itemsize
                total += nb
                # per-param leaves count sharded when _opt_state_sharding
                # placed them over 'data' (ZeRO stages)
                try:
                    sharded = "data" in str(getattr(v.sharding, "spec", ""))
                except Exception:
                    sharded = False
                per_replica += nb // (dsize if sharded else 1)
        for st in fused:
            for k, v in st.items():
                nb = int(np.prod(v.shape or (1,))) * np.dtype(
                    v.dtype).itemsize
                total += nb
                per_replica += nb // (dsize if (self._wus and v.ndim) else 1)
        self._opt_state_bytes = {"global": total,
                                 "per_replica": per_replica}
        if not _obs_enabled():
            return
        from ...observability import metrics as _m
        g = _m.gauge("mem.opt_state_bytes", unit="bytes",
                     help="optimizer state footprint")
        g.set(total, scope="global")
        g.set(per_replica, scope="per_replica")

    def _record_param_gauges(self):
        """mem.params_bytes{scope=global|per_replica}: analytic
        parameter footprint from the placed shardings. Under ZeRO-3 the
        'data'-sharded leaves divide per_replica by the data-axis size;
        TP-tagged leaves divide by the model-axis size — the acceptance
        signal for param sharding. The analytic numbers are always
        computed (footprint() consumers don't depend on the telemetry
        switch); only the gauge emission is gated."""
        from ...observability.train_metrics import sharded_bytes
        tot, per = sharded_bytes([p._value for p in self._p]
                                 + [b._value for b in self._b])
        self._params_bytes = {"global": tot, "per_replica": per}
        if not _obs_enabled():
            return
        from ...observability import metrics as _m
        g = _m.gauge("mem.params_bytes", unit="bytes",
                     help="parameter/buffer footprint from placed "
                          "shardings")
        g.set(tot, scope="global")
        g.set(per, scope="per_replica")

    # ------------------------------------------- ZeRO-2 grad shards --
    def _init_grad_accum(self):
        """ZeRO-2: persistent gradient-accumulation state
        (arXiv:2004.13336 stage 2 — grads live reduce-SCATTERED, never
        fully materialized between micro-steps). Fused flat buckets
        shard over 'data' when sharding_stage >= 2: the out-sharding of
        the accumulation sum drives GSPMD to lower the gradient
        reduction as reduce-scatter straight into the per-replica
        shard, the same state-driven formulation the ZeRO-1 update uses
        (see the wus NOTE in apply_update). The per-param rest subset
        accumulates with the param's own sharding (ZeRO-3 params keep
        their 'data' shard; TP/replicated params accumulate in full —
        only the bucketed subset earns the shard)."""
        mesh_ = self._mesh
        repl = NamedSharding(mesh_, PartitionSpec())
        vec = NamedSharding(mesh_, PartitionSpec("data")) \
            if (self._stage >= 2 and self._wus) else repl
        gb, gsh = [], []
        if self._fused is not None:
            for b, m in zip(self._fused["bucketer"].buckets,
                            self._fused["meta"]):
                z = jnp.zeros((b.padded_size,), m["cdtype"])
                gb.append(jax.device_put(z, vec))
                gsh.append(vec)
        rb, rsh = [], []
        for i in self._rest_idx:
            z = jnp.zeros(self._p[i]._value.shape, self._p[i]._value.dtype)
            rb.append(jax.device_put(z, self._p_sh[i]))
            rsh.append(self._p_sh[i])
        self._grad_state = {"fused": gb, "rest": rb}
        self._g_sh = {"fused": gsh, "rest": rsh}
        self._record_grad_gauges()

    def _record_grad_gauges(self):
        """mem.grad_bytes{scope}: footprint of the persistent grad
        accumulators (only exists with grad_accum_steps > 1); ZeRO-2
        divides the bucketed share by the data-axis size."""
        if self._grad_state is None:
            return
        from ...observability.train_metrics import sharded_bytes
        tot, per = sharded_bytes(self._grad_state["fused"]
                                 + self._grad_state["rest"])
        self._grad_bytes = {"global": tot, "per_replica": per}
        if not _obs_enabled():
            return
        from ...observability import metrics as _m
        g = _m.gauge("mem.grad_bytes", unit="bytes",
                     help="persistent grad-accumulator footprint")
        g.set(tot, scope="global")
        g.set(per, scope="per_replica")

    def _model_axis_comm(self, arrays):
        """Analytic per-step model-axis collectives for the TP-tagged
        params (the activation all-reduces GSPMD inserts for the
        mp_layers sharding constraints): one fwd all-reduce per
        row-parallel weight (output constrained replicated after a
        'model'-contracted matmul) and one bwd all-reduce per
        column-parallel weight (dgrad of a replicated input). Bytes
        are activation payloads at this batch signature."""
        msize = self._mesh.shape.get("model", 1)
        if msize <= 1:
            return []
        toks = batch_tokens(arrays)
        fwd_c = fwd_b = bwd_c = bwd_b = 0
        for p in self._p:
            spec = tuple(getattr(p, "_partition_spec", ()) or ())
            v = p._value
            if "model" not in spec or v.ndim < 2:
                continue
            item = v.dtype.itemsize
            if spec[0] == "model":
                # row-parallel / vocab-parallel weight [in(model), out]:
                # fwd output all-reduce of [toks, out]
                fwd_c += 1
                fwd_b += toks * int(v.shape[-1]) * item
            elif "model" in spec[1:]:
                # column-parallel weight [in, out(model)]: bwd dgrad
                # all-reduce of [toks, in]
                bwd_c += 1
                bwd_b += toks * int(v.shape[0]) * item
        out = []
        if fwd_c:
            out.append(("all_reduce", "model", fwd_c, fwd_b))
        if bwd_c:
            out.append(("all_reduce", "model", bwd_c, bwd_b))
        return out

    def _refresh_comm_accounting(self, obs, sig, arrays,
                                 boundary=True):
        """Point the telemetry at THIS signature's comm entries (base
        data-axis list + token-count-dependent model-axis activation
        all-reduces) on EVERY call — alternating batch shapes, and
        warm-started steps that never enter the compile branch, must
        each charge their own per-axis bytes. ``boundary=False`` is
        the accum micro-step view: boundary-only entries (the ZeRO-1/2
        param all-gather, which lives in the apply program) are
        excluded so micro-steps don't charge phantom gather bytes."""
        key = (sig, boundary)
        entries = self._comm_by_sig.get(key)
        if entries is None:
            base = list(getattr(self, "_obs_base_comm", []))
            if not boundary:
                skip = {id(e) for e in self._obs_boundary_comm}
                base = [e for e in base if id(e) not in skip]
            entries = self._comm_by_sig[key] = (
                base + self._model_axis_comm(arrays))
        obs.comm_per_step = entries

    def _last_cost_analysis(self):
        batch = getattr(self, "_obs_last_batch", None)
        return self.cost_analysis(*batch) if batch else None

    def _apply_update_closure(self):
        """The optimizer-update trace shared by the one-shot step
        (_build) and the ZeRO-2 apply program (_build_apply):
        per-param path for the rest subset, fused flat buckets
        (optionally 'data'-sharded, ZeRO-1/2) for the fused subset.

        ``flat_grads``: pre-flattened per-bucket gradients (the ZeRO-2
        persistent shards, already averaged) — when given, the
        concatenate-from-per-param step is skipped and ``grads`` is
        only consulted for the rest subset."""
        opt = self._opt
        fz = self._fused
        rest = self._rest_idx
        p_names = self._p_names
        p_tensors = self._p
        wus = self._wus
        repl = NamedSharding(self._mesh, PartitionSpec())

        def apply_update(p_vals, grads, opt_state, lr, flat_grads=None):
            if fz is None:
                return opt._fn_apply_all(list(p_vals), grads, opt_state,
                                         lr, p_names, p_tensors)
            from ...optimizer.fused import fused_bucket_update
            from ...distributed.collective import fake_quantized_grad
            new_p = list(p_vals)
            rp, rs = opt._fn_apply_all(
                [p_vals[i] for i in rest], [grads[i] for i in rest],
                opt_state["per_param"], lr,
                [p_names[i] for i in rest], [p_tensors[i] for i in rest])
            for j, i in enumerate(rest):
                new_p[i] = rp[j]
            params_idx = fz["idx"]
            new_fused = []
            for bi, (b, m, st) in enumerate(zip(fz["bucketer"].buckets,
                                                fz["meta"],
                                                opt_state["fused"])):
                cd = m["cdtype"]
                if flat_grads is not None:
                    flat_g = flat_grads[bi].astype(cd)
                else:
                    parts = [jnp.ravel(grads[params_idx[i]]).astype(cd)
                             for i in b.idx]
                    flat_g = jnp.concatenate(parts) if len(parts) > 1 \
                        else parts[0]
                    if b.padded_size != b.size:
                        flat_g = jnp.pad(flat_g,
                                         (0, b.padded_size - b.size))
                # NOTE (wus): no explicit sharding constraint on flat_g /
                # flat_p. The 'data'-sharded in/out shardings of the flat
                # optimizer state drive GSPMD to shard the whole update
                # chain (the arXiv:2004.13336 "automatic" formulation) —
                # the gradient reduction feeding it lowers as
                # reduce-scatter (or all-reduce + local slice on backends
                # without the reduce-scatter-creation pass, e.g. CPU).
                # Constraining the raw unreduced gradient directly was
                # observed to corrupt partial-sum accounting on
                # multi-axis meshes (model-axis grads double-reduced).
                st2 = dict(st)
                if fz["quant"]:
                    # error-feedback quantize-dequantize of the reduced
                    # gradient (convergence model of the int8 collective;
                    # the wire-level path is collective.quantized_*)
                    flat_g, st2["ef_residual"] = fake_quantized_grad(
                        flat_g, st["ef_residual"])
                if m["mp"]:
                    flat_p = st["master_weight"]
                else:
                    pparts = [jnp.ravel(p_vals[params_idx[i]]).astype(cd)
                              for i in b.idx]
                    flat_p = jnp.concatenate(pparts) if len(pparts) > 1 \
                        else pparts[0]
                    if b.padded_size != b.size:
                        flat_p = jnp.pad(flat_p,
                                         (0, b.padded_size - b.size))
                coeffs = dict(m["coeffs"])
                inner = {k: v for k, v in st2.items()
                         if k not in ("master_weight", "ef_residual")}
                p2, st_out = fused_bucket_update(
                    fz["kind"], flat_p, flat_g, inner, lr.astype(cd),
                    coeffs, opt)
                if m["mp"]:
                    st_out["master_weight"] = p2
                if fz["quant"]:
                    st_out["ef_residual"] = st2["ef_residual"]
                new_fused.append(st_out)
                if wus:
                    # updated flat params live sharded; gathering them
                    # back to replicated is the ZeRO-1 all-gather
                    p2 = jax.lax.with_sharding_constraint(p2, repl)
                for k, i in enumerate(b.idx):
                    off = int(b.offsets[k])
                    seg = jax.lax.slice_in_dim(p2, off, off + b.sizes[k])
                    new_p[params_idx[i]] = seg.reshape(
                        b.shapes[k]).astype(m["dtype"])
            return new_p, {"per_param": rs, "fused": new_fused}
        return apply_update

    def _grad_closure(self):
        """Forward+backward trace (no scaler) shared by the ZeRO-2
        accumulation program: returns (loss, new_buffers, new_key,
        grads) for one micro-batch."""
        model = self._model
        loss_fn = self._loss_fn
        p_tensors = self._p
        b_tensors = self._b
        n_in = self._n_in

        def compute(p_vals, b_vals, rng_key, batch):
            from ...jit.bridge import bound_state
            model_in = batch[:n_in]
            labels = batch[n_in:]

            def loss_of(pv):
                with bound_state(p_tensors, pv, b_tensors, b_vals,
                                 rng_key) as gen:
                    outs = model(*[Tensor(a) for a in model_in])
                    outs = outs if isinstance(outs, tuple) else (outs,)
                    loss = loss_fn(*outs, *[Tensor(a) for a in labels])
                    new_b = [t._value for t in b_tensors]
                    return loss._value, (loss._value, new_b, gen._key)

            (_, (loss_val, new_b, new_key)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(list(p_vals))
            return loss_val, new_b, new_key, grads
        return compute

    def _build_accum(self, batch_sh):
        """ZeRO-2 micro-step program: fwd+bwd, then ADD the gradients
        into the persistent accumulators (flat buckets 'data'-sharded —
        GSPMD lowers the reduction feeding a sharded accumulator as
        reduce-scatter, so the full gradient never materializes).
        Params/opt-state untouched; buffers advance per micro-batch."""
        mesh_ = self._mesh
        repl = NamedSharding(mesh_, PartitionSpec())
        compute = self._grad_closure()
        fz = self._fused
        rest = self._rest_idx
        obs = self._obs if _obs_enabled() else None
        from ...framework.flags import flag_value
        guard = bool(flag_value("anomaly_guard"))  # read at trace time

        def accum_fn(p_vals, b_vals, gbufs, rbufs, rng_key, batch):
            loss_val, new_b, _, grads = compute(p_vals, b_vals, rng_key,
                                                batch)
            if obs is not None:
                obs.grad_norm_callback(grads)  # async host record
            ok = jnp.isfinite(loss_val) if guard else None

            def gate(g):
                # anomaly guard under accumulation: a NaN/Inf micro-loss
                # contributes ZERO gradient (the update still runs at the
                # accumulation boundary on the healthy micro-steps)
                return g if ok is None else jnp.where(ok, g,
                                                      jnp.zeros_like(g))

            new_g = []
            if fz is not None:
                for b, m, acc in zip(fz["bucketer"].buckets, fz["meta"],
                                     gbufs):
                    parts = [jnp.ravel(grads[fz["idx"][i]]).astype(
                        m["cdtype"]) for i in b.idx]
                    flat_g = jnp.concatenate(parts) if len(parts) > 1 \
                        else parts[0]
                    if b.padded_size != b.size:
                        flat_g = jnp.pad(flat_g,
                                         (0, b.padded_size - b.size))
                    new_g.append(acc + gate(flat_g))
            new_r = [acc + gate(grads[i]) for acc, i in zip(rbufs, rest)]
            if guard:
                new_b = [jnp.where(ok, n, o)
                         for o, n in zip(b_vals, new_b)]
            return loss_val, new_b, new_g, new_r

        donate = (1, 2, 3) if self._donate else ()
        jitted = jax.jit(
            accum_fn,
            in_shardings=(self._p_sh, self._b_sh, self._g_sh["fused"],
                          self._g_sh["rest"], None, batch_sh),
            out_shardings=(repl, self._b_sh, self._g_sh["fused"],
                           self._g_sh["rest"]),
            donate_argnums=donate)

        def run(*args):
            with _step_scope(mesh_):
                return jitted(*args)
        run._jitted = jitted
        return run

    def _build_apply(self):
        """ZeRO-2 boundary program: consume the accumulated grad shards
        (averaged over grad_accum_steps, clipped jointly), run the
        optimizer update, return ZEROED accumulators. Batch-shape
        independent — compiled once per step object."""
        mesh_ = self._mesh
        grad_clip = self._opt._grad_clip
        fz = self._fused
        rest = self._rest_idx
        n_p = len(self._p)
        inv_n = 1.0 / float(self._accum_n)
        apply_update = self._apply_update_closure()

        def apply_fn(p_vals, opt_state, lr, gbufs, rbufs):
            flats = [g * inv_n for g in gbufs]
            rgrads = [g * inv_n for g in rbufs]
            # joint global-norm clip across the flat buckets + the rest
            # subset (bucket padding is zero, so the norm is exact)
            clipped = _clip_grads_functional(flats + rgrads, grad_clip)
            flats, rgrads = clipped[:len(flats)], clipped[len(flats):]
            grads = [None] * n_p
            for j, i in enumerate(rest):
                grads[i] = rgrads[j]
            new_p, new_state = apply_update(
                list(p_vals), grads, opt_state, lr,
                flat_grads=flats if fz is not None else None)
            return (new_p, new_state,
                    [jnp.zeros_like(g) for g in gbufs],
                    [jnp.zeros_like(g) for g in rbufs])

        donate = (0, 1, 3, 4) if self._donate else ()
        jitted = jax.jit(
            apply_fn,
            in_shardings=(self._p_sh, self._s_sh, None,
                          self._g_sh["fused"], self._g_sh["rest"]),
            out_shardings=(self._p_sh, self._s_sh,
                           self._g_sh["fused"], self._g_sh["rest"]),
            donate_argnums=donate)

        def run(*args):
            with _step_scope(mesh_):
                return jitted(*args)
        run._jitted = jitted
        return run

    # ------------------------------------------------------------------
    def _batch_shardings(self, arrays):
        mesh_ = self._mesh
        if self._batch_specs is not None:
            return [NamedSharding(mesh_, s) for s in self._batch_specs]
        out = []
        for a in arrays:
            spec = [None] * a.ndim
            if a.ndim >= 1 and mesh_.shape["data"] > 1 \
                    and a.shape[0] % mesh_.shape["data"] == 0:
                spec[0] = "data"
            out.append(NamedSharding(mesh_, PartitionSpec(*spec)))
        return out

    def _build(self, batch_sh):
        model = self._model
        loss_fn = self._loss_fn
        opt = self._opt
        p_tensors = self._p
        b_tensors = self._b
        p_names = self._p_names
        n_in = self._n_in
        grad_clip = opt._grad_clip
        mesh_ = self._mesh
        repl = NamedSharding(mesh_, PartitionSpec())

        scaler = self._scaler
        obs = self._obs if _obs_enabled() else None
        fz = self._fused
        rest = self._rest_idx
        wus = self._wus
        from ...framework.flags import flag_value
        guard = bool(flag_value("anomaly_guard"))  # read at trace time

        apply_update = self._apply_update_closure()

        def step_fn(p_vals, b_vals, opt_state, rng_key, lr, batch,
                    scaler_st):
            from ...jit.bridge import bound_state
            model_in = batch[:n_in]
            labels = batch[n_in:]
            scale = scaler_st[0] if scaler is not None else None

            def loss_of(pv):
                with bound_state(p_tensors, pv, b_tensors, b_vals,
                                 rng_key) as gen:
                    outs = model(*[Tensor(a) for a in model_in])
                    outs = outs if isinstance(outs, tuple) else (outs,)
                    loss = loss_fn(*outs, *[Tensor(a) for a in labels])
                    new_b = [t._value for t in b_tensors]
                    lv = loss._value
                    if scale is not None:
                        # multiply in f32: casting the scale DOWN to an
                        # f16 loss dtype overflows for scale > 65504
                        lv = lv.astype(jnp.float32) * scale
                    return lv, (loss._value, new_b, gen._key)

            (_, (loss_val, new_b, new_key)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(list(p_vals))
            if scaler is not None:
                from ...amp.grad_scaler import (compiled_unscale,
                                                compiled_select_and_adapt)
                grads, found_inf = compiled_unscale(scale, grads)
            if obs is not None:
                obs.grad_norm_callback(grads)  # async host record, no sync
            grads = _clip_grads_functional(grads, grad_clip)
            new_p, new_state = apply_update(list(p_vals), grads, opt_state,
                                            lr)
            if scaler is not None:
                new_p, new_state, scaler_st = compiled_select_and_adapt(
                    scaler, found_inf, new_p, list(p_vals), new_state,
                    opt_state, scaler_st)
            if guard:
                # anomaly guard (FLAGS_anomaly_guard): a NaN/Inf loss
                # keeps pre-step params/buffers/opt-state — fused
                # scalar-predicate selects, no host sync (GSPMD shards
                # the selects like the state they gate)
                bad = ~jnp.isfinite(loss_val)
                new_p = [jnp.where(bad, o, n)
                         for o, n in zip(p_vals, new_p)]
                new_b = [jnp.where(bad, o, n)
                         for o, n in zip(b_vals, new_b)]
                new_state = jax.tree_util.tree_map(
                    lambda o, n: jnp.where(bad, o, n), opt_state,
                    new_state)
            return loss_val, new_p, new_b, new_state, new_key, scaler_st

        donate = (0, 1, 2) if self._donate else ()
        jitted = jax.jit(
            step_fn,
            in_shardings=(self._p_sh, self._b_sh, self._s_sh, None, None,
                          batch_sh, None),
            out_shardings=(repl, self._p_sh, self._b_sh, self._s_sh, None,
                           None),
            donate_argnums=donate)

        def run(p_vals, b_vals, opt_state, key, lr, arrays, scaler_st):
            with _step_scope(mesh_):
                return jitted(p_vals, b_vals, opt_state, key, lr, arrays,
                              scaler_st)
        run._jitted = jitted  # for cost_analysis (lower without running)
        return run

    @property
    def opt_state(self):
        return self._opt_state

    def lower(self, *batch):
        """Lower the whole step (fwd+bwd+update) for this batch
        signature without compiling or running it: ``.as_text()`` shows
        which kernels and shardings the program carries,
        ``.cost_analysis()`` XLA's cost model, ``.compile()`` the
        memory analysis. Consumes no donated buffer and leaves the
        global RNG alone."""
        arrays = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
        if sig not in self._compiled:
            self._compiled[sig] = self._build(self._batch_shardings(arrays))
        run = self._compiled[sig]
        if getattr(run, "_jitted", None) is None:
            # AOT-loaded executable (hybrid/aot.load_step_bundle): no
            # lowering attached. Trace an analysis-only twin — never
            # installed into _compiled, so the warm-started executable
            # keeps serving the hot path
            if sig not in self._analysis:
                self._analysis[sig] = self._build(
                    self._batch_shardings(arrays))
            run = self._analysis[sig]
        from ...amp.grad_scaler import scaler_state_in
        sc_in = (scaler_state_in(self._scaler)
                 if self._scaler is not None else ())
        # fixed key, NOT default_generator().split(): lowering only needs
        # the key's type, and advancing the global RNG from an analysis
        # call (e.g. the telemetry MFU probe) would silently change the
        # training trajectory (same stance as PipelineTrainStep.
        # memory_analysis)
        with _step_scope(self._mesh):
            return run._jitted.lower(
                [p._value for p in self._p], [b._value for b in self._b],
                self._opt_state, jax.random.key(0),
                self._opt._lr_operand(), arrays,
                sc_in)

    def cost_analysis(self, *batch):
        """XLA's cost model for the whole hybrid-parallel step
        (fwd+bwd+update) at this batch signature — same contract as
        TrainStep.cost_analysis: reads the LOWERED module (no backend
        compile/execute)."""
        ca = self.lower(*batch).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return ca

    def params_with_grad(self):
        """One bool per trainable parameter: whether its Adam first
        moment is non-zero, i.e. whether a gradient has reached it.
        Reads the step's own state layout (per-parameter dicts, and
        flat fused buckets sliced by each parameter's segment), so a
        caller need not know it."""
        fused = self._opt_state["fused"] \
            if isinstance(self._opt_state, dict) else []
        per_param = self._opt_state["per_param"] \
            if isinstance(self._opt_state, dict) else self._opt_state
        flags = {}
        for i, st in zip(self._rest_idx, per_param):
            flags[i] = jnp.any(st["moment1"] != 0)
        if fused:
            idx = self._fused["idx"]
            for b, st in zip(self._fused["bucketer"].buckets, fused):
                for k, j in enumerate(b.idx):
                    off = int(b.offsets[k])
                    seg = jax.lax.slice_in_dim(st["moment1"], off,
                                               off + b.sizes[k])
                    flags[idx[j]] = jnp.any(seg != 0)
        got = jax.device_get([flags[i] for i in range(len(self._p))])
        return [bool(v) for v in got]

    def __call__(self, *batch):
        if self._accum_n > 1:
            return self._call_accum(*batch)
        obs = self._obs if (self._obs is not None and _obs_enabled()) \
            else None
        if obs is not None:
            obs.step_start()
        arrays = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
        if obs is not None:
            self._refresh_comm_accounting(obs, sig, arrays)
        if sig not in self._compiled:
            # a (re)trace is the load-bearing event worth a span: the
            # retrace that wedges or thrashes shows up attributed to its
            # batch signature (nests under the Trainer's dispatch span)
            with _tracing.span("dist.compile", batch=str(sig),
                               stage=self._stage, wus=self._wus):
                self._compiled[sig] = self._build(
                    self._batch_shardings(arrays))
            if obs is not None and self._obs_use_xla_mfu:
                # the batch is pinned ONLY until the one-shot MFU probe
                # consumes it in this step's step_end (cleared below)
                self._obs_last_batch = batch
                obs.reset_flops(self._obs_flops_fn)  # new shape, new MFU
        gen = default_generator()
        key_in = gen.split()
        lr = self._opt._lr_operand()
        from ...amp.grad_scaler import scaler_state_in, scaler_state_out
        sc = self._scaler
        sc_in = scaler_state_in(sc) if sc is not None else ()
        loss, new_p, new_b, new_state, _, sc_out = self._compiled[sig](
            [p._value for p in self._p], [b._value for b in self._b],
            self._opt_state, key_in, lr, arrays, sc_in)
        if sc is not None:
            scaler_state_out(sc, sc_out)
        for t, v in zip(self._p, new_p):
            t._value = v
        for t, v in zip(self._b, new_b):
            t._value = v
        self._opt_state = new_state
        if isinstance(new_state, dict):
            # per-param subset syncs eagerly (no device work — the state
            # leaves are handed over as-is); the fused flat buffers sync
            # lazily via the optimizer's _deferred_sync
            self._opt._fn_sync_to_accumulators(
                [self._p[i] for i in self._rest_idx],
                new_state["per_param"])
        else:
            self._opt._fn_sync_to_accumulators(self._p, new_state)
        if obs is not None:
            obs.step_end(batch_tokens(arrays))  # runs the MFU probe once
            self._obs_last_batch = None
        return Tensor(loss)

    def _call_accum(self, *batch):
        """ZeRO-2 stepping: every call runs the accumulation micro-step
        (grads ADDED into the persistent 'data'-sharded accumulators);
        every ``grad_accum_steps``-th call also runs the apply program
        (optimizer update from the accumulated shards, accumulators
        zeroed). Returns the micro-batch loss."""
        obs = self._obs if (self._obs is not None and _obs_enabled()) \
            else None
        if obs is not None:
            obs.step_start()
        arrays = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
        sig = ("accum",) + tuple((tuple(a.shape), str(a.dtype))
                                 for a in arrays)
        if obs is not None:
            # the apply program (and its param all-gather) runs only on
            # the accumulation-boundary call
            self._refresh_comm_accounting(
                obs, sig, arrays,
                boundary=self._micro + 1 >= self._accum_n)
        if sig not in self._compiled:
            with _tracing.span("dist.compile", batch=str(sig),
                               stage=self._stage, wus=self._wus,
                               mode="accum"):
                self._compiled[sig] = self._build_accum(
                    self._batch_shardings(arrays))
        gen = default_generator()
        key_in = gen.split()
        gs = self._grad_state
        loss, new_b, gf, gr = self._compiled[sig](
            [p._value for p in self._p], [b._value for b in self._b],
            gs["fused"], gs["rest"], key_in, arrays)
        for t, v in zip(self._b, new_b):
            t._value = v
        gs["fused"], gs["rest"] = list(gf), list(gr)
        self._micro += 1
        if self._micro >= self._accum_n:
            self._micro = 0
            if self._apply_compiled is None:
                with _tracing.span("dist.compile", stage=self._stage,
                                   wus=self._wus, mode="apply"):
                    self._apply_compiled = self._build_apply()
            lr = self._opt._lr_operand()
            new_p, new_state, zg, zr = self._apply_compiled(
                [p._value for p in self._p], self._opt_state, lr,
                gs["fused"], gs["rest"])
            for t, v in zip(self._p, new_p):
                t._value = v
            self._opt_state = new_state
            gs["fused"], gs["rest"] = list(zg), list(zr)
            if isinstance(new_state, dict):
                self._opt._fn_sync_to_accumulators(
                    [self._p[i] for i in self._rest_idx],
                    new_state["per_param"])
            else:
                self._opt._fn_sync_to_accumulators(self._p, new_state)
        if obs is not None:
            obs.step_end(batch_tokens(arrays))
        return Tensor(loss)

"""HybridTrainStep — the one front door to hybrid-parallel training.

A :class:`~.plan.HybridParallelPlan` names the composition; this engine
builds the mesh, picks the executing step class, and owns the
cross-cutting concerns the step classes don't:

- **routing**: pp degree > 1 → the pipeline engine
  (meta_parallel.PipelineTrainStep, schedule from the plan — including
  the explicit 1F1B); otherwise the GSPMD step
  (fleet.dist_step.DistTrainStep) with the plan's ZeRO stage and
  persistent grad shards.
- **footprint telemetry**: ``mem.params_bytes{scope}`` /
  ``mem.opt_state_bytes{scope}`` come from the step classes; the
  engine re-exports them plus the plan description so a test can
  assert the sharding actually bought the memory it claims — FROM the
  JSONL sink, not from trust.
- **deployment**: ``save_bundle``/``load_bundle`` serialize the
  compiled step through the PR-8 engine-bundle format with the mesh
  topology joined into the fingerprint (hybrid/aot.py) — a bundle
  partitioned for ``data=4,model=2`` must never warm-start a
  ``data=8`` run.
"""
from __future__ import annotations

from typing import Callable, Optional

from ....observability import enabled as _obs_enabled
from ...mesh import mesh_scope, set_mesh
from .plan import HybridParallelPlan

__all__ = ["HybridTrainStep"]


class HybridTrainStep:
    """Plan-driven hybrid train step (ZeRO x TP x PP composition).

    ``plan`` or ``mesh_spec`` (e.g. ``"data=4,model=2"``) selects the
    topology. The mesh is built from the plan unless an explicit
    ``mesh`` is passed (whose axis sizes must match the plan —
    inferred ``-1`` degrees are adopted from it). NOTE: TP-tagged
    layers read the process mesh at construction, and the model is a
    ctor argument here — so the usual pattern is
    ``set_mesh(plan.build_mesh())`` BEFORE building the model (as in
    docs/TRAINING.md); ``install_mesh=True`` additionally installs
    this engine's mesh as the process mesh for eager work AFTER
    construction.
    """

    def __init__(self, model, optimizer, loss_fn: Callable,
                 plan: Optional[HybridParallelPlan] = None,
                 mesh_spec: Optional[str] = None, mesh=None,
                 runtime_config=None, scaler=None,
                 n_model_inputs: int = 1, donate_state: bool = True,
                 install_mesh: bool = False):
        if plan is None:
            plan = HybridParallelPlan.from_spec(
                mesh_spec or "", runtime_config=runtime_config)
        elif mesh_spec is not None:
            raise ValueError("pass plan OR mesh_spec, not both")
        self.plan = plan
        if mesh is not None:
            # resolve inferred -1 degrees / reject mismatched meshes,
            # so topology()/fingerprint() always name the real layout
            plan.adopt_mesh(mesh)
            self._mesh = mesh
        else:
            self._mesh = plan.build_mesh()
        if install_mesh:
            set_mesh(self._mesh)
        if plan.pp > 1:
            from ..meta_parallel.pipeline_parallel import PipelineTrainStep
            if plan.grad_accum_steps > 1:
                raise NotImplementedError(
                    "grad_accum_steps under pipeline parallelism: the "
                    "schedule's microbatching IS the accumulation — "
                    "raise num_microbatches instead")
            if n_model_inputs != 1:
                raise NotImplementedError(
                    "the pipeline schedule feeds exactly ONE tensor "
                    "through the stages (batch[0]); fold extra model "
                    "inputs (masks, position ids) into the preamble's "
                    "input or use a data=/model=-only plan with "
                    "n_model_inputs")
            self._inner = PipelineTrainStep(
                model, optimizer, loss_fn,
                num_microbatches=plan.num_microbatches,
                mesh=self._mesh, zero_stage=plan.zero_stage,
                schedule_mode=plan.schedule, scaler=scaler,
                donate_state=donate_state)
        else:
            from ..dist_step import DistTrainStep
            self._inner = DistTrainStep(
                model, optimizer, loss_fn,
                n_model_inputs=n_model_inputs,
                sharding_stage=plan.zero_stage, mesh=self._mesh,
                scaler=scaler, donate_state=donate_state,
                runtime_config=runtime_config,
                grad_accum_steps=plan.grad_accum_steps)
        self._model = model
        if _obs_enabled():
            from ....observability import metrics as _m
            from ....observability.runtime import set_identity
            _m.gauge("train.hybrid.zero_stage").set(plan.zero_stage)
            _m.gauge("train.hybrid.world_size").set(plan.world_size())
            # fleet identity: rank files record the mesh layout they
            # ran under (docs/OBSERVABILITY.md "Fleet view")
            set_identity(topology=plan.topology())

    # ------------------------------------------------------------------
    @property
    def mesh(self):
        return self._mesh

    @property
    def inner(self):
        """The executing step object (DistTrainStep or
        PipelineTrainStep) — footprint dicts (``_params_bytes``,
        ``_opt_state_bytes``) and ``opt_state`` live there."""
        return self._inner

    def footprint(self) -> dict:
        """The analytic memory story (same numbers as the
        ``mem.*_bytes`` gauges): what sharding bought, per scope."""
        out = {}
        for k in ("_params_bytes", "_opt_state_bytes", "_grad_bytes"):
            v = getattr(self._inner, k, None)
            if v:
                out[k.strip("_")] = dict(v)
        return out

    def __call__(self, *batch):
        with mesh_scope(self._mesh):
            return self._inner(*batch)

    def lower(self, *batch):
        """The executing step lowered for ``batch``'s signature, not
        compiled and not run (the GSPMD step's ``DistTrainStep.lower``;
        the pipeline engine has no single program to lower)."""
        lower = getattr(self._inner, "lower", None)
        if lower is None:
            raise NotImplementedError(
                "lower() is the GSPMD step's: the pipeline engine has "
                "no single lowered program — use a data=/model=-only "
                "plan (pp=1) instead, or PipelineTrainStep."
                "memory_analysis for the pipeline's own analysis")
        return lower(*batch)

    # --------------------------------------------------------- deploy --
    def save_bundle(self, path: str, *batch):
        """Serialize this step's compiled executable for ``batch``'s
        signature into a PR-8 engine bundle whose fingerprint includes
        the mesh topology (hybrid/aot.py)."""
        from .aot import save_step_bundle
        return save_step_bundle(self, path, *batch)

    def load_bundle(self, path: str, *batch):
        """Warm-start: install the bundle's executable for ``batch``'s
        signature instead of compiling. Raises
        :class:`~....inference.aot.bundle.BundleInvalid` on any
        fingerprint/topology/model mismatch."""
        from .aot import load_step_bundle
        return load_step_bundle(self, path, *batch)

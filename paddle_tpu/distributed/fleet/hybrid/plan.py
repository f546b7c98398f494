"""Hybrid-parallel planning: one declarative object that names the
whole composition — mesh axes, ZeRO stage, pipeline schedule, overlap
knobs — and renders it three ways:

- a ``jax.sharding.Mesh`` (``build_mesh``) the step classes execute on;
- a canonical topology string (``topology()``) humans and tools pass
  around (``HybridParallelPlan.from_spec("data=4,model=2")``);
- a fingerprint dict (``fingerprint()``) that JOINS the AOT bundle
  identity (hybrid/aot.py): a serialized train step is only valid on
  the exact mesh topology it was partitioned for, so topology drift
  must invalidate the bundle the same way a jaxlib drift does.

Reference parity: fleet/base/topology.py builds orthogonal process
groups from a degree list (dp/mp/pp/sharding/sep); here the same
degrees are named mesh axes (distributed/mesh.py AXES) and the ZeRO
stage is a sharding decision, not a separate group.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence

from ...mesh import AXES, build_mesh as _build_mesh

__all__ = ["HybridParallelPlan", "parse_mesh_spec"]

# spec-string aliases (the reference's degree names)
_AXIS_ALIASES = {
    "dp": "data", "data": "data",
    "pp": "stage", "stage": "stage", "pipeline": "stage",
    "cp": "context", "context": "context", "sep": "context",
    "ep": "expert", "expert": "expert",
    "mp": "model", "model": "model", "tp": "model",
}

_SCHEDULES = ("1F1B", "1F1B-explicit", "F-then-B", "VPP")


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """``"data=4,model=2"`` → ``{"data": 4, "model": 2}``. Axis names
    accept the reference's aliases (dp/mp/pp/cp/ep and tp/sep); a
    single ``-1`` degree is inferred from the device count at
    ``build_mesh`` time."""
    out: Dict[str, int] = {}
    for part in (spec or "").replace(";", ",").split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"mesh spec entry {part!r} is not axis=degree "
                "(e.g. 'data=4,model=2')")
        name, _, deg = part.partition("=")
        axis = _AXIS_ALIASES.get(name.strip().lower())
        if axis is None:
            raise ValueError(
                f"unknown mesh axis {name.strip()!r}; expected one of "
                f"{sorted(set(_AXIS_ALIASES))}")
        if axis in out:
            raise ValueError(f"duplicate degree for axis {axis!r}")
        out[axis] = int(deg)
    return out


@dataclass
class HybridParallelPlan:
    """The full parallelism decision for one training run."""

    degrees: Dict[str, int] = field(default_factory=dict)
    zero_stage: int = 0
    schedule: str = "1F1B"          # pipeline schedule (pp > 1)
    num_microbatches: int = 1
    grad_accum_steps: int = 1       # >1 with zero_stage>=2: grad shards
    overlap: bool = True            # bucketed grad comm (T3 pipelining)

    def __post_init__(self):
        degs = {a: 1 for a in AXES}
        for k, v in (self.degrees or {}).items():
            if k not in degs:
                raise ValueError(f"unknown mesh axis {k!r}")
            degs[k] = int(v)
        if sum(1 for v in degs.values() if v == -1) > 1:
            raise ValueError("at most one mesh degree may be -1")
        bad = {a: v for a, v in degs.items() if v < 1 and v != -1}
        if bad:
            raise ValueError(
                f"mesh degrees must be >= 1 (or a single -1 to infer "
                f"from the device count), got {bad}")
        self.degrees = degs
        if self.zero_stage not in (0, 1, 2, 3):
            raise ValueError(f"zero_stage must be 0..3, got "
                             f"{self.zero_stage!r}")
        if self.schedule not in _SCHEDULES:
            raise ValueError(
                f"unknown schedule {self.schedule!r}; expected one of "
                f"{_SCHEDULES}")
        if self.num_microbatches < 1 or self.grad_accum_steps < 1:
            raise ValueError("num_microbatches/grad_accum_steps must "
                             "be >= 1")

    # ------------------------------------------------------------------
    @classmethod
    def from_spec(cls, spec: str, *, zero_stage: Optional[int] = None,
                  runtime_config=None, **kw) -> "HybridParallelPlan":
        """Build a plan from a topology string. ``zero_stage`` falls
        back to the RuntimeConfig knob (the autotune-proposed value)
        when not pinned explicitly."""
        if zero_stage is None:
            if runtime_config is None:
                from ....framework.runtime_config import RuntimeConfig
                runtime_config = RuntimeConfig.from_flags()
            zero_stage = int(getattr(runtime_config, "zero_stage", 0)
                             or 0)
        return cls(degrees=parse_mesh_spec(spec), zero_stage=zero_stage,
                   **kw)

    # ------------------------------------------------------------------
    @property
    def dp(self) -> int:
        return self.degrees["data"]

    @property
    def pp(self) -> int:
        return self.degrees["stage"]

    @property
    def mp(self) -> int:
        return self.degrees["model"]

    def _require_resolved(self, what: str):
        """An inferred (-1) degree is only known once a mesh exists;
        fingerprinting an unresolved plan would let topologies that
        differ only in the inferred axis collide (the exact drift the
        AOT `topology` invalidation exists to catch)."""
        if any(v == -1 for v in self.degrees.values()):
            raise ValueError(
                f"{what} needs concrete mesh degrees, but an inferred "
                f"-1 degree is unresolved ({self.degrees}) — call "
                "build_mesh() (or construct the HybridTrainStep, which "
                "adopts the mesh's sizes) first")

    def adopt_mesh(self, mesh) -> "HybridParallelPlan":
        """Resolve inferred (-1) degrees from a concrete mesh and
        verify every pinned degree matches it — a plan claiming
        data=4 over a data=8 mesh is a caller bug, not a layout."""
        sizes = dict(mesh.shape)
        for a in AXES:
            got = int(sizes.get(a, 1))
            if self.degrees[a] == -1:
                self.degrees[a] = got
            elif self.degrees[a] != got:
                raise ValueError(
                    f"plan degree {a}={self.degrees[a]} does not match "
                    f"the mesh ({a}={got}); build the mesh from the "
                    "plan (plan.build_mesh()) or fix the spec")
        return self

    def world_size(self) -> int:
        self._require_resolved("world_size()")
        n = 1
        for v in self.degrees.values():
            n *= max(int(v), 1)
        return n

    def topology(self) -> str:
        """Canonical topology string: axes in mesh order, degree-1 axes
        omitted (``"replicated"`` when every axis is 1). This string —
        not the raw user spec — joins the AOT fingerprint."""
        self._require_resolved("topology()")
        parts = [f"{a}={self.degrees[a]}" for a in AXES
                 if self.degrees[a] > 1]
        return ",".join(parts) if parts else "replicated"

    def fingerprint(self) -> Dict:
        """What a serialized hybrid train step's validity depends on
        beyond the model: the mesh partitioning and the schedule
        compiled into the executable (hybrid/aot.py joins this into
        the bundle identity)."""
        self._require_resolved("fingerprint()")
        return {
            "topology": self.topology(),
            "zero_stage": int(self.zero_stage),
            "schedule": str(self.schedule),
            "num_microbatches": int(self.num_microbatches),
            "grad_accum_steps": int(self.grad_accum_steps),
        }

    def build_mesh(self, devices: Optional[Sequence] = None):
        d = self.degrees
        mesh = _build_mesh(dp=d["data"], pp=d["stage"],
                           cp=d["context"], ep=d["expert"],
                           mp=d["model"], devices=devices)
        # inferred (-1) degrees become concrete here, so topology()/
        # fingerprint() always name the REAL partitioning
        self.adopt_mesh(mesh)
        return mesh

    def describe(self) -> str:
        zs = {0: "DP", 1: "ZeRO-1 (opt-state shards)",
              2: "ZeRO-2 (+persistent grad shards)",
              3: "ZeRO-3 (param shards)"}[self.zero_stage]
        bits = [f"mesh[{self.topology()}]", zs]
        if self.mp > 1:
            bits.append("TP over 'model'")
        if self.pp > 1:
            bits.append(f"PP {self.schedule} x{self.num_microbatches}mb")
        if self.grad_accum_steps > 1:
            bits.append(f"accum={self.grad_accum_steps}")
        return " + ".join(bits)

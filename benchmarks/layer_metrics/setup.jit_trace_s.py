"""Seconds JAX spent tracing (jaxpr_trace_duration events of the compile log) before the window's start."""
from benchmarks.lib import stage_gaps

NAME, UNIT = "setup.jit_trace_s", "s"
LAYER, MOVES = "serve programs", "setup_s"


def read(record, trace):
    return stage_gaps.setup_seconds(record, ("trace",))

"""Trace events in JAX's compile log (observability.runtime) stamped inside the window: a program shape the warm-up never compiled."""
from benchmarks.lib import stage_gaps

NAME, UNIT = "serve.retraces_in_window.open", "count"
LAYER, MOVES = "serve programs", "ttft_p95_ms"


def read(record, trace):
    return stage_gaps.retraces_in_window(record)

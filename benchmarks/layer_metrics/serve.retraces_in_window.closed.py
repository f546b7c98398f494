"""Trace events in JAX's compile log (observability.runtime) stamped inside the window: a program shape the warm-up never compiled."""
from benchmarks.lib import stage_gaps

NAME, UNIT = "serve.retraces_in_window.closed", "count"
LAYER, MOVES = "serve programs", "serve_tokens_per_s"


def read(record, trace):
    return stage_gaps.retraces_in_window(record)

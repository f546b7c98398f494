"""Device time in the prefill and suffix-prefill programs over device busy time."""
from benchmarks.lib import readers, trace_reduce

NAME, UNIT = "step.prefill_share_pct.closed", "%"
LAYER, MOVES = "serve programs", "serve_tokens_per_s"


def read(record, trace):
    hit = trace_reduce.time_of(trace, "programs", readers.PREFILL)
    busy = trace["busy_s"] * max(trace["devices"], 1)
    return 100.0 * hit[1] / busy if hit and busy else None

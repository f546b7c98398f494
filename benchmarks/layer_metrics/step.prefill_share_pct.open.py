"""Device time in the prefill programs (the chunked scan's side) over device busy time."""
from benchmarks.lib import readers, trace_reduce

NAME, UNIT = "step.prefill_share_pct.open", "%"
LAYER, MOVES = "serve programs", "tpot_p95_ms"


def read(record, trace):
    hit = trace_reduce.time_of(trace, "programs", readers.PREFILL)
    busy = trace["busy_s"] * max(trace["devices"], 1)
    return 100.0 * hit[1] / busy if hit and busy else None

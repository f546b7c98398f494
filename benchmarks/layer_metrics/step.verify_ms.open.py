"""Median device time of one self-drafting tick, from the trace's XLA
Modules line: one run of the tick's program (`_raw_mtp_step`: the verify
span through the trunk and, in the same program, the draft pass)."""
from benchmarks.lib import trace_reduce

NAME, UNIT = "step.verify_ms.open", "ms"
LAYER, MOVES = "serve programs", "tpot_p95_ms"
TICK = r"^_raw_mtp_step$"


def read(record, trace):
    tick = trace_reduce.time_of(trace, "programs", TICK)
    return tick[2] * 1e3 if tick else None

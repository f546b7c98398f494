"""Median device time of one run of the decode-step program, from the trace's XLA Modules line."""
from benchmarks.lib import readers

NAME, UNIT = "step.decode_ms.open", "ms"
LAYER, MOVES = "serve programs", "tpot_p95_ms"


def read(record, trace):
    return readers.decode_ms(record, trace)

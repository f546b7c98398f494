"""serving.in_flight over serving.slots, sampled every 10 ms of the window, mean."""
from benchmarks.lib import readers

NAME, UNIT = "serve.batch_occupancy_pct.open", "%"
LAYER, MOVES = "serve loop, host", "tpot_p95_ms"


def read(record, trace):
    return readers.occupancy_pct(record, trace)

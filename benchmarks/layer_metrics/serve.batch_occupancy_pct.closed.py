"""serving.in_flight over serving.slots, sampled every 10 ms of the window, mean."""
from benchmarks.lib import readers

NAME, UNIT = "serve.batch_occupancy_pct.closed", "%"
LAYER, MOVES = "serve loop, host", "serve_tokens_per_s"


def read(record, trace):
    return readers.occupancy_pct(record, trace)

"""1 - union of busy intervals over the traced window, from the device trace."""
from benchmarks.lib import readers

NAME, UNIT = "device.idle_pct.closed", "%"
LAYER, MOVES = "device", "serve_tokens_per_s"


def read(record, trace):
    return readers.idle_pct(record, trace)

"""1 - union of busy intervals over the traced window, from the device trace."""
from benchmarks.lib import readers

NAME, UNIT = "device.idle_pct.open", "%"
LAYER, MOVES = "device", "tpot_p95_ms"


def read(record, trace):
    return readers.idle_pct(record, trace)

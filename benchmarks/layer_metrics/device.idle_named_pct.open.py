"""Share of the device's idle seconds (gaps between merged ops, numbers trace, from the first recorded serve.tick to the last) that lie under a serve.* annotation of the serve thread: how much of the idle time the stage spans can name."""
from benchmarks.lib import stage_gaps

NAME, UNIT = "device.idle_named_pct.open", "%"
LAYER, MOVES = "device", "tpot_p95_ms"


def read(record, trace):
    return stage_gaps.idle_named_pct(record)

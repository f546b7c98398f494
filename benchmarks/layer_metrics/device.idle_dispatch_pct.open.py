"""Idle seconds of the device under the serve loop's serve.dispatch stage (ragged metadata, operand placement, the jit call), over the device's window."""
from benchmarks.lib import stage_gaps

NAME, UNIT = "device.idle_dispatch_pct.open", "%"
LAYER, MOVES = "device", "tpot_p95_ms"


def read(record, trace):
    return stage_gaps.idle_under_pct(record, "serve.dispatch")

"""Idle seconds of the device under the serve loop's serve.admit stage (queue pick, page planning, prefix lookup, the prefill's host side), over the device's window."""
from benchmarks.lib import stage_gaps

NAME, UNIT = "device.idle_admit_pct.open", "%"
LAYER, MOVES = "device", "tpot_p95_ms"


def read(record, trace):
    return stage_gaps.idle_under_pct(record, "serve.admit")

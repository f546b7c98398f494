"""How late the open-loop generator sent: sent minus due, 95th percentile, on the generator's own clock."""
from benchmarks.lib import readers

NAME, UNIT = "gen.late_p95_ms", "ms"
LAYER, MOVES = "load generator", "ttft_p95_ms"


def read(record, trace):
    return readers.p95(record.get("late_ms"))

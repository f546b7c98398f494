"""serving.page_utilization sampled every 10 ms of the window, 95th percentile."""
from benchmarks.lib import readers

NAME, UNIT = "kv.page_util_p95_pct.closed", "%"
LAYER, MOVES = "KV pool", "serve_tokens_per_s"


def read(record, trace):
    return readers.p95((record.get("occupancy") or {}).get("page_util"), 100.0)

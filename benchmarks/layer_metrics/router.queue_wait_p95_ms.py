"""Wait before prefill starts: the program's request spans folded by observability/critpath into admission + dispatch + queue, 95th percentile."""
from benchmarks.lib import readers

NAME, UNIT = "router.queue_wait_p95_ms", "ms"
LAYER, MOVES = "router", "ttft_p95_ms"


def read(record, trace):
    return readers.p95(record.get("router_wait_s"), 1e3)

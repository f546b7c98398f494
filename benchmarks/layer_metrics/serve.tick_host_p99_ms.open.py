"""Host time of a serve-loop tick (serve.tick less serve.resolve.wait, from the program's tick ring), 99th percentile over the ticks that began in the window: a long tick lets arrivals pile up."""
from benchmarks.lib import stage_gaps

NAME, UNIT = "serve.tick_host_p99_ms.open", "ms"
LAYER, MOVES = "serve loop, host", "ttft_p95_ms"


def read(record, trace):
    return stage_gaps.tick_host_ms(record, 99)

"""Host time of a serve-loop tick (serve.tick less serve.resolve.wait, from the program's tick ring), median over the ticks that began in the window."""
from benchmarks.lib import stage_gaps

NAME, UNIT = "serve.tick_host_ms.closed", "ms"
LAYER, MOVES = "serve loop, host", "serve_tokens_per_s"


def read(record, trace):
    return stage_gaps.tick_host_ms(record, 50)

"""The longest garbage collection that began in the window (the program's gc log, stamped on the ticks' clock), 0 where it logged none: every thread stands still that long."""
from benchmarks.lib import prefill_account

NAME, UNIT = "host.gc_pause_max_ms.closed", "ms"
LAYER, MOVES = "serve loop, host", "serve_tokens_per_s"


def read(record, trace):
    return prefill_account.gc_pause_max_ms(record)

"""What a stalled token waited: 95th percentile, over the tokens handed out by a pass that ran a prefill, of that pass's prefill seconds (dispatch to first tokens)."""
from benchmarks.lib import prefill_account

NAME, UNIT = "serve.decode_stall_p95_ms.open", "ms"
LAYER, MOVES = "serve loop, host", "tpot_p95_ms"


def read(record, trace):
    return prefill_account.decode_stall_p95_ms(record)

"""Share of the token gaps that held a prefill: tokens handed out by a pass of the serve loop that ran a prefill (first tokens left out), over all tokens that closed a gap, whole window. Above 5 the tpot tail IS a stalled gap."""
from benchmarks.lib import prefill_account

NAME, UNIT = "serve.stalled_token_pct.open", "%"
LAYER, MOVES = "serve loop, host", "tpot_p95_ms"


def read(record, trace):
    return prefill_account.stalled_token_pct(record)

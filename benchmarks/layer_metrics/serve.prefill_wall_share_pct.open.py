"""Share of the window's seconds between a prefill's dispatch and its first tokens on the host (the serve loop's own stamps, summed in the tick ring over the whole window): no token reaches a stream meanwhile (the step in flight runs to its end first, so this is more than the prefill programs' device time)."""
from benchmarks.lib import prefill_account

NAME, UNIT = "serve.prefill_wall_share_pct.open", "%"
LAYER, MOVES = "serve loop, host", "ttft_p95_ms"


def read(record, trace):
    return prefill_account.wall_share_pct(record)

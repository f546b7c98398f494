"""Share of the positions the window's prefill programs computed (rows x bucket) that held no prompt token, from the tick ring's counts."""
from benchmarks.lib import prefill_account

NAME, UNIT = "serve.prefill_pad_pct.open", "%"
LAYER, MOVES = "serve loop, host", "ttft_p95_ms"


def read(record, trace):
    return prefill_account.pad_pct(record)

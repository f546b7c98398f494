"""Prefill seconds (dispatch to first tokens, from the tick ring) a prompt token FORWARDED, over the whole window; nothing once chunks of a chunked prefill were counted."""
from benchmarks.lib import prefill_account

NAME, UNIT = "serve.prefill_us_per_token.closed", "us"
LAYER, MOVES = "serve loop, host", "serve_tokens_per_s"


def read(record, trace):
    return prefill_account.us_per_token(record)

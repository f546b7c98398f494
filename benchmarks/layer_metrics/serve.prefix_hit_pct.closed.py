"""Prompt tokens served from reused pages (pred.stats pages_reused x page size) over the prompt tokens sent in the window."""
NAME, UNIT = "serve.prefix_hit_pct.closed", "%"
LAYER, MOVES = "serve loop, host", "serve_tokens_per_s"


def read(record, trace):
    w = record.get("pred_stats_window") or {}
    sent = record.get("prompt_tokens_sent")
    if not sent or "pages_reused" not in w:
        return None
    return 100.0 * w["pages_reused"] * record["geometry"]["page_size"] / sent

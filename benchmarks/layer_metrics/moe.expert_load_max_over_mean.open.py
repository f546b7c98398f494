"""Stragglers among the held experts: the busiest expert's tokens over
the mean expert's, from the program's counter `moe.expert_tokens`
(all layers, the whole run: warm-up, window and drain). 1 is an even
load; a grouped matmul waits for its largest group."""
NAME, UNIT = "moe.expert_load_max_over_mean.open", "ratio"
LAYER, MOVES = "expert layer", "tpot_p95_ms"


def read(record, trace):
    from paddle_tpu.observability import metrics
    per = {}
    for s in metrics.counter("moe.expert_tokens").samples():
        e = s.labels.get("expert")
        per[e] = per.get(e, 0.0) + s.value
    total = sum(per.values())
    if not per or not total:
        return None
    return max(per.values()) / (total / len(per))

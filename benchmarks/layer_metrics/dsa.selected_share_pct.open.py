"""How sparse the traffic made attention: keys the decode steps'
queries attended to over keys they could see, from the program's
counters `dsa.keys_selected` / `dsa.keys_live` (all layers, the slots
that carry a request, the whole run: warm-up, window and drain). 100 is
dense: every context at or under `topk`."""
NAME, UNIT = "dsa.selected_share_pct.open", "%"
LAYER, MOVES = "paged kernels", "tpot_p95_ms"


def read(record, trace):
    from paddle_tpu.observability import metrics

    def total(name):
        return sum(s.value for s in metrics.counter(name).samples())
    live = total("dsa.keys_live")
    return 100.0 * total("dsa.keys_selected") / live if live else None

"""Share of the drafted tokens that the verify accepted, from the
program's on-device counters `mtp.drafts_accepted` over
`mtp.drafts_proposed` (the whole run: warm-up, window and drain). With
weights from a seed the drafter agrees with the trunk at chance, one in
the vocabulary: the cell reads about 0, and nothing in the program or
the benchmark makes it read more."""
NAME, UNIT = "mtp.accept_pct.open", "%"
LAYER, MOVES = "serve programs", "tpot_p95_ms"


def read(record, trace):
    from paddle_tpu.observability import metrics
    total = lambda name: sum(s.value for s in
                             metrics.counter(name).samples())
    proposed = total("mtp.drafts_proposed")
    if not proposed:
        return None
    return 100.0 * total("mtp.drafts_accepted") / proposed

"""How far from doubly stochastic the worst residual map of the run was:
the larger of the program's gauges `mhc.sinkhorn_row_err_max` and
`mhc.sinkhorn_col_err_max` (the largest |row sum - 1| and |column sum -
1| of any H_res a step formed, float32, on the device, down with the
step's tokens; all layers, the whole run: warm-up, window and drain). The
sweep ends on the rows, so this reads the columns: what exactly
`hc_sinkhorn_iters` sweeps leave on the slowest token. A program that
sweeps fewer times reads higher."""
NAME, UNIT = "mhc.sinkhorn_err_max.open", "ratio"
LAYER, MOVES = "residual streams", "tpot_p95_ms"
GAUGES = ("mhc.sinkhorn_row_err_max", "mhc.sinkhorn_col_err_max")


def read(record, trace):
    from paddle_tpu.observability import metrics
    found = [0.0]
    for name in GAUGES:
        gauge = metrics.get_registry().get(name)    # None on a parent
        found += [s.value for s in gauge.samples()] if gauge else []
    return max(found) or None

"""Share of its roofline that the decode step's SSM state update reaches
in one Mamba-2 layer: the least time to read and write the float32 state
of the slots that carry a request (and the step's small tensors) at the
HBM rate, over the median device time of the op of `_raw_decode_step`
that produces the layer's new state (one call a Mamba layer a step). The bytes come from
`benchmarks/kernels/ssm_state_update.py` at the window's mean number of
occupied slots; the program updates every row of the pool whatever the
occupancy, so an emptier batch reads lower."""
import os
import re

from benchmarks.lib import harness

NAME, UNIT = "ssm.state_update_roofline", "%"
LAYER, MOVES = "state-space kernels", "tpot_p95_ms"
CONFIG = "benchmarks/configs/granite-4.0-h-small-serve.json"


def state_ops(trace, rows, heads, head_dim):
    """The fused update of a layer's state: a fusion that multiplies the
    old state by its decay, adds the outer product and reduces the new
    state against C, writing y `f32[rows, heads, head_dim]` (the output
    the trace names it by) and the new state beside it."""
    rx = re.compile(rf"reduce\w*:fusion:f32\[{rows},{heads},{head_dim}\]$")
    return [v for n, v in trace.get("ops", {}).items() if rx.search(n)]


def read(record, trace):
    occ = (record.get("occupancy") or {}).get("occupancy")
    if not occ or not record.get("peaks"):
        return None
    cfg = harness.load_json(os.path.join(record["root"], CONFIG))
    g = record["geometry"]
    heads, p, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_state"])
    ops = state_ops(trace, g["slots"] + 1, heads, p)
    if not ops:
        return None
    seconds = sum(v["median_s"] for v in ops)
    kernel = harness.load_module(record["root"], "kernels",
                                 "ssm_state_update")
    active = max(1.0, sum(occ) / len(occ) * g["slots"])
    least = kernel.least_seconds(active, heads, p, n, cfg["mamba_n_groups"],
                                 g["itemsize"], record["peaks"])
    return 100.0 * least / seconds

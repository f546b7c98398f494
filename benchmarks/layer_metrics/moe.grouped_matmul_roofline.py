"""Share of their roofline that the two grouped expert matmuls reach in
one layer of a decode step: the least time to read the weights of the
held experts that the step's routing touches, and to move the rows'
activations, over the mean device times of the two grouped matmuls of
`_raw_decode_step` (one call each a layer a step), added. Bytes and operations come from
`benchmarks/kernels/moe_grouped.py` at the window's mean number of
occupied slots (the program routes every slot's row, occupied or not,
so an emptier batch reads lower)."""
import os
import re

from benchmarks.lib import harness

NAME, UNIT = "moe.grouped_matmul_roofline", "%"
LAYER, MOVES = "expert layer", "tpot_p95_ms"
CONFIG = "benchmarks/configs/granite-4.0-h-small-serve.json"


def grouped_ops(trace, rows, widths):
    """The compiler's grouped-matmul kernels (`jax.lax.ragged_dot`) whose
    output is [rows, one of widths]."""
    rx = re.compile(r"ragged[-_]dot.*:\w+\[" + str(rows) + r",("
                    + "|".join(str(w) for w in widths) + r")\]$")
    return [v for n, v in trace.get("ops", {}).items() if rx.search(n)]


def read(record, trace):
    occ = (record.get("occupancy") or {}).get("occupancy")
    if not occ or not record.get("peaks"):
        return None
    cfg = harness.load_json(os.path.join(record["root"], CONFIG))
    g = record["geometry"]
    k, width, hidden = (cfg["num_experts_per_tok"], cfg["intermediate_size"],
                        cfg["hidden_size"])
    ops = grouped_ops(trace, g["slots"] * k, (2 * width, hidden))
    if not ops:
        return None
    # mean, not median: the time follows the experts touched, which
    # follows the occupancy, and the bytes are taken at its mean
    seconds = sum(v["total_s"] / v["calls"] for v in ops)
    kernel = harness.load_module(record["root"], "kernels", "moe_grouped")
    active = max(1.0, sum(occ) / len(occ) * g["slots"])
    least = kernel.least_seconds(
        active, hidden, width, cfg["num_local_experts"],
        cfg["published"]["num_local_experts"], k, g["itemsize"],
        record["peaks"])
    return 100.0 * least / seconds

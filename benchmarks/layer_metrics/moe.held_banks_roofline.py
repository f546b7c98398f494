"""Share of their roofline that the two grouped expert matmuls reach in
ONE expert layer of a self-drafting tick (the layer that takes most of
the tick): the least time to read the banks of the held experts that the
tick's routing touches and to move the rows' activations
(`benchmarks/kernels/moe_grouped.py`, at the window's mean number of
occupied slots times the span's two tokens a slot, over the published
256 experts of which this chip holds `experts_held`), over the mean
device times of the compiler's two grouped matmuls (`ragged-dot` with
`slots x span x top_k` rows: one call each an expert layer, the trunk's
and the MTP module's alike), added. The program routes every slot's
rows, occupied or not, so an emptier batch reads lower. The sizes come
from the cell's configuration file."""
import os

from benchmarks.lib import harness

NAME, UNIT = "moe.held_banks_roofline", "%"
LAYER, MOVES = "expert layer", "tpot_p95_ms"
CONFIG = "benchmarks/configs/openpangu-ultra-moe-718b-serve.json"
SPAN = 2


def read(record, trace):
    occ = (record.get("occupancy") or {}).get("occupancy")
    if not occ or not record.get("peaks") or not record.get("root"):
        return None
    path = os.path.join(record["root"], CONFIG)
    if not os.path.isfile(path):
        return None
    cfg = harness.load_json(path)
    g = record["geometry"]
    k, width, hidden = (cfg["num_experts_per_tok"],
                        cfg["moe_intermediate_size"], cfg["hidden_size"])
    grouped = harness.load_module(record["root"], "layer_metrics",
                                  "moe.grouped_matmul_roofline").grouped_ops
    ops = grouped(trace, g["slots"] * SPAN * k, (2 * width, hidden))
    if not ops:
        return None
    # mean, not median: the time follows the banks touched, which
    # follows the occupancy, and the bytes are taken at its mean
    seconds = sum(v["total_s"] / v["calls"] for v in ops)
    kernel = harness.load_module(record["root"], "kernels", "moe_grouped")
    active = max(1.0, sum(occ) / len(occ) * g["slots"])
    least = kernel.least_seconds(
        active * SPAN, hidden, width, len(cfg["experts_held"]),
        cfg["published"]["n_routed_experts"], k, g["itemsize"],
        record["peaks"])
    return 100.0 * least / seconds

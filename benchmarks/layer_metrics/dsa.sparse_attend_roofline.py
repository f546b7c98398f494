"""Share of its roofline that the decode step's attention over the
selected keys reaches in one layer: the least time at the HBM rate to
read K and V of the min(context, topk) SELECTED keys of each slot that
carries a request, with q in and the output back
(`benchmarks/kernels/dsa_sparse_attend.py`, at the window's mean
occupied slots and mean cached length), over the median device time of
the kernel the program names `dsa.attend` (one call a layer a step).
The program's form reads every live page and masks, so it reads near
topk / context here: that is headroom, not a fault. `head_dim` is the
configuration's (it is not hidden / heads)."""
import os

from benchmarks.lib import harness, trace_reduce

NAME, UNIT = "dsa.sparse_attend_roofline", "%"
LAYER, MOVES = "paged kernels", "tpot_p95_ms"
CONFIG = "benchmarks/configs/keye-vl-2.0-30b-a3b-serve.json"
KERNEL = r"^dsa\.attend:custom-call:\w+\[\d+,\d+,\d+\]$"


def read(record, trace):
    hit = trace_reduce.time_of(trace, "ops", KERNEL)
    if not hit or not record.get("root"):
        return None
    step = harness.load_module(record["root"], "layer_metrics",
                               "dsa.indexer_roofline").mean_step(record)
    if not step:
        return None
    cfg = harness.load_json(os.path.join(record["root"], CONFIG))
    kernel = harness.load_module(record["root"], "kernels",
                                 "dsa_sparse_attend")
    g = record["geometry"]
    least = kernel.least_seconds(
        [step[1]] * step[0], cfg["sa_config"]["topk"], g["kv_heads"],
        cfg["head_dim"], g["q_heads"], g["itemsize"], record["peaks"])
    return 100.0 * least / hit[2]

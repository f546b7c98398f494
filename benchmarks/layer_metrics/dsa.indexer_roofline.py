"""Share of its roofline that the decode step's index-score kernel
reaches in one layer: the least time its bytes allow at the HBM rate
(`benchmarks/kernels/dsa_indexer.py`: the live index keys of the slots
that carry a request, at the window's mean number of occupied slots and
their mean cached length; the query's index vectors and weights in, one
float32 score a key out), over the median device time of the kernel the
program names `dsa.indexer` (one call a layer a step). The sizes come
from the cell's configuration file."""
import os

from benchmarks.lib import harness, trace_reduce

NAME, UNIT = "dsa.indexer_roofline", "%"
LAYER, MOVES = "paged kernels", "tpot_p95_ms"
CONFIG = "benchmarks/configs/keye-vl-2.0-30b-a3b-serve.json"
KERNEL = r"^dsa\.indexer:custom-call:f32\[\d+,1,\d+\]$"


def mean_step(record):
    """(slots that carry a request, keys each holds) of the window's
    mean decode step, or None where the record lacks them."""
    occ = (record.get("occupancy") or {}).get("occupancy")
    ctx = record.get("mean_decode_ctx")
    if not occ or not ctx or not record.get("peaks") \
            or not record.get("root"):
        return None
    active = max(1, round(sum(occ) / len(occ) * record["geometry"]["slots"]))
    return active, ctx


def read(record, trace):
    hit = trace_reduce.time_of(trace, "ops", KERNEL)
    step = mean_step(record)
    if not hit or not step:
        return None
    sa = harness.load_json(os.path.join(record["root"], CONFIG))["sa_config"]
    kernel = harness.load_module(record["root"], "kernels", "dsa_indexer")
    least = kernel.least_seconds(
        [step[1]] * step[0], sa["indexer_num_heads"], sa["indexer_head_dim"],
        record["geometry"]["itemsize"], record["peaks"])
    return 100.0 * least / hit[2]

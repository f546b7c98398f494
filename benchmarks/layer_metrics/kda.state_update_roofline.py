"""Share of its roofline that the decode step's delta-rule state update
reaches in one KDA layer: the least time to read and write the float32
state of the slots that carry a request (and the step's small tensors)
at the HBM rate (`benchmarks/kernels/kda_state_update.py`, at the
window's mean number of occupied slots), over the median device time of
the kernel the program names `kda.state_update` in `_raw_decode_step`
(output `f32[slots + 1, heads, d_k, d_v]`; one call a KDA layer a step).
The kernel visits the occupied rows and, once, the pool's last row."""
import os

from benchmarks.lib import harness, trace_reduce

NAME, UNIT = "kda.state_update_roofline", "%"
LAYER, MOVES = "state-space kernels", "tpot_p95_ms"
CONFIG = "benchmarks/configs/ling-3.0-flash-serve.json"
KERNEL = r"^kda\.state_update:custom-call:f32\[\d+,\d+,\d+,\d+\]$"


def read(record, trace):
    hit = trace_reduce.time_of(trace, "ops", KERNEL)
    occ = (record.get("occupancy") or {}).get("occupancy")
    if not hit or not occ or not record.get("peaks") \
            or not record.get("root"):
        return None
    cfg = harness.load_json(os.path.join(record["root"], CONFIG))
    g = record["geometry"]
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    kernel = harness.load_module(record["root"], "kernels",
                                 "kda_state_update")
    active = max(1.0, sum(occ) / len(occ) * g["slots"])
    least = kernel.least_seconds(active, heads, d, d, g["itemsize"],
                                 record["peaks"])
    return 100.0 * least / hit[2]

"""Share of its roofline that the ragged paged-attention kernel reaches:
the least time its bytes allow at the HBM rate, over the median device
time of one call. The bytes of a call come from
`benchmarks/kernels/ragged_paged.py`, at the window's mean number of
active slots and their mean cached length (an estimate of the mean
call, not a count of each call)."""
from benchmarks.lib import harness, trace_reduce

NAME, UNIT = "ragged_paged_roofline", "%"
LAYER, MOVES = "paged kernels", "tpot_p95_ms"
# the program gives its kernels no name: in the decode step the paged
# kernel is the custom call whose output is [slots, heads, head_dim]
KERNEL = r"_raw_decode_step:custom-call:\w+\[\d+,\d+,\d+\]$"


def read(record, trace):
    hit = trace_reduce.time_of(trace, "ops", KERNEL)
    occ = (record.get("occupancy") or {}).get("occupancy")
    ctx = record.get("mean_decode_ctx")
    if not hit or not occ or not ctx or not record.get("peaks"):
        return None
    kernel = harness.load_module(record["root"], "kernels",
                                 "ragged_paged")
    g = record["geometry"]
    active = max(1, round(sum(occ) / len(occ) * g["slots"]))
    least = kernel.least_seconds([ctx] * active, g["page_size"],
                                 g["kv_heads"], g["head_dim"],
                                 g["q_heads"], g["itemsize"],
                                 record["peaks"])
    return 100.0 * least / hit[2]

"""Share of its roofline that the verify span's latent attention
reaches: the least time its bytes and operations allow
(`benchmarks/kernels/mla_verify.py`: the live rows of the slots that
carry a request ONCE a layer for the span's two queries, at the window's
mean number of occupied slots and their mean cached length; the 2 x 128
absorbed queries in, the summed latents back), over the median device
time of the kernel the program names `mla.attend` with the span's output
`[slots, 2 x heads, lanes]` (in `_raw_mtp_step` one call a trunk layer
and one in the draft pass, the same shape and work; the prefill's
flash kernel carries the same scope's name and another shape). The sizes
come from the cell's configuration file."""
import os

from benchmarks.lib import harness, trace_reduce

NAME, UNIT = "mla.verify_roofline", "%"
LAYER, MOVES = "paged kernels", "tpot_p95_ms"
CONFIG = "benchmarks/configs/openpangu-ultra-moe-718b-serve.json"
SPAN = 2


def read(record, trace):
    occ = (record.get("occupancy") or {}).get("occupancy")
    ctx = record.get("mean_decode_ctx")
    if not occ or not ctx or not record.get("peaks") \
            or not record.get("root"):
        return None
    path = os.path.join(record["root"], CONFIG)
    if not os.path.isfile(path):
        return None
    cfg = harness.load_json(path)
    g = record["geometry"]
    heads = cfg["num_attention_heads"]
    hit = trace_reduce.time_of(
        trace, "ops", rf"^mla\.attend:custom-call:\w+\[{g['slots']},"
        rf"{SPAN * heads},\d+\]$")
    if not hit:
        return None
    active = max(1, round(sum(occ) / len(occ) * g["slots"]))
    kernel = harness.load_module(record["root"], "kernels", "mla_verify")
    least = kernel.least_seconds(
        [ctx] * active, SPAN, heads, cfg["kv_lora_rank"],
        cfg["qk_rope_head_dim"], g["itemsize"], record["peaks"])
    return 100.0 * least / hit[2]

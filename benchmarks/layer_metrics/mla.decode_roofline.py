"""Share of its roofline that the decode step's latent attention reaches
in the MLA layer: the least time its bytes allow at the HBM rate
(`benchmarks/kernels/mla_decode.py`: the live rows of the slots that
carry a request ONCE, at the window's mean number of occupied slots and
their mean cached length; the absorbed queries in, the summed latents
back), over the median device time of the kernel the program names
`mla.attend` in `_raw_decode_step` (output `[slots, heads, lanes]`; one
call a step: this cut has one MLA layer; the prefill's flash kernel
carries the same scope's name and another shape). The sizes come from
the cell's configuration file."""
import os

from benchmarks.lib import harness, trace_reduce

NAME, UNIT = "mla.decode_roofline", "%"
LAYER, MOVES = "paged kernels", "tpot_p95_ms"
CONFIG = "benchmarks/configs/ling-3.0-flash-serve.json"


def read(record, trace):
    occ = (record.get("occupancy") or {}).get("occupancy")
    ctx = record.get("mean_decode_ctx")
    if not occ or not ctx or not record.get("peaks") \
            or not record.get("root"):
        return None
    cfg = harness.load_json(os.path.join(record["root"], CONFIG))
    g = record["geometry"]
    hit = trace_reduce.time_of(
        trace, "ops", rf"^mla\.attend:custom-call:\w+\[{g['slots']},"
        rf"{cfg['num_attention_heads']},\d+\]$")
    if not hit:
        return None
    active = max(1, round(sum(occ) / len(occ) * g["slots"]))
    kernel = harness.load_module(record["root"], "kernels", "mla_decode")
    least = kernel.least_seconds(
        [ctx] * active, cfg["num_attention_heads"], cfg["kv_lora_rank"],
        cfg["qk_rope_head_dim"], g["itemsize"], record["peaks"])
    return 100.0 * least / hit[2]

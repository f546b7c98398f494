"""Share of its roofline that the decode step's latent attention over
the SELECTED rows reaches in one layer: the least time at the HBM rate
to read the min(context, topk) selected rows of each slot that carries a
request ONCE, with the absorbed queries in and the summed latents back
(`benchmarks/kernels/mla_sparse_decode.py`, at the window's mean
occupied slots and mean cached length), over the median device time of
the kernel the program names `mla.attend` in `_raw_decode_step` (output
`[slots, heads, lanes]`, one call a layer a step; the prefill's flash
kernel carries the same scope's name and another shape). The program's
form reads every live page and masks, so it reads near topk / context
here: that is headroom, not a fault."""
import os

from benchmarks.lib import harness, trace_reduce

NAME, UNIT = "mla_dsa.sparse_decode_roofline", "%"
LAYER, MOVES = "paged kernels", "tpot_p95_ms"
CONFIG = "benchmarks/configs/glm-5-serve.json"


def read(record, trace):
    if not record.get("root") or not record.get("geometry"):
        return None
    step = harness.load_module(record["root"], "layer_metrics",
                               "mla_dsa.indexer_roofline").mean_step(record)
    if not step:
        return None
    cfg = harness.load_json(os.path.join(record["root"], CONFIG))
    g = record["geometry"]
    hit = trace_reduce.time_of(
        trace, "ops", rf"^mla\.attend:custom-call:\w+\[{g['slots']},"
        rf"{cfg['num_attention_heads']},\d+\]$")
    if not hit:
        return None
    kernel = harness.load_module(record["root"], "kernels",
                                 "mla_sparse_decode")
    least = kernel.least_seconds(
        [step[1]] * step[0], cfg["index_topk"], cfg["num_attention_heads"],
        cfg["kv_lora_rank"], cfg["qk_rope_head_dim"], g["itemsize"],
        record["peaks"])
    return 100.0 * least / hit[2]

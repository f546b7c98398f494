"""Share of its roofline that the decode step's index-score kernel
reaches in one layer of the model whose indexer selects among latent
rows: `dsa.indexer_roofline`'s arithmetic (the least time the live index
keys' bytes allow at the HBM rate, `benchmarks/kernels/dsa_indexer.py`,
at the window's mean occupied slots and mean cached length, over the
median device time of the kernel the program names `dsa.indexer` in
`_raw_decode_step`: output `f32[slots, 1, keys]`, one call a layer a
step) at this configuration's 32 index heads of 128, where a key fills
its 128-lane row. The sizes come from the cell's configuration file."""
import os

from benchmarks.lib import harness, trace_reduce

NAME, UNIT = "mla_dsa.indexer_roofline", "%"
LAYER, MOVES = "paged kernels", "tpot_p95_ms"
CONFIG = "benchmarks/configs/glm-5-serve.json"
KERNEL = r"^dsa\.indexer:custom-call:f32\[\d+,1,\d+\]$"


def mean_step(record):
    """(slots that carry a request, rows each holds) of the window's
    mean decode step, or None where the record lacks them."""
    if not record.get("root"):
        return None
    return harness.load_module(record["root"], "layer_metrics",
                               "dsa.indexer_roofline").mean_step(record)


def read(record, trace):
    hit = trace_reduce.time_of(trace, "ops", KERNEL)
    step = mean_step(record)
    if not hit or not step:
        return None
    cfg = harness.load_json(os.path.join(record["root"], CONFIG))
    kernel = harness.load_module(record["root"], "kernels", "dsa_indexer")
    least = kernel.least_seconds(
        [step[1]] * step[0], cfg["index_n_heads"], cfg["index_head_dim"],
        record["geometry"]["itemsize"], record["peaks"])
    return 100.0 * least / hit[2]

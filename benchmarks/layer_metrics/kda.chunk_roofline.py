"""Share of its roofline that the prefill's chunked delta rule reaches:
the least time the DEFINITION's operations and bytes allow
(`benchmarks/kernels/kda_chunk.py`: seven operations a state element a
head a token, q, k, v, g, beta in and o out) for the tokens the traced
prefills ran, over the device time of the chunked recurrence's ops
(`benchmarks/lib/kda_ops.py` `chunk_ops`: the ops of `kda_chunked`, told
by their [rows, heads, chunks, ...] shapes), summed over the trace. The
tokens are counted from the trace itself: the scan over chunks makes
its carried state `f32[rows, heads, d_k, d_v]` once a chunk a layer (the
most often run op of that shape; a segment's copies of it run a
sixteenth as often), so calls x rows x chunk is tokens x layers, padding
included (the program computes a bucket's padding like its prompt). The
projections, the convolution and the output norm are not in it."""
import os
import re

from benchmarks.lib import harness, kda_ops

NAME, UNIT = "kda.chunk_roofline", "%"
LAYER, MOVES = "state-space kernels", "ttft_p95_ms"
CONFIG = "benchmarks/configs/ling-3.0-flash-serve.json"


def read(record, trace):
    if not record.get("peaks") or not record.get("root"):
        return None
    path = os.path.join(record["root"], CONFIG)
    if not os.path.isfile(path):
        return None
    cfg = harness.load_json(path)
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    chunk = cfg.get("kda_chunk_size", 64)
    ops = kda_ops.chunk_ops(trace, heads, d)
    carried = re.compile(rf":f32\[[12],{heads},{d},{d}\]$")
    token_layers = sum(
        rows * chunk * max([v["calls"] for n, (r, v) in ops.items()
                            if r == rows and carried.search(n)] or [0])
        for rows in (1, 2))
    seconds = sum(v["total_s"] for _, v in ops.values())
    if not token_layers or not seconds:
        return None
    kernel = harness.load_module(record["root"], "kernels", "kda_chunk")
    least = kernel.least_seconds(token_layers, heads, d, d,
                                 record["geometry"]["itemsize"],
                                 record["peaks"])
    return 100.0 * least / seconds

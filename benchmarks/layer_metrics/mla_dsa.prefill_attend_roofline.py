"""Share of the MXU's peak that the prefill's attention under the
selection reaches: the definition's operations in decompressed form
(`benchmarks/kernels/mla_sparse_prefill.py`: 4 x heads x 256 a query a
SELECTED key) for the chunks of queries the traced prefills ran, over
the device time of the kernel the program names `mla.attend` in
`_raw_prefill` (output `[rows, heads, tiles, 512, 256]`: one call a
chunk of queries a layer), summed over the trace. The chunks are counted
from the trace itself, so a prefill the trace cuts counts what of it ran:
a chunk whose queries were selected for also ran the index-score kernel
(`dsa.indexer`, output `f32[rows, chunk, keys]`) and holds `chunk`
queries of `topk` keys; every other chunk that ran lies in a prompt's
first `topk` tokens. The program attends a prompt a row, so every row is
a prompt's; a leading chunk's padding counts as queries (under a
hundredth of a prompt's pairs). The program's form computes every key
up to a chunk's last query and masks: it reads near 2 topk / prompt of
what the MXU does, which is headroom."""
import os
import re

from benchmarks.lib import harness

NAME, UNIT = "mla_dsa.prefill_attend_roofline", "%"
LAYER, MOVES = "prefill kernels", "ttft_p95_ms"
CONFIG = "benchmarks/configs/glm-5-serve.json"


def chunks(trace, cfg):
    """{rows: (attention calls, their seconds, index-score calls)} of
    the prefill's kernels in the trace."""
    heads, chunk = cfg["num_attention_heads"], cfg.get("q_chunk_size", 512)
    attend = re.compile(rf"^mla\.attend:custom-call:\w+\[(\d+),{heads},"
                        rf"\d+,\d+,{cfg['qk_head_dim']}\]$")
    scores = re.compile(rf"^dsa\.indexer:custom-call:f32\[(\d+),{chunk},"
                        rf"\d+\]$")
    out = {}
    for name, v in trace.get("ops", {}).items():
        for rx, at in ((attend, 0), (scores, 2)):
            m = rx.match(name)
            if m:
                row = out.setdefault(int(m.group(1)), [0, 0.0, 0])
                row[at] += v["calls"]
                if at == 0:
                    row[1] += v["total_s"]
    return out


def read(record, trace):
    if not record.get("peaks") or not record.get("root"):
        return None
    path = os.path.join(record["root"], CONFIG)
    if not os.path.isfile(path):
        return None
    cfg = harness.load_json(path)
    found = chunks(trace, cfg)
    seconds = sum(s for _, s, _ in found.values())
    if not seconds:
        return None
    kernel = harness.load_module(record["root"], "kernels",
                                 "mla_sparse_prefill")
    chunk = cfg.get("q_chunk_size", 512)
    pairs = sum(rows * kernel.chunk_pairs(a - i, i, chunk,
                                          cfg["index_topk"])
                for rows, (a, _, i) in found.items())
    tokens = sum(rows * a * chunk for rows, (a, _, _) in found.items())
    least = kernel.least_seconds(
        pairs, tokens, cfg["num_attention_heads"], cfg["qk_head_dim"],
        cfg["v_head_dim"], record["geometry"]["itemsize"], record["peaks"])
    return 100.0 * least / seconds

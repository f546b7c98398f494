"""Share of the decode step's device time spent choosing the keys:
the index-score kernel (the custom call the program names
`dsa.indexer`, output `f32[slots, 1, keys]`) and the exact top-k after
it (`dsa.select`: the counting passes of the bisection, fusions with an
`s32[slots]` or `u32[slots]` output, and the masks and keys it builds,
ops with a `[slots, keys]` output), summed over the trace, over the
total of `_raw_decode_step`. The prefill's scoring and selection have
other shapes (`[rows, chunk, keys]`) and do not enter."""
import re

from benchmarks.lib import readers, trace_reduce

NAME, UNIT = "dsa.select_share_pct.open", "%"
LAYER, MOVES = "paged kernels", "tpot_p95_ms"
INDEXER = re.compile(r"^dsa\.indexer:custom-call:f32\[(\d+),1,(\d+)\]$")


def read(record, trace):
    step = trace_reduce.time_of(trace, "programs", readers.DECODE)
    found = [m for m in map(INDEXER.match, trace.get("ops", {})) if m]
    if not step or not step[1] or not found:
        return None
    slots, keys = found[0].groups()
    chosen = re.compile(
        rf"^dsa\.indexer:custom-call:f32\[{slots},1,{keys}\]$"
        rf"|\[{slots},{keys}\]$"
        rf"|_fusion:fusion:[su]32\[{slots}\]$")
    seconds = sum(v["total_s"] for n, v in trace["ops"].items()
                  if chosen.search(n))
    return 100.0 * seconds / step[1]

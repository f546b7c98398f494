"""Share of the prefill programs' device time spent choosing the keys:
a chunk's index scores (the custom call the program names
`dsa.indexer`, output `f32[rows, chunk, keys]`) and the exact top-k
after it (`dsa.select`: the bisection's counting passes and the masks it
builds), over the total of `_raw_prefill`. The model runs both inside
ONE conditional a chunk (it selects only where a query sees more than
`topk` keys), which the trace lists as an op of its own with the
selection's output, `conditional:...:pred[rows, chunk, keys]`, beside
the ops inside it: where it is there, its time is the answer; a program
without it is read by its pieces (the score kernel and the ops with a
`[rows, chunk, keys]` or `[su]32[rows, chunk]` output). The decode
step's scoring and selection have other shapes (`[slots, 1, keys]`,
`[slots, keys]`) and do not enter."""
import re

from benchmarks.lib import readers, trace_reduce

NAME, UNIT = "mla_dsa.prefill_select_share_pct.open", "%"
LAYER, MOVES = "prefill kernels", "ttft_p95_ms"
INDEXER = re.compile(r"^dsa\.indexer:custom-call:f32\[(\d+),(\d+),(\d+)\]$")


def read(record, trace):
    prefill = trace_reduce.time_of(trace, "programs", readers.PREFILL)
    found = {m.groups() for m in map(INDEXER.match, trace.get("ops", {}))
             if m and m.group(2) != "1"}
    if not prefill or not prefill[1] or not found:
        return None
    shapes = "|".join(rf"\[{n},{c},{s}\]" for n, c, s in found)
    whole = re.compile(rf"^conditional:conditional:pred(?:{shapes})$")
    pieces = re.compile(rf"(?:{shapes})$|" + "|".join(
        rf":[su]32\[{n},{c}\]$" for n, c, _ in found))
    ops = trace["ops"]
    chosen = [v for name, v in ops.items() if whole.search(name)] \
        or [v for name, v in ops.items() if pieces.search(name)]
    return 100.0 * sum(v["total_s"] for v in chosen) / prefill[1]

"""Share of the prefill programs' device time spent reading and mixing
the residual streams: the ops whose name starts `mhc.` with a prompt's
row count (`benchmarks/lib/mhc_ops.py`), summed over the trace, over the
total of `_raw_prefill`."""
from benchmarks.lib import mhc_ops, readers, trace_reduce

NAME, UNIT = "mhc.prefill_share_pct.open", "%"
LAYER, MOVES = "residual streams", "ttft_p95_ms"


def read(record, trace):
    prefill = trace_reduce.time_of(trace, "programs", readers.PREFILL)
    if not prefill or not prefill[1] or not record.get("geometry"):
        return None
    found, _ = mhc_ops.ops(trace, record["geometry"]["slots"])
    return 100.0 * mhc_ops.seconds(found) / prefill[1] if found else None

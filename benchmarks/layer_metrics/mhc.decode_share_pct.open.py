"""Share of the decode step's device time spent reading and mixing the
residual streams: the ops whose name starts `mhc.` with the slots' row
count (`benchmarks/lib/mhc_ops.py`), summed over the trace, over the
total of `_raw_decode_step`. A step's 32 rows are latency-bound work
beside the weights it reads."""
from benchmarks.lib import mhc_ops, readers, trace_reduce

NAME, UNIT = "mhc.decode_share_pct.open", "%"
LAYER, MOVES = "residual streams", "tpot_p95_ms"


def read(record, trace):
    step = trace_reduce.time_of(trace, "programs", readers.DECODE)
    if not step or not step[1] or not record.get("geometry"):
        return None
    _, found = mhc_ops.ops(trace, record["geometry"]["slots"])
    return 100.0 * mhc_ops.seconds(found) / step[1] if found else None

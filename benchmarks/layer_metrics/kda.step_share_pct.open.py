"""Share of the decode step's device time spent in the KDA layers'
own work: the ops of `_raw_decode_step` whose output leads with
`slots + 1` rows (`benchmarks/lib/kda_ops.py` `decode_ops`: the
convolution over the window, the gates, the norms, the columns laid out
for the state kernel and the `kda.state_update` kernel itself; only the
state pool has that extent), summed over the trace, over the total of
`_raw_decode_step`.
The KDA layers' projections (plain matmuls over the batch's 32 rows) are
not in it."""
from benchmarks.lib import kda_ops, readers, trace_reduce

NAME, UNIT = "kda.step_share_pct.open", "%"
LAYER, MOVES = "state-space kernels", "tpot_p95_ms"


def read(record, trace):
    step = trace_reduce.time_of(trace, "programs", readers.DECODE)
    if not step or not step[1] or not record.get("geometry"):
        return None
    # async copies are counted by their wait (`copy-done`), not twice
    ops = {n: v for n, v in kda_ops.decode_ops(
        trace, record["geometry"]["slots"]).items()
        if not n.startswith(("copy-start", "slice-start"))}
    if not ops:
        return None
    return 100.0 * sum(v["total_s"] for v in ops.values()) / step[1]

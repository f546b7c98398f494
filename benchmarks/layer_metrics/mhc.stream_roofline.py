"""Share of its roofline that the residual streams' traffic reaches in
the traced prefill programs: the least time the definition's bytes allow
(`benchmarks/kernels/mhc_stream.py`: a token a sublayer reads its
streams ONCE, writes them ONCE, writes the sublayer's input and reads
its output, (2n + 2) C numbers, whatever implements it and however it is
fused; bound by memory) for the tokens x sublayers the trace holds, over
the summed device time of every op whose name starts `mhc.` with a
prompt's row count (`benchmarks/lib/mhc_ops.py`). Two kernels that each
pass over the streams can reach (2n + 2) / (3n + 2) = 71 % at n = 4. The
sizes come from the cell's configuration file."""
import os

from benchmarks.lib import harness, mhc_ops

NAME, UNIT = "mhc.stream_roofline", "%"
LAYER, MOVES = "residual streams", "ttft_p95_ms"
CONFIG = "benchmarks/configs/xing4.0-29b-a4b-serve.json"


def read(record, trace):
    if not record.get("peaks") or not record.get("root") \
            or not record.get("geometry"):
        return None
    path = os.path.join(record["root"], CONFIG)
    if not os.path.isfile(path):
        return None
    g = record["geometry"]
    found, _ = mhc_ops.ops(trace, g["slots"])
    spent = mhc_ops.seconds(found)
    if not spent:
        return None
    cfg = harness.load_json(path)
    kernel = harness.load_module(record["root"], "kernels", "mhc_stream")
    least = kernel.least_seconds(
        mhc_ops.sublayer_tokens(found), cfg["hc_mult"], cfg["hidden_size"],
        g["itemsize"], record["peaks"])
    return 100.0 * least / spent

"""Which of a trace's ops are a Kimi Delta Attention layer's. The
compiler names a fusion after what it holds, not after the scope it was
traced under, so the readers tell the layer's ops by their shapes, which
nothing else in the program has:

- decode: the state pool has one row more than the batch, and the step's
  KDA tensors are padded to it. An op of `_raw_decode_step` whose output
  leads with `slots + 1` rows is a KDA layer's (convolution, gates,
  norms, the state update, which is a Pallas kernel and ALSO carries its
  scope's name, `kda.state_update`); no other tensor of the step has
  that extent.
- prefill: the chunked delta rule lays its tensors out [rows, heads,
  chunks, ...] ([chunks, rows, heads, ...] once stacked for the scan over
  chunks) with rows 1 or 2 (a prefill program takes at most two prompts)
  and every later extent a chunk's, a sub-chunk's or a head's (at most
  2 x d_k). Attention's [rows, heads, bucket, ...] has a bucket there.
"""
from __future__ import annotations

import re

SHAPE = re.compile(r":\w+\[([\d,]+)\]$")


def dims(op_name):
    m = SHAPE.search(op_name)
    return [int(d) for d in m.group(1).split(",")] if m else []


def decode_ops(trace, slots):
    """{name: entry} of the ops whose output leads with slots + 1 rows."""
    return {n: v for n, v in trace.get("ops", {}).items()
            if dims(n)[:1] == [slots + 1] and len(dims(n)) > 1}


def chunk_ops(trace, heads, d_k):
    """{name: (rows, entry)} of the prefill's chunked-recurrence ops."""
    out = {}
    for n, v in trace.get("ops", {}).items():
        d = dims(n)
        for at in (0, 1):
            if len(d) >= at + 4 and d[at] in (1, 2) and d[at + 1] == heads \
                    and all(x <= 2 * d_k for x in d[at + 2:]):
                out[n] = (d[at], v)
                break
    return out

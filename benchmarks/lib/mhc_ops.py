"""Which of a trace's ops are the hyper-connections' (a model with
several residual streams mixed at every sublayer:
`paddle_tpu/kernels/hyper_connections.py`). The program runs the maps
and the mixing as Pallas kernels under the scopes `mhc.pre` and
`mhc.post`, and a kernel's custom call carries its scope's name into
the trace: every op whose name starts `mhc.` is theirs, however a later
change fuses them, as long as it keeps the prefix. The first output of
each leads with the rows the call covered: a decode step's are the
slots, a prefill program's its prompt's bucket (one prompt a program),
which no cell makes as small as the slots.
"""
from __future__ import annotations

import re

OP = re.compile(r"^mhc\.[\w.\-]*:custom-call:\w+\[(\d+),(\d+)\]$")


def ops(trace, slots):
    """({name: (rows, entry)} of a prefill's ops, the same of a decode
    step's)."""
    prompt, step = {}, {}
    for name, v in trace.get("ops", {}).items():
        m = OP.match(name)
        if m:
            rows = int(m.group(1))
            (step if rows == slots else prompt)[name] = (rows, v)
    return prompt, step


def seconds(found):
    return sum(v["total_s"] for _, v in found.values())


def sublayer_tokens(found):
    """Tokens x sublayers that `found` covered: a sublayer is one
    `mhc.pre` and one `mhc.post` over its rows (a trace that cuts a
    program may hold one more of either: their mean)."""
    each = {}
    for name, (rows, v) in found.items():
        kind = name.split(":")[0]
        each[kind] = each.get(kind, 0) + rows * v["calls"]
    return sum(each.values()) / len(each) if each else 0

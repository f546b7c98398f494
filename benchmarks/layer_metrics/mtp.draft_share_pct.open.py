"""Share of the self-drafting tick's device time that the draft pass
takes: what speculation costs a tick whatever it yields; lower is better
at a given acceptance.

The tick is ONE program (`_raw_mtp_step`: the verify span through the
trunk, then the model's MTP module under the scope `mtp.draft`), and no
fused op carries a scope into the trace, so the draft pass is found by a
landmark in each run of the program: the program runs the head twice
(an op whose output is `[slots, span]` tokens, or their logits, and that
lasts at least half as long as reading the head's weights takes), the
trunk's to decide the span and the draft's last; everything after the
trunk's head is the draft pass. Read from the raw `numbers` trace (the
summary keeps no order), over every run of the program that lies whole
in it."""
import bisect
import os
import re

from benchmarks.lib import harness, stage_gaps, trace_reduce

NAME, UNIT = "mtp.draft_share_pct.open", "%"
LAYER, MOVES = "serve programs", "tpot_p95_ms"
CONFIG = "benchmarks/configs/openpangu-ultra-moe-718b-serve.json"
TICK = r"^_raw_mtp_step$"
SPAN = 2


def ticks(planes, tick, is_head):
    """[(seconds of one run of the program `tick`, seconds of it after
    its first head op)] for the runs that hold two head ops."""
    rx, out = re.compile(tick), []
    for p in planes:
        if not trace_reduce.DEVICE_PLANE.match(p["name"]):
            continue
        ops = sorted((s, s + d, n)
                     for n, s, d in p["lines"].get(trace_reduce.OPS_LINE, []))
        starts = [o[0] for o in ops]
        for name, s, d in p["lines"].get(trace_reduce.MODULES_LINE, []):
            if not rx.search(trace_reduce.program_name(name)):
                continue
            inside = ops[bisect.bisect_left(starts, s):
                         bisect.bisect_right(starts, s + d)]
            heads = [o for o in inside if is_head(o[2], o[1] - o[0])]
            if len(heads) == 2:
                out.append((d, s + d - heads[0][1]))
    return out


def head_test(slots, span, vocab, least_s):
    """An op that gives `[slots, span]` tokens (or their logits) and
    lasts at least half of `least_s`."""
    shape = re.compile(rf"\[({slots},{span}|{slots},{span},{vocab}|"
                       rf"{slots * span},{vocab})\]$")
    return lambda name, seconds: seconds >= 0.5 * least_s and bool(
        shape.search(trace_reduce.op_name(name)))


def read(record, trace):
    if not trace_reduce.time_of(trace, "programs", TICK) \
            or not record.get("peaks") or not record.get("root"):
        return None
    path = os.path.join(record["root"], CONFIG)
    numbers = stage_gaps.numbers_dir(record["root"])
    if not os.path.isfile(path) or numbers is None:
        return None
    cfg, g = harness.load_json(path), record["geometry"]
    least = cfg["hidden_size"] * cfg["vocab_size"] * g["itemsize"] \
        / record["peaks"]["hbm_bytes_per_s"]
    found = ticks(trace_reduce.load(numbers), TICK,
                  head_test(g["slots"], SPAN, cfg["vocab_size"], least))
    if not found or not sum(t for t, _ in found):
        return None
    return 100.0 * sum(d for _, d in found) / sum(t for t, _ in found)

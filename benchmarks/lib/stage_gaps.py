"""Arithmetic for the per-layer metrics that read what the program says
of itself: the serve loop's tick ring and the compile log (both stamped
on `time.perf_counter`, which is `benchmarks.lib.serve.clock`, so the
record's window cuts them), and the loop's stage annotations in the
`numbers` trace, on the profiler's clock beside the device's ops.

Readers run after the runner has returned and freed the predictor: the
ring and the log are process-wide and outlive it, and the trace is
still on disk. A program without the ring, the log or the annotations
(a parent commit) gives None, never an error.
"""
from __future__ import annotations

import bisect
import glob
import os

from benchmarks.lib import stats, trace_reduce

TICK = "serve.tick"
WAIT = "serve.resolve.wait"
_reduced = {}          # trace directory -> idle_by_stage (one entry)


def window(record):
    """(start, end) of the measured window on the benchmark's clock, or
    None. The runners keep the window's length (`window_s`); its start
    is the tracer's start, which waits for it (`trace_window`)."""
    w0 = record.get("w0")
    if w0 is None and record.get("trace_window"):
        w0 = record["trace_window"][0]
    if w0 is None or not record.get("window_s"):
        return None
    return float(w0), float(w0) + float(record["window_s"])


# ----------------------------------------------------------- tick ring --

def window_ticks(record):
    """The serve loop's tick records that began inside the window."""
    from paddle_tpu.observability import tracing
    read, win = getattr(tracing, "ticks", None), window(record)
    if read is None or win is None:
        return []
    return [t for t in read(since=win[0], until=win[1])
            if t.get("name") == TICK]


def host_seconds(tick):
    """A tick less its blocking read of the device's token."""
    return tick["dur"] - tick["stages"].get(WAIT, 0.0)


def tick_host_ms(record, q):
    """q-th percentile of the host time of the window's ticks, ms."""
    v = stats.percentile([host_seconds(t) for t in window_ticks(record)],
                         q)
    return None if v is None else v * 1e3


# --------------------------------------------------------- compile log --

def _compile_log(since, until):
    from paddle_tpu.observability import runtime
    read = getattr(runtime, "compile_log", None)
    return None if read is None else read(since=since, until=until)


def retraces_in_window(record):
    """Trace events JAX reported inside the window (the reference check
    compiles after it and does not count)."""
    win = window(record)
    log = _compile_log(*win) if win else None
    if log is None:
        return None
    return float(sum(1 for e in log if e["kind"] == "trace"))


def setup_seconds(record, kinds):
    """Seconds of the compile events of `kinds` received before the
    window's start."""
    win = window(record)
    log = _compile_log(None, win[0]) if win else None
    if log is None:
        return None
    return float(sum(e["seconds"] for e in log if e["kind"] in kinds))


# ------------------------------------------------------ idle, by stage --

def numbers_dir(root):
    """The `numbers` trace of the run being read: the harness clears a
    cell's trace before the run and after the readers, so one is there;
    the newest, should a killed run have left another behind."""
    found = glob.glob(os.path.join(root, "benchmarks", "out", "*",
                                   "trace", "numbers"))
    return max(found, key=os.path.getmtime) if found else None


def stage_name(event_name):
    """`serve.prefill#n=2,bucket=256#` -> `serve.prefill`."""
    return event_name.split("#", 1)[0]


def stage_intervals(planes):
    """{stage: merged [(start, end)]} of the `serve.*` annotations on
    the serve threads: the host lines that hold a `serve.tick`."""
    out = {}
    for p in planes:
        if trace_reduce.DEVICE_PLANE.match(p["name"]):
            continue
        for line in p["lines"].values():
            if not any(stage_name(n) == TICK for n, _, _ in line):
                continue
            for n, s, d in line:
                n = stage_name(n)
                if n.startswith("serve.") and d > 0:
                    out.setdefault(n, []).append((s, s + d))
    return {n: trace_reduce.union(v) for n, v in out.items()}


def device_gaps(planes):
    """(idle gaps [(start, end)], seconds of the devices' windows): a
    gap lies between two merged busy intervals of one device, a window
    runs from a device's first op to its last."""
    gaps, window_s = [], 0.0
    for p in planes:
        if not trace_reduce.DEVICE_PLANE.match(p["name"]):
            continue
        ops = p["lines"].get(trace_reduce.OPS_LINE) \
            or p["lines"].get(trace_reduce.MODULES_LINE, [])
        busy = trace_reduce.union([(s, s + d) for _, s, d in ops if d > 0])
        if busy:
            window_s += busy[-1][1] - busy[0][0]
            gaps += [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    return gaps, window_s


def overlap(gaps, intervals):
    """Seconds of `gaps` that lie inside `intervals` (merged, sorted)."""
    ends = [e for _, e in intervals]
    total = 0.0
    for gs, ge in gaps:
        i = bisect.bisect_right(ends, gs)
        while i < len(intervals) and intervals[i][0] < ge:
            total += min(ge, ends[i]) - max(gs, intervals[i][0])
            i += 1
    return total


def idle_by_stage(record):
    """`idle_of_planes` of the run's `numbers` trace, read once for the
    metrics that share it."""
    d = numbers_dir(record["root"]) if record.get("root") else None
    if d is None:
        return None
    if d not in _reduced:
        _reduced.clear()
        _reduced[d] = idle_of_planes(trace_reduce.load(d))
    return _reduced[d]


def idle_of_planes(planes):
    """{"idle_s", "window_s", "idle_in_ticks_s", "named_s", "by_stage":
    {stage: idle seconds under it, children included}}; None where the
    trace holds no device op or no stage annotation. `idle_in_ticks_s`
    is the idle time between the first recorded tick's start and the
    last one's end: a tick that began before the profiler did, or had
    not ended when it stopped, leaves no annotation, so the idle time
    of the trace's two edges can be under no stage."""
    stages = stage_intervals(planes)
    gaps, window_s = device_gaps(planes)
    if TICK not in stages or not window_s:
        return None
    everything = trace_reduce.union(
        [iv for v in stages.values() for iv in v])
    ticked = [(stages[TICK][0][0], stages[TICK][-1][1])]
    return {"idle_s": sum(e - s for s, e in gaps), "window_s": window_s,
            "idle_in_ticks_s": overlap(gaps, ticked),
            "named_s": overlap(gaps, everything),
            "by_stage": {n: overlap(gaps, v) for n, v in stages.items()}}


def idle_named_pct(record):
    """Share of the device's idle seconds, from the first recorded tick
    to the last, that lie under a `serve.*` annotation of a serve
    thread."""
    r = idle_by_stage(record)
    if r is None or not r["idle_in_ticks_s"]:
        return None
    return 100.0 * r["named_s"] / r["idle_in_ticks_s"]


def idle_under_pct(record, stage):
    """Idle seconds under `stage` over the device's window."""
    r = idle_by_stage(record)
    if r is None:
        return None
    return 100.0 * r["by_stage"].get(stage, 0.0) / r["window_s"]

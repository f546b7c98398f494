"""From a profiler trace (`*.xplane.pb`) to the numbers the per-layer
metrics read. The reading follows `paddle_tpu/profiler`'s `_TraceStats`
(`jax.profiler.ProfileData`); the reduction is the benchmark's own.

A device is a plane named `/device:TPU:<n>`. Its line `XLA Ops` holds
one event for every operation the device ran, and `XLA Modules` one for
every run of a compiled program. Busy time is the UNION of the op
intervals of a device (ops never overlap on one TPU core, but the union
is what "an operation ran" means). The window is the DEVICE's: from its
first op's start to its last op's end. The host's planes start earlier
and end later (the profiler records host threads while it starts and
stops, with no device line recording), so their extent is no window for
a device number. Idle time is the window less the busy time, which is
the sum of the gaps between busy intervals.

A traced run may hold two traces, one after the other: `numbers/`, taken
with the Python tracer off, which every number is read from, and
`names/`, taken with it on (it slows a serve loop's tick by about a
quarter), read only to name the idle gaps by the host's frames.
"""
from __future__ import annotations

import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all")


def load(trace_dir):
    """[{name, lines: {line name: [(event name, start_s, dur_s)]}}]"""
    import jax
    planes = []
    for pb in sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                               recursive=True)):
        for plane in jax.profiler.ProfileData.from_file(pb).planes:
            lines = {}
            for line in plane.lines:
                evs = [(ev.name, float(ev.start_ns) * 1e-9,
                        float(ev.duration_ns or 0.0) * 1e-9)
                       for ev in line.events]
                if evs:     # threads may share a name: keep them apart
                    key, k = line.name, 1
                    while key in lines:
                        k += 1
                        key = f"{line.name}#{k}"
                    lines[key] = evs
            planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals):
    """Merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


HLO = re.compile(r"^%?([\w.\-]+?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])?.*? "
                 r"([\w\-]+)\(")


def op_name(event_name):
    """An op event on a TPU is named by its whole HLO instruction,
    `%fusion.12 = bf16[32,4096]{...} fusion(...)`. Shortened to
    `fusion:fusion:bf16[32,4096]` (name without its number, opcode,
    shape of the first output), so that the runs of one instruction in
    every layer fall under one name."""
    m = HLO.match(event_name)
    if not m:
        return event_name[:80]
    return f"{m.group(1)}:{m.group(3)}:{m.group(2) or ''}"


def program_name(event_name):
    """`jit__raw_decode_step(123)` -> `_raw_decode_step`."""
    name = event_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def _by_name(events, key=lambda n: n):
    table = {}
    for name, _, dur in events:
        table.setdefault(key(name), []).append(dur)
    return {n: {"calls": len(d), "total_s": sum(d),
                "median_s": statistics.median(d)}
            for n, d in table.items()}


DISPATCH = re.compile(r"_dispatch_step|_dispatch_mixed_step|_serve\b")


def _host_events(planes):
    """Events of the host threads that dispatch device work (those with
    an event named like the serve loop's dispatch); of every host thread
    where none is found."""
    lines = [line for p in planes if not DEVICE_PLANE.match(p["name"])
             for line in p["lines"].values()]
    driving = [line for line in lines
               if any(DISPATCH.search(n) for n, _, _ in line)]
    return [e for line in (driving or lines) for e in line if e[2] > 0]


def _name_gaps(gaps, host, limit=10):
    """Idle gaps summed by what the host was doing: for each gap the
    innermost (shortest) host event that covers half of it or more,
    else the event that overlaps most of it, else `no host event`."""
    import bisect
    table = {}
    if not gaps:
        return []
    # an event shorter than half the shortest gap names none of them, and
    # a frame that lasts a quarter of a second says nothing about one
    shortest = min(ge - gs for gs, ge in gaps)
    host = sorted((e for e in host if 0.5 * shortest <= e[2] <= 0.25),
                  key=lambda e: e[1])
    starts = [e[1] for e in host]
    longest = max((e[2] for e in host), default=0.0)
    for gs, ge in gaps:
        lo = bisect.bisect_left(starts, gs - longest)
        hi = bisect.bisect_right(starts, ge)
        cover, most = None, ("no host event", 0.0)
        for n, s, d in host[lo:hi]:
            ov = min(ge, s + d) - max(gs, s)
            if ov <= 0:
                continue
            if ov >= 0.5 * (ge - gs) and (cover is None or d < cover[1]):
                cover = (n, d)
            if ov > most[1]:
                most = (n, ov)
        name = cover[0] if cover else most[0]
        table[name] = table.get(name, 0.0) + (ge - gs)
    return sorted(([n, s] for n, s in table.items()),
                  key=lambda x: -x[1])[:limit]


def _busy(plane):
    """Merged busy intervals of one device plane."""
    ops = plane["lines"].get(OPS_LINE, [])
    mods = plane["lines"].get(MODULES_LINE, [])
    return union([(s, s + d) for _, s, d in (ops or mods) if d > 0])


def idle_gaps(planes, limit=10):
    """The longest idle gaps of the devices in `planes`, summed by what
    the host was doing: [[name, seconds]], at most `limit`."""
    gaps = []
    for p in planes:
        if DEVICE_PLANE.match(p["name"]):
            busy = _busy(p)
            gaps += [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:500]   # the longest
    return _name_gaps(gaps, _host_events(planes), limit)


def reduce_planes(planes):
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    per_device, spans, ops_all, mods_all, exposed = [], [], [], [], []
    for p in devices:
        ops = p["lines"].get(OPS_LINE, [])
        busy = _busy(p)
        per_device.append(sum(e - s for s, e in busy))
        if busy:
            spans.append((busy[0][0], busy[-1][1]))
        ops_all += ops
        mods_all += p["lines"].get(MODULES_LINE, [])
        coll = [(s, s + d) for n, s, d in ops
                if COLLECTIVE.search(op_name(n))]
        comp = union([(s, s + d) for n, s, d in ops
                      if not COLLECTIVE.search(op_name(n)) and d > 0])
        exposed.append(_uncovered(union(coll), comp))
    n_dev = max(len(devices), 1)
    ops_t = _by_name(ops_all, op_name)
    return {
        "planes": [{"name": p["name"],
                    "lines": {n: len(v) for n, v in p["lines"].items()}}
                   for p in planes],
        "devices": len(devices),
        # first op's start to last op's end, over the devices
        "window_s": (max(e for _, e in spans) - min(s for s, _ in spans))
        if spans else 0.0,
        "busy_s": sum(per_device) / n_dev,
        "busy_s_per_device": per_device,
        "collective_exposed_s": sum(exposed) / n_dev,
        "programs": _by_name(mods_all, program_name),
        "ops": ops_t,
        "breakdown": {
            "device_ops": sorted(([n, v["total_s"] / n_dev]
                                  for n, v in ops_t.items()),
                                 key=lambda x: -x[1])[:10],
            "idle_gaps": idle_gaps(planes)},
    }


def _uncovered(intervals, cover):
    """Seconds of `intervals` not covered by `cover` (both merged)."""
    total, j = 0.0, 0
    for s, e in intervals:
        cur = s
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > cur:
                total += cover[k][0] - cur
            cur = max(cur, cover[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def head(planes, seconds):
    """The events of the first `seconds` after the first device op, for
    a look by hand and for the recorded trace in `tests/data/`."""
    starts = [s for p in planes if DEVICE_PLANE.match(p["name"])
              for _, s, _ in p["lines"].get(OPS_LINE, [])]
    if not starts:
        return []
    t0 = min(starts)
    return [{"name": p["name"],
             "lines": {n: [[e[0][:200], e[1] - t0, e[2]] for e in line
                           if t0 <= e[1] and e[1] + e[2] <= t0 + seconds]
                       for n, line in p["lines"].items()}}
            for p in planes]


def reduce_dir(trace_dir, head_seconds=0.0):
    """Reduce the trace under `trace_dir/numbers`; where `trace_dir/names`
    holds a second trace, the idle gaps are that one's, and its own busy
    time and window stand beside them (`names_trace`)."""
    planes = load(os.path.join(trace_dir, "numbers"))
    reduced = reduce_planes(planes)
    names = load(os.path.join(trace_dir, "names"))
    if names:
        with_python = reduce_planes(names)
        reduced["breakdown"]["idle_gaps"] = \
            with_python["breakdown"]["idle_gaps"]
        reduced["names_trace"] = {k: with_python[k]
                                  for k in ("busy_s", "window_s")}
    if head_seconds:
        reduced["head"] = head(planes, head_seconds)
    return reduced


def time_of(reduced, table, pattern):
    """(calls, total seconds, median seconds) of the entries of
    `reduced[table]` whose name matches `pattern`; None if none does."""
    rx = re.compile(pattern)
    hit = [v for n, v in reduced[table].items() if rx.search(n)]
    if not hit:
        return None
    calls = sum(v["calls"] for v in hit)
    total = sum(v["total_s"] for v in hit)
    return calls, total, statistics.median(v["median_s"] for v in hit)

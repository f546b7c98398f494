"""Arithmetic shared by the per-layer metric readers. A reader gets the
runner's record and the reduced trace and returns a number, or None
where it finds nothing to read (the harness then leaves the metric out).
"""
from __future__ import annotations

from benchmarks.lib import stats, trace_reduce

DECODE = r"_raw_decode_step"
PREFILL = r"_raw_prefill|_raw_suffix_prefill"


def occupancy_pct(record, trace):
    s = (record.get("occupancy") or {}).get("occupancy")
    return 100.0 * sum(s) / len(s) if s else None


def decode_ms(record, trace):
    hit = trace_reduce.time_of(trace, "programs", DECODE)
    return hit[2] * 1e3 if hit else None


def idle_pct(record, trace):
    if not trace["window_s"] or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def p95(values, scale=1.0):
    v = stats.percentile(values or [], 95)
    return None if v is None else v * scale

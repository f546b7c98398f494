"""Arithmetic for the per-layer metrics that read the serve loop's own
account of its prefills and of the tokens it handed out, and the
collector's pauses: all from the tick ring and the gc log, stamped on
`time.perf_counter` (`benchmarks.lib.serve.clock`), over the ticks that
began in the WHOLE window, not the traced seconds.

A tick's record carries `tokens` (tokens the pass handed to the
streams) and `first` (how many of them were a request's first: they
close no gap), and a pass that ran a prefill carries beside them
`pf_s` (seconds from a prefill's dispatch to its first tokens on the
host, summed), `pf_tokens` (prompt tokens forwarded), `pf_padded`
(rows x bucket: positions computed) and, for chunks of a chunked
prefill taken in a mixed step, `pf_chunk` (their share of `pf_tokens`;
such a chunk has no seconds of its own). By the loop's order the tokens
a pass hands out after it ran a prefill are the tokens whose gap held
that prefill.

A program without the fields or the log (a parent commit) gives None,
never an error.
"""
from __future__ import annotations

from benchmarks.lib import stage_gaps, stats

FIELDS = ("pf_n", "pf_s", "pf_tokens", "pf_padded", "pf_chunk",
          "pf_stalled", "tokens", "first")


def sums(record):
    """{field: sum over the window's ticks} with `window_s`,
    `stalled_tokens` (tokens that closed a gap, handed out by a pass
    that ran a prefill), `gap_tokens` (all that closed a gap) and
    `stalls_s` (a stalled token's wait: its pass's `pf_s`, once a
    token); None where no tick of the window says what it handed out."""
    ticks = stage_gaps.window_ticks(record)
    if not any("tokens" in t for t in ticks):
        return None
    out = {f: sum(t.get(f, 0) for t in ticks) for f in FIELDS}
    out["window_s"] = float(record["window_s"])
    out["gap_tokens"] = out["tokens"] - out["first"]
    out["stalls_s"] = []
    for t in ticks:
        if t.get("pf_s", 0) > 0:
            out["stalls_s"] += [t["pf_s"]] * (t.get("tokens", 0)
                                              - t.get("first", 0))
    out["stalled_tokens"] = len(out["stalls_s"])
    return out


def wall_share_pct(record):
    """Seconds between a prefill's dispatch and its first tokens, over
    the window's seconds."""
    s = sums(record)
    return None if s is None else 100.0 * s["pf_s"] / s["window_s"]


def us_per_token(record):
    """Prefill seconds a prompt token forwarded; nothing once chunks of
    a chunked prefill were counted (their seconds are the step's)."""
    s = sums(record)
    if s is None or s["pf_chunk"] or not s["pf_tokens"]:
        return None
    return 1e6 * s["pf_s"] / s["pf_tokens"]


def pad_pct(record):
    """Share of the positions the prefill programs computed that held
    no prompt token."""
    s = sums(record)
    if s is None or not s["pf_padded"]:
        return None
    return 100.0 * (1.0 - s["pf_tokens"] / s["pf_padded"])


def stalled_token_pct(record):
    """Share of the token gaps that held a prefill."""
    s = sums(record)
    if s is None or not s["gap_tokens"]:
        return None
    return 100.0 * s["stalled_tokens"] / s["gap_tokens"]


def decode_stall_p95_ms(record):
    """95th percentile, over the stalled tokens, of the prefill seconds
    of the pass that handed them out."""
    s = sums(record)
    v = stats.percentile(s["stalls_s"], 95) if s else None
    return None if v is None else v * 1e3


def gc_pause_max_ms(record):
    """The longest collection that began in the window, 0 where none
    was logged."""
    from paddle_tpu.observability import runtime
    read, win = getattr(runtime, "gc_log", None), stage_gaps.window(record)
    if read is None or win is None:
        return None
    return 1e3 * max((e["seconds"] for e in read(since=win[0],
                                                 until=win[1])),
                     default=0.0)

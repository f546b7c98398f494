#!/usr/bin/env python3
"""trace_replay.py — production-shaped load traces for the serving bench.

Every serving bench so far drives uniform synthetic floods; production
traffic is nothing like that — session popularity is zipf (a few hot
prefixes dominate), arrival rate ramps diurnally and spikes, tenants
mix interactive and batch, and prompt/output lengths are long-tailed.
This tool closes the gap in both directions:

- ``synth``  — generate a trace from a shape spec (zipf sessions,
  diurnal ramp, tenant mix, lognormal prompt/output lengths, an
  optional prefill-heavy load spike).
- ``fit``    — estimate that shape spec from recorded telemetry (the
  ``router.request`` / ``serve.request`` spans a real deployment
  already writes), then synthesize a matching trace: replayable
  production traffic without shipping production prompts.
- ``show``   — summarize a trace file.
- ``timeline`` — rebuild the control-loop decision timeline from the
  ``{"kind": "control"}`` records in a telemetry file;
  tests/test_trace_replay.py asserts it matches the live pool.

Trace format (JSONL): one ``{"kind": "trace_header"}`` line with the
spec, then one ``{"kind": "trace_request"}`` line per request with
arrival offset ``t`` (seconds from trace start), ``session``, ``tier``,
``prompt_len``, ``max_new`` and ``phase`` ("base" | "spike"). A replay
(tests/test_trace_replay.py drives one) submits each request to a
Router at its arrival offset: prompts are derived
deterministically from the session id so same-session requests share a
prefix and exercise the router's affinity path.

Stdlib-only by design (`python -I` clean) — it must run where the
telemetry landed, not where the stack is installed.
"""
from __future__ import annotations

import argparse
import json
import math
import random
import sys
from typing import Dict, List, Optional

DEFAULT_SPEC = {
    "requests": 200,
    "duration_s": 20.0,
    "sessions": 32,
    "zipf_alpha": 1.1,
    "tiers": {"interactive": 0.5, "batch": 0.5},
    "prompt_len_p50": 24,
    "prompt_len_sigma": 0.6,
    "max_new_p50": 8,
    "max_new_sigma": 0.5,
    "prompt_len_max": 256,
    "max_new_max": 64,
    "diurnal": 0.3,        # peak-to-mean rate modulation, 0 disables
    "spike": None,         # {"start_frac","dur_frac","factor","tier",
    "seed": 0,             #  "prompt_len_factor"}
}


# --------------------------------------------------------------- synth --
def _zipf_weights(n: int, alpha: float) -> List[float]:
    w = [1.0 / math.pow(r, alpha) for r in range(1, n + 1)]
    s = sum(w)
    return [x / s for x in w]


def _lognormal(rng: random.Random, p50: float, sigma: float,
               lo: int, hi: int) -> int:
    v = p50 * math.exp(rng.gauss(0.0, sigma))
    return max(lo, min(int(round(v)), hi))


def _pick(rng: random.Random, weighted: Dict[str, float]) -> str:
    r = rng.random() * sum(weighted.values())
    for k, w in weighted.items():
        r -= w
        if r <= 0:
            return k
    return next(iter(weighted))


def synthesize(spec: Optional[dict] = None) -> List[dict]:
    """Generate trace_request dicts (sorted by arrival offset) from a
    shape spec; unspecified fields take DEFAULT_SPEC values."""
    s = dict(DEFAULT_SPEC)
    s.update(spec or {})
    rng = random.Random(int(s.get("seed", 0)))
    n = int(s["requests"])
    dur = float(s["duration_s"])
    spike = s.get("spike") or None

    # arrival process: weight time bins by the diurnal curve plus the
    # spike factor, spread the request budget proportionally, jitter
    # within the bin — deterministic for a given seed
    bins = max(int(n), 10)
    weights = []
    for i in range(bins):
        frac = (i + 0.5) / bins
        w = 1.0 + float(s["diurnal"]) * math.sin(2 * math.pi * frac)
        if spike:
            lo = float(spike["start_frac"])
            hi = lo + float(spike["dur_frac"])
            if lo <= frac < hi:
                w *= float(spike.get("factor", 3.0))
        weights.append(max(w, 1e-6))
    total_w = sum(weights)

    zipf = _zipf_weights(int(s["sessions"]), float(s["zipf_alpha"]))
    session_ids = list(range(int(s["sessions"])))
    out: List[dict] = []

    def _emit(frac: float):
        t = frac * dur
        in_spike = bool(spike
                        and float(spike["start_frac"]) <= frac
                        < float(spike["start_frac"])
                        + float(spike["dur_frac"]))
        # the spike is EXTRA load from the spike tier riding on top
        # of base traffic, which continues at its usual rate: the
        # 1/factor fraction of spike-window arrivals that the base
        # rate accounts for keeps the base tier mix, the excess is
        # the flood
        factor = float(spike.get("factor", 3.0)) if spike else 1.0
        if (in_spike and spike.get("tier")
                and (factor <= 1.0
                     or rng.random() >= 1.0 / factor)):
            tier = str(spike["tier"])
        else:
            tier = _pick(rng, s["tiers"])
        plen = _lognormal(rng, float(s["prompt_len_p50"]),
                          float(s["prompt_len_sigma"]), 4,
                          int(s["prompt_len_max"]))
        if in_spike:
            plen = min(int(plen
                           * float(spike.get("prompt_len_factor",
                                             2.0))),
                       int(s["prompt_len_max"]))
        out.append({
            "kind": "trace_request",
            "t": round(t, 4),
            "session": rng.choices(session_ids, weights=zipf)[0],
            "tier": tier,
            "prompt_len": plen,
            "max_new": _lognormal(rng, float(s["max_new_p50"]),
                                  float(s["max_new_sigma"]), 1,
                                  int(s["max_new_max"])),
            "phase": "spike" if in_spike else "base",
        })

    budget = 0.0
    for i, w in enumerate(weights):
        budget += n * w / total_w
        while budget >= 1.0 and len(out) < n:
            budget -= 1.0
            _emit((i + rng.random()) / bins)
    while len(out) < n:
        # float accumulation can leave the budget a hair under the
        # request count — top up at weighted-random arrival times
        i = rng.choices(range(bins), weights=weights)[0]
        _emit((i + rng.random()) / bins)
    out.sort(key=lambda r: r["t"])
    return out


def write_trace(path: str, reqs: List[dict],
                spec: Optional[dict] = None):
    s = dict(DEFAULT_SPEC)
    s.update(spec or {})
    with open(path, "w") as f:
        hdr = {"kind": "trace_header", "version": 1,
               "requests": len(reqs), "spec": s}
        f.write(json.dumps(hdr) + "\n")
        for r in reqs:
            f.write(json.dumps(r) + "\n")


def load_trace(path: str):
    """(header, requests) — tolerates a missing header and a torn
    final line (a live file mid-write)."""
    header, reqs = None, []
    with open(path) as f:
        for ln in f:
            ln = ln.strip()
            if not ln:
                continue
            try:
                rec = json.loads(ln)
            except ValueError:
                continue
            k = rec.get("kind")
            if k == "trace_header":
                header = rec
            elif k == "trace_request":
                reqs.append(rec)
    reqs.sort(key=lambda r: r["t"])
    return header, reqs


def session_prompt(session: int, prompt_len: int,
                   vocab: int = 1000) -> List[int]:
    """Deterministic prompt for a session: a shared per-session prefix
    (half the prompt, capped) + a request-unique tail, so same-session
    requests hit the router's prefix-affinity path the way repeated
    conversations do."""
    rng = random.Random(1000003 * (session + 1))
    shared = [rng.randrange(2, vocab) for _ in range(prompt_len)]
    keep = max(prompt_len // 2, 1)
    tail_rng = random.Random(rng.random())
    return shared[:keep] + [tail_rng.randrange(2, vocab)
                            for _ in range(prompt_len - keep)]


# ----------------------------------------------------------------- fit --
def fit_from_telemetry(paths: List[str]) -> dict:
    """Estimate a shape spec from recorded router.request /
    serve.request spans. Only the SHAPE is kept (rate, tenant mix,
    length percentiles) — prompt content never leaves the deployment."""
    starts, plens, tokens = [], [], []
    tiers: Dict[str, float] = {}
    for path in paths:
        with open(path) as f:
            for ln in f:
                try:
                    rec = json.loads(ln)
                except ValueError:
                    continue
                if rec.get("kind") != "span" or rec.get("name") not in (
                        "router.request", "serve.request"):
                    continue
                labels = rec.get("labels", {})
                starts.append(float(rec.get("start", 0.0)))
                if "prompt_len" in labels:
                    plens.append(int(labels["prompt_len"]))
                t = labels.get("tier")
                if t:
                    tiers[t] = tiers.get(t, 0.0) + 1.0
                for ev in rec.get("events", []):
                    if ev.get("name") == "finish" and "tokens" in ev:
                        tokens.append(int(ev["tokens"]))
    spec = dict(DEFAULT_SPEC)
    if starts:
        spec["requests"] = len(starts)
        spec["duration_s"] = round(
            max(max(starts) - min(starts), 1.0), 3)
    if plens:
        plens.sort()
        spec["prompt_len_p50"] = plens[len(plens) // 2]
        spec["prompt_len_max"] = plens[-1]
    if tokens:
        tokens.sort()
        spec["max_new_p50"] = max(tokens[len(tokens) // 2], 1)
        spec["max_new_max"] = max(tokens[-1], 1)
    if tiers:
        total = sum(tiers.values())
        spec["tiers"] = {k: round(v / total, 4)
                         for k, v in sorted(tiers.items())}
    return spec


# ------------------------------------------------- control timeline --
def rebuild_timeline(records: List[dict]) -> dict:
    """Reconstruct the controller's state evolution purely from its
    ``{"kind": "control"}`` audit records — the acceptance test for
    "auditable from the JSONL alone". Returns the final pool size,
    tier weights and shed set plus the ordered action list; raises
    ValueError when the records cannot be replayed consistently
    (missing init, out-of-order seq, pool-size mismatch)."""
    ctrl = sorted((r for r in records if r.get("kind") == "control"),
                  key=lambda r: r.get("seq", 0))
    if not ctrl:
        raise ValueError("no control records")
    if ctrl[0].get("rule") != "init":
        raise ValueError("control stream does not start at init")
    seqs = [r.get("seq") for r in ctrl]
    if seqs != list(range(seqs[0], seqs[0] + len(seqs))):
        raise ValueError(f"gap in control seq numbers: {seqs}")
    init = ctrl[0]["params"]
    pool = int(init["pool"])
    weights = dict(init.get("tier_weights") or {})
    shed = set(init.get("shed_tiers") or ())
    actions = []
    for rec in ctrl[1:]:
        rule, action, p = rec["rule"], rec["action"], rec["params"]
        if rule == "scale_out":
            if int(p["pool_before"]) != pool:
                raise ValueError(
                    f"seq {rec['seq']}: pool_before {p['pool_before']} "
                    f"!= replayed {pool}")
            pool = int(p["pool_after"])
        elif rule == "scale_in":
            if int(p["pool_before"]) != pool:
                raise ValueError(
                    f"seq {rec['seq']}: pool_before {p['pool_before']} "
                    f"!= replayed {pool}")
            pool = int(p["pool_after"])
        elif rule == "shift_quantum":
            weights[rec["tier"]] = float(p["weight_after"])
        elif rule == "shed":
            if action == "shed_on":
                shed.update(p["shed_tiers"])
            else:
                shed.clear()
        actions.append({"seq": rec["seq"], "tick": rec.get("tick"),
                        "rule": rule, "action": action,
                        "tier": rec.get("tier"),
                        "pool": pool})
    return {"pool_size": pool, "tier_weights": weights,
            "shed_tiers": sorted(shed), "actions": actions,
            "decisions": len(actions)}


def _read_records(path: str) -> List[dict]:
    out = []
    with open(path) as f:
        for ln in f:
            try:
                out.append(json.loads(ln))
            except ValueError:
                continue
    return out


# ----------------------------------------------------------------- CLI --
def _summarize(header, reqs) -> str:
    lines = [f"trace: {len(reqs)} requests"]
    if header:
        spec = header.get("spec", {})
        lines.append(f"  spec: duration={spec.get('duration_s')}s "
                     f"sessions={spec.get('sessions')} "
                     f"zipf_alpha={spec.get('zipf_alpha')}")
    if reqs:
        by_tier: Dict[str, int] = {}
        by_phase: Dict[str, int] = {}
        for r in reqs:
            by_tier[r["tier"]] = by_tier.get(r["tier"], 0) + 1
            by_phase[r["phase"]] = by_phase.get(r["phase"], 0) + 1
        span = reqs[-1]["t"] - reqs[0]["t"]
        plens = sorted(r["prompt_len"] for r in reqs)
        lines.append(f"  arrivals over {span:.2f}s  "
                     f"tiers={by_tier}  phases={by_phase}")
        lines.append(f"  prompt_len p50={plens[len(plens) // 2]} "
                     f"max={plens[-1]}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="trace_replay.py",
        description="synthesize / fit / inspect serving load traces")
    sub = ap.add_subparsers(dest="cmd", required=True)

    syn = sub.add_parser("synth", help="generate a trace from a spec")
    syn.add_argument("--out", required=True)
    syn.add_argument("--requests", type=int)
    syn.add_argument("--duration", type=float)
    syn.add_argument("--sessions", type=int)
    syn.add_argument("--zipf-alpha", type=float)
    syn.add_argument("--seed", type=int)
    syn.add_argument("--tiers", help="name=frac,name=frac")
    syn.add_argument("--spike",
                     help="start_frac,dur_frac,factor[,tier"
                          "[,prompt_len_factor]]")

    fit = sub.add_parser("fit", help="fit a spec from telemetry spans "
                                     "and synthesize a matching trace")
    fit.add_argument("telemetry", nargs="+")
    fit.add_argument("--out", required=True)
    fit.add_argument("--seed", type=int)

    show = sub.add_parser("show", help="summarize a trace file")
    show.add_argument("trace")

    tl = sub.add_parser("timeline",
                        help="rebuild the control-decision timeline "
                             "from telemetry JSONL")
    tl.add_argument("telemetry")

    a = ap.parse_args(argv)
    if a.cmd == "synth":
        spec = {}
        if a.requests is not None:
            spec["requests"] = a.requests
        if a.duration is not None:
            spec["duration_s"] = a.duration
        if a.sessions is not None:
            spec["sessions"] = a.sessions
        if a.zipf_alpha is not None:
            spec["zipf_alpha"] = a.zipf_alpha
        if a.seed is not None:
            spec["seed"] = a.seed
        if a.tiers:
            spec["tiers"] = {k: float(v) for k, v in
                             (kv.split("=") for kv in
                              a.tiers.split(","))}
        if a.spike:
            parts = a.spike.split(",")
            spike = {"start_frac": float(parts[0]),
                     "dur_frac": float(parts[1]),
                     "factor": float(parts[2])}
            if len(parts) > 3 and parts[3]:
                spike["tier"] = parts[3]
            if len(parts) > 4:
                spike["prompt_len_factor"] = float(parts[4])
            spec["spike"] = spike
        reqs = synthesize(spec)
        write_trace(a.out, reqs, spec)
        print(_summarize({"spec": {**DEFAULT_SPEC, **spec}}, reqs))
        return 0
    if a.cmd == "fit":
        spec = fit_from_telemetry(a.telemetry)
        if a.seed is not None:
            spec["seed"] = a.seed
        reqs = synthesize(spec)
        write_trace(a.out, reqs, spec)
        print(_summarize({"spec": spec}, reqs))
        return 0
    if a.cmd == "show":
        header, reqs = load_trace(a.trace)
        print(_summarize(header, reqs))
        return 0
    if a.cmd == "timeline":
        try:
            t = rebuild_timeline(_read_records(a.telemetry))
        except ValueError as e:
            print(f"timeline: {e}", file=sys.stderr)
            return 1
        print(json.dumps(t, indent=2))
        return 0
    return 2


if __name__ == "__main__":
    sys.exit(main())

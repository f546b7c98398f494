#!/usr/bin/env python
"""Reconstruct request/step timelines and SLO percentiles from spans.

Reads the `{"kind": "span"}` lines that paddle_tpu.observability.tracing
writes into the telemetry JSONL (same file as the metric samples) or a
flight-recorder dump (`flight_<pid>.json`, written to
`$PADDLE_TPU_FLIGHT_DIR`, default `output/` — see docs/OBSERVABILITY.md
"Flight recorder"), and renders:

- **SLO percentiles** — TTFT, per-token latency, end-to-end request
  latency (from `serve.request` spans and their events) and train step
  time (from `train.step` spans): p50 / p90 / p99 / max.
- **Per-request timelines** — the slowest N requests with queue wait,
  TTFT, token count, status; `--request ID` takes a trace id OR a
  request_id label and renders the request's full cross-role waterfall
  (every span of the trace — router admission, prefill replica, decode
  replica — indented under its parent, events inline) plus the
  critical-path stage decomposition (admission / queue / prefill /
  handoff legs / decode / flush, telescoping so the stages sum to the
  measured TTFT and E2E). Falls back to the flat serve.request event
  timeline when the id doesn't resolve to a trace.
- **Per-step waterfalls** — train.step spans with their data / dispatch
  / loss-sync child phases as aligned bars.
- **Site table** — duration stats per span name (every instrumented
  site: serve.*, train.*, ckpt.*, dist.compile, comm.*, launch.epoch,
  launch.recovery).
- **Recovery timeline** (`--recovery`) — the hang→kill→restart→resume
  incident reconstruction: the wedged rank's last heartbeat, the
  stale-heartbeat detector's kill, the restart epoch, the resume step,
  and the measured MTTR, from launch.* spans plus heartbeat JSONL
  (`--heartbeat <log_dir>/heartbeat_rank0.jsonl`, repeatable).

    python tools/trace_report.py telemetry.jsonl
    python tools/trace_report.py telemetry.jsonl --requests 10
    python tools/trace_report.py telemetry.jsonl --request req3
    python tools/trace_report.py output/flight_1234.json --chrome trace.json
    python tools/trace_report.py telemetry.jsonl --recovery \
        --heartbeat log/heartbeat_rank0.jsonl
    # fleet output: several per-rank files, or a whole launcher log dir
    python tools/trace_report.py log/telemetry_rank*.jsonl
    python tools/trace_report.py --dir log/

Multiple inputs (or ``--dir`` with a launcher log directory of
``telemetry_rank<k>.jsonl`` files) merge into one span pool — rotated
``.1`` siblings are folded in per file; with ``--recovery`` a
directory's ``heartbeat_rank*.jsonl`` files join automatically. For
the cross-rank views (step skew, stragglers, comm balance) see
``tools/fleet_report.py``.

No paddle_tpu import needed — this runs anywhere there is a file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional


def _load_critpath():
    """The stage-decomposition analyzer, loaded straight off its file
    (paddle_tpu/observability/critpath.py is stdlib-only by contract)
    so this tool never imports the paddle_tpu package (which pulls
    jax). Returns None when the file isn't beside this checkout —
    the waterfall still renders, just without the stage table."""
    import importlib.util
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(os.path.dirname(here), "paddle_tpu",
                        "observability", "critpath.py")
    if not os.path.exists(path):
        return None
    try:
        spec = importlib.util.spec_from_file_location(
            "_pt_critpath", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    except Exception:
        return None


# ---------------------------------------------------------------- loading --
def _warn_torn(path: str, line: str):
    """Crash-time telemetry ends mid-record (the process died between
    write() and the line's newline): skip it loudly instead of
    raising — everything before the torn line is intact."""
    print(f"warning: {path}: skipping torn final line "
          f"({len(line)} bytes) — truncated mid-record "
          "(crash-time telemetry)", file=sys.stderr)


def _jsonl_records(path: str) -> List[dict]:
    """Parsed records of one JSONL file; a torn final line warns and
    is skipped, interior garbage is skipped silently."""
    with open(path) as f:
        lines = f.read().splitlines()
    out = []
    for i, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        try:
            out.append(json.loads(line))
        except json.JSONDecodeError:
            if i == len(lines) - 1:
                _warn_torn(path, line)
    return out


def _read_optional(path: str) -> List[dict]:
    """JSONL records of a file that may not exist (control.jsonl /
    fleet.jsonl are only written when their subsystem ran)."""
    try:
        return _jsonl_records(path)
    except OSError:
        return []


def load_spans(path: str) -> List[dict]:
    """Spans from a telemetry JSONL file (kind == "span" lines) or a
    flight-recorder dump (one JSON object with spans/open_spans). A
    size-rotated sibling (``<path>.1``, JsonlExporter rotation) is
    folded in first so long-run history reads as one logical file; a
    torn final line (crash-time write) is skipped with a warning."""
    with open(path) as f:
        head = f.read(1)
        f.seek(0)
        if head == "{":
            try:
                doc = json.load(f)
                if isinstance(doc, dict) and "spans" in doc:
                    return list(doc.get("spans") or []) + \
                        list(doc.get("open_spans") or [])
            except json.JSONDecodeError:
                pass   # torn flight dump: the line path below warns
    out = []
    paths = ([path + ".1"] if os.path.exists(path + ".1") else []) \
        + [path]
    for p in paths:
        for rec in _jsonl_records(p):
            if rec.get("kind") == "span":
                out.append(rec)
    return out


def load_aux(path: str) -> dict:
    """Control-plane records from a telemetry JSONL file: the
    `{"kind": "control"}` decision audit log and `{"kind":
    "slo_breach"}` evidence records the SLO engine / PoolController
    write (docs/OBSERVABILITY.md "SLOs & the control loop"), plus
    `slo.*` metric samples for the burn-rate timeline, plus histogram
    samples carrying tail exemplars (trace ids of the largest
    observations). Flight dumps carry none of these; rotation siblings
    fold in like load_spans."""
    aux = {"control": [], "breaches": [], "slo": [], "exemplars": []}
    try:
        with open(path) as f:
            # a flight-recorder dump is ONE json document (multi-record
            # JSONL fails the whole-file parse): spans only, no aux
            try:
                doc = json.load(f)
                if isinstance(doc, dict) and "spans" in doc:
                    return aux
            except json.JSONDecodeError:
                pass
    except OSError:
        return aux
    paths = ([path + ".1"] if os.path.exists(path + ".1") else []) \
        + [path]
    for p in paths:
        for rec in _jsonl_records(p):
            kind = rec.get("kind")
            if kind == "control":
                aux["control"].append(rec)
            elif kind == "slo_breach":
                aux["breaches"].append(rec)
            elif kind == "histogram" and rec.get("exemplars"):
                aux["exemplars"].append(rec)
            elif str(rec.get("name") or "").startswith("slo."):
                aux["slo"].append(rec)
    return aux


def render_slo_control(aux: dict) -> str:
    """The `slo` / `control` section: burn-rate timeline per SLO spec
    and window, breach records, and the control-decision audit log
    (chronological by controller seq)."""
    out: List[str] = []
    w = out.append
    burn: Dict[tuple, List[tuple]] = {}
    for s in aux.get("slo") or []:
        if s.get("name") != "slo.burn_rate":
            continue
        lb = s.get("labels") or {}
        burn.setdefault((str(lb.get("slo", "?")),
                         str(lb.get("window", "?"))), []).append(
            (float(s.get("ts") or 0.0), float(s.get("value") or 0.0)))
    if burn:
        w("== SLO burn rate (>1.0 = error budget burning faster than "
          "allowed) ==")
        w(f"  {'slo':<18}{'window':>8}{'samples':>9}{'max':>8}"
          f"{'last':>8}  timeline")
        for key in sorted(burn):
            pts = sorted(burn[key])
            vals = [v for _, v in pts]
            step = max(1, len(vals) // 10)
            tl = " ".join(f"{v:.1f}" for v in vals[::step][-10:])
            flag = "  << burning" if vals[-1] >= 1.0 else ""
            w(f"  {key[0]:<18}{key[1]:>8}{len(vals):>9}"
              f"{max(vals):>8.2f}{vals[-1]:>8.2f}  {tl}{flag}")
    breaches = aux.get("breaches") or []
    if breaches:
        w("== SLO breaches ==")
        for b in sorted(breaches, key=lambda r: r.get("ts") or 0):
            w("  t=%.2f slo=%s burn fast=%.2f slow=%.2f "
              "events(fast)=%s evidence_spans=%d exemplars=%d"
              % (float(b.get("ts") or 0.0), b.get("slo"),
                 float(b.get("burn_fast") or 0.0),
                 float(b.get("burn_slow") or 0.0),
                 b.get("events_fast"),
                 len(b.get("evidence") or []),
                 len(b.get("exemplars") or [])))
            for e in b.get("exemplars") or []:
                w(f"    exemplar {float(e.get('value') or 0) * 1e3:.2f}"
                  f"ms -> trace {e.get('trace')} "
                  "(tools/trace_report.py --request <trace>)")
    ex_recs = aux.get("exemplars") or []
    if ex_recs:
        # a long run exports each family many times: keep the LAST
        # sample per (name, labels) — exemplars are cumulative tails
        last: Dict[tuple, dict] = {}
        for r in ex_recs:
            key = (str(r.get("name")),
                   tuple(sorted((r.get("labels") or {}).items())))
            last[key] = r
        w("== tail exemplars (largest observations -> traces) ==")
        for key in sorted(last, key=str):
            r = last[key]
            lbl = ",".join(f"{k}={v}"
                           for k, v in sorted(
                               (r.get("labels") or {}).items()))
            pairs = "  ".join(
                f"{float(e.get('value') or 0) * 1e3:.2f}ms"
                f"->{e.get('trace')}"
                for e in r.get("exemplars") or [])
            w(f"  {r.get('name')}"
              + (f"{{{lbl}}}" if lbl else "") + f"  {pairs}")
    ctl = aux.get("control") or []
    if ctl:
        ctl = sorted(ctl, key=lambda r: (r.get("seq") is None,
                                         r.get("seq") or 0,
                                         r.get("ts") or 0))
        w("== control decisions ==")
        w(f"  {'seq':>5}{'tick':>7}  {'rule':<14}{'action':<16}"
          f"{'tier':<12}{'burn_f':>7}  params")
        for r in ctl:
            ins = r.get("inputs") or {}
            bf = ins.get("burn_fast")
            bf_s = f"{float(bf):.2f}" if bf is not None else "-"
            params = r.get("params") or {}
            ps = " ".join(f"{k}={params[k]}" for k in sorted(params))
            w(f"  {str(r.get('seq', '-')):>5}"
              f"{str(r.get('tick', '-')):>7}"
              f"  {str(r.get('rule', '-')):<14}"
              f"{str(r.get('action', '-')):<16}"
              f"{str(r.get('tier') or '-'):<12}{bf_s:>7}  {ps}")
    return "\n".join(out)


def load_heartbeats(paths: List[str]) -> List[dict]:
    """`{"kind": "heartbeat"}` lines from heartbeat.jsonl /
    heartbeat_rank*.jsonl / telemetry files (missing files skipped;
    torn final lines skipped with a warning)."""
    out = []
    for path in paths:
        if not isinstance(path, str) or not os.path.exists(path):
            continue
        for rec in _jsonl_records(path):
            if rec.get("kind") == "heartbeat" and "ts" in rec:
                out.append(rec)
    out.sort(key=lambda r: r["ts"])
    return out


def render_recovery(spans: List[dict], beats: List[dict],
                    controls: Optional[List[dict]] = None,
                    fleet_events: Optional[List[dict]] = None,
                    goodput: Optional[Dict[str, float]] = None) -> str:
    """Incident timeline for a hang→kill→restart→resume episode: the
    wedged rank's last heartbeat, the detector's kill, the restart
    epoch, and the resume step — one chronological view over the
    launcher spans (launch.epoch / launch.recovery) and the per-rank
    worker heartbeats, ending with the measured MTTR.

    With `controls` (the mitigation controller's control.jsonl) and
    `fleet_events` (fleet.jsonl) the same view renders the full
    MITIGATION incident chain: skew detected → decision (or hold, with
    the reason) → kill/reassign → restart epoch → resume → goodput
    delta — every step of it straight from the audit records, so an
    operator replays exactly what the actuator saw and why it acted."""
    ev = []  # (ts, text)
    mttrs = []
    for c in controls or []:
        ts = float(c.get("ts") or 0.0)
        act = c.get("action")
        params = c.get("params") or {}
        inp = c.get("inputs") or {}
        tag = f"seq={c.get('seq')}"
        if act == "exclude_restart":
            ev.append((ts, f"MITIGATION {tag}: exclude rank "
                           f"{params.get('rank')} (stage "
                           f"{params.get('stage')}, world "
                           f"{params.get('world_before')} -> "
                           f"{params.get('world_after')}; "
                           f"{inp.get('classification')}, "
                           f"{inp.get('consecutive')} consecutive slow "
                           f"steps) -> SIGKILL + elastic restart"))
        elif act == "reassign_stages":
            ev.append((ts, f"MITIGATION {tag}: reassign stages "
                           f"{params.get('stage_map')} (slow rank "
                           f"{params.get('rank')} in stage "
                           f"{params.get('slow_stage')} takes the "
                           f"lightest) -> restart"))
        elif act in ("hold_flap", "hold_cooldown", "tolerate"):
            why = params.get("reasons") \
                or (f"previous rank {params.get('previous_rank')} "
                    f"{params.get('since_s')}s ago"
                    if act == "hold_flap" else
                    f"{params.get('remaining_s')}s remaining"
                    if act == "hold_cooldown" else "")
            ev.append((ts, f"mitigation {tag}: {act} rank "
                           f"{inp.get('rank', params.get('rank'))} "
                           f"({why})"))
        # init/observe records are bookkeeping, not incidents
    for fe in fleet_events or []:
        e = fe.get("event")
        ts = float(fe.get("ts") or 0.0)
        if e == "straggler":
            ev.append((ts, f"STRAGGLER rank={fe.get('rank')} step "
                           f"{fe.get('step')}: {fe.get('dur_s')}s vs "
                           f"median {fe.get('median_s')}s "
                           f"({fe.get('consecutive')} consecutive; "
                           f"dominant {fe.get('dominant_span')!r})"))
        elif e == "rank_retired":
            ev.append((ts, f"rank {fe.get('rank')} retired from the "
                           "fleet join (excluded)"))
    for s in spans:
        name = s.get("name")
        lab = s.get("labels") or {}
        start = float(s.get("start", 0.0))
        dur = float(s.get("dur") or 0.0)
        if name == "launch.epoch":
            ev.append((start, f"epoch {lab.get('epoch', '?')} start "
                              f"(restarts={lab.get('restarts', '?')})"))
            for e in s.get("events") or []:
                en = e.get("name")
                at = {k: v for k, v in e.items()
                      if k not in ("ts", "name")}
                if en == "hang_detected":
                    ev.append((e["ts"],
                               f"HANG DETECTED rank={at.get('rank')} "
                               f"pid={at.get('pid')} silent "
                               f"{at.get('silent_s')}s, last phase "
                               f"{at.get('phase')!r}"
                               + (f" step {at.get('step')}"
                                  if at.get("step") is not None else "")
                               + " -> SIGKILL"))
                elif en == "pod_exit":
                    ev.append((e["ts"],
                               f"pod exit rc={at.get('rc')} -> restart"))
                else:
                    ev.append((e["ts"], f"{en} {at}"))
            if s.get("status") is not None:
                ev.append((start + dur,
                           f"epoch {lab.get('epoch', '?')} end "
                           f"({s.get('status')})"))
        elif name == "launch.recovery":
            ev.append((start, f"recovery window opened (rank "
                              f"{lab.get('rank')}, wedged in phase "
                              f"{lab.get('phase')!r})"))
            m = lab.get("mttr_s")
            ev.append((start + dur,
                       f"recovery {s.get('status', '?')}"
                       + (f": MTTR {m}s" if m is not None else "")))
            if m is not None and s.get("status") == "ok":
                mttrs.append(float(m))
    # worker heartbeats: phase transitions + the silence gaps between
    # beats (a wedged rank reads as one long gap ending in the kill)
    by_rank: Dict[str, List[dict]] = {}
    for b in beats:
        if "ranks" in b:    # launcher pod snapshots: skip, too chatty
            continue
        by_rank.setdefault(str(b.get("rank", "?")), []).append(b)
    for rank, bs in sorted(by_rank.items()):
        prev = None
        for b in bs:
            gap = (b["ts"] - prev["ts"]) if prev else 0.0
            if prev is not None and gap > 2.0:
                ev.append((prev["ts"],
                           f"rank {rank} last beat before {gap:.1f}s "
                           f"gap: phase {prev.get('phase')!r}"
                           + (f" step {prev.get('step')}"
                              if prev.get("step") is not None else "")))
            if prev is None or b.get("phase") != prev.get("phase") \
                    or gap > 2.0:
                ev.append((b["ts"],
                           f"rank {rank} beat: phase {b.get('phase')!r}"
                           + (f" step {b.get('step')}"
                              if b.get("step") is not None else "")))
            prev = b
    if not ev:
        return ("(no recovery timeline: need launch.epoch/"
                "launch.recovery spans and/or heartbeat lines — pass "
                "the telemetry JSONL and --heartbeat "
                "<log_dir>/heartbeat_rank*.jsonl)")
    ev.sort(key=lambda t: t[0])
    t0 = ev[0][0]
    out = ["== recovery timeline =="]
    for ts, text in ev:
        out.append(f"  +{ts - t0:9.3f}s  {text}")
    if mttrs:
        out.append(f"  MTTR (detection -> restarted rank progressing): "
                   f"{mttrs[-1]:.3f}s"
                   + (f" (episodes: {len(mttrs)})"
                      if len(mttrs) > 1 else ""))
    if controls:
        seqs = [c.get("seq") for c in controls
                if c.get("seq") is not None]
        gaps = [(a, b) for a, b in zip(seqs, seqs[1:]) if b != a + 1]
        out.append(f"  audit stream: {len(controls)} control records, "
                   + ("seq contiguous"
                      if not gaps and seqs and seqs[0] == 1
                      else f"seq GAPS at {gaps} (tampered or torn?)"))
    if goodput and len(goodput) >= 2 and "toleration" in goodput \
            and "mitigation" in goodput and goodput["toleration"] > 0:
        delta = (goodput["mitigation"] / goodput["toleration"] - 1.0) \
            * 100.0
        out.append("  goodput: "
                   + ", ".join(f"{arm}={v:.4f}"
                               for arm, v in sorted(goodput.items()))
                   + f" ({delta:+.1f}% from mitigation)")
    return "\n".join(out)


def percentile(xs: List[float], q: float) -> float:
    if not xs:
        return 0.0
    ys = sorted(xs)
    pos = q * (len(ys) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ys) - 1)
    frac = pos - lo
    return ys[lo] * (1 - frac) + ys[hi] * frac


def _pct_row(label: str, xs: List[float], unit_ms: bool = True) -> str:
    scale = 1e3 if unit_ms else 1.0
    u = "ms" if unit_ms else "s"
    return (f"  {label:<18}n={len(xs):<6}"
            f"p50 {percentile(xs, 0.5) * scale:8.2f}{u}  "
            f"p90 {percentile(xs, 0.9) * scale:8.2f}{u}  "
            f"p99 {percentile(xs, 0.99) * scale:8.2f}{u}  "
            f"max {max(xs) * scale:8.2f}{u}")


# ---------------------------------------------------------------- analysis --
def _event(span: dict, name: str) -> Optional[dict]:
    for e in span.get("events") or []:
        if e.get("name") == name:
            return e
    return None


class Request:
    """One serve.request span decoded into SLO-relevant timings."""

    def __init__(self, span: dict):
        self.span = span
        labels = span.get("labels") or {}
        self.id = labels.get("request_id", "?")
        self.prompt_len = labels.get("prompt_len")
        self.tier = labels.get("tier")
        self.replica = labels.get("replica")
        # disaggregated fleets: role-configured replicas label their
        # serve.request spans role=prefill|decode (unified: absent)
        self.role = labels.get("role")
        # tensor-parallel replicas carry the device GROUP they occupy
        # ("0-1" / "0,2"); per-replica views render it so a 2-device
        # replica reads as one row spanning two chips, not one chip
        self.devices = labels.get("devices")
        self.status = span.get("status", "?")
        self.start = float(span.get("start", 0.0))
        self.e2e = float(span.get("dur") or 0.0)
        adm = _event(span, "admitted")
        self.queue_wait = (adm["ts"] - self.start) if adm else None
        ft = _event(span, "first_token")
        self.ttft = (ft["ts"] - self.start) if ft else None
        toks = [e["ts"] for e in span.get("events") or []
                if e.get("name") == "token"]
        if ft:
            toks = [ft["ts"]] + toks
        self.token_times = toks
        fin = _event(span, "finish")
        self.tokens = fin.get("tokens") if fin else (
            len(toks) if toks else None)
        # chunked prefill (docs/SERVING.md): one prefill_chunk event
        # per ingested chunk; ingest = first chunk -> first token (the
        # TTFT decomposition for a chunked request)
        self.chunks = [e for e in span.get("events") or []
                       if e.get("name") == "prefill_chunk"]
        self.ingest = (ft["ts"] - self.chunks[0]["ts"]) \
            if ft and self.chunks else None
        # speculative decoding (docs/SERVING.md): one spec event per
        # verify tick carrying proposed/accepted draft counts — the
        # accepted column and the accept-rate summary read these
        self.spec = [e for e in span.get("events") or []
                     if e.get("name") == "spec"]
        self.spec_proposed = sum(int(e.get("proposed") or 0)
                                 for e in self.spec)
        self.spec_accepted = sum(int(e.get("accepted") or 0)
                                 for e in self.spec)

    @property
    def per_token(self) -> List[float]:
        ts = self.token_times
        return [b - a for a, b in zip(ts, ts[1:])]


def _handoffs(spans: List[dict]) -> List[dict]:
    """Prefill→decode handoffs decoded from router.request spans: one
    entry per `handoff` event (a readmit REPLAYS the import but never
    re-hands-off, so counting handoff events is double-count-free),
    with the export→pages-resident latency taken from the FIRST
    handoff_imported event and any export/import failures kept as
    fallback reasons."""
    out = []
    for s in spans:
        if s.get("name") != "router.request":
            continue
        evs = s.get("events") or []
        ho = next((e for e in evs if e.get("name") == "handoff"), None)
        if ho is None:
            continue
        imp = next((e for e in evs
                    if e.get("name") == "handoff_imported"), None)
        reasons = [e.get("reason", "export_miss") for e in evs
                   if e.get("name") in ("handoff_import_failed",
                                        "handoff_export_failed")]
        out.append({
            "request": (s.get("labels") or {}).get("request_id", "?"),
            "from": ho.get("from_replica", "?"),
            "bytes": int(ho.get("bytes") or 0),
            "pages": int(ho.get("pages") or 0),
            "imported": int(imp.get("imported") or 0) if imp else 0,
            "reused": int(imp.get("reused") or 0) if imp else 0,
            "latency": (imp["ts"] - ho["ts"]) if imp else None,
            "fallbacks": reasons,
            "readmitted": any(e.get("name") == "readmitted"
                              for e in evs),
        })
    return out


def analyze(spans: List[dict]) -> dict:
    reqs = [Request(s) for s in spans if s.get("name") == "serve.request"]
    steps = [s for s in spans if s.get("name") == "train.step"]
    by_parent: Dict[str, List[dict]] = {}
    for s in spans:
        p = s.get("parent")
        if p:
            by_parent.setdefault(p, []).append(s)
    sites: Dict[str, List[float]] = {}
    for s in spans:
        sites.setdefault(s.get("name", "?"), []).append(
            float(s.get("dur") or 0.0))
    return {"requests": reqs, "steps": steps, "children": by_parent,
            "sites": sites, "handoffs": _handoffs(spans)}


# ----------------------------------------------------- request waterfall --
def resolve_trace(spans: List[dict], ident: str) -> Optional[str]:
    """Resolve a --request identifier to a trace id: an exact trace id
    match, else the trace of any span labeled request_id=ident (router
    handles mint rr<N>, serve loops req<N>)."""
    for s in spans:
        if s.get("trace") == ident:
            return ident
    for s in spans:
        if (s.get("labels") or {}).get("request_id") == ident \
                and s.get("trace"):
            return s["trace"]
    return None


def render_waterfall(spans: List[dict], trace_id: str,
                     critpath=None) -> str:
    """One request's cross-role waterfall: every span of the trace
    indented under its parent (router admission at the root, the
    prefill and decode replicas' serve.request spans below it), events
    inline at their timeline offsets, then the critical-path stage
    decomposition whose telescoping stages sum to the measured E2E
    (and, up to the prefill stage, to TTFT)."""
    tspans = sorted((s for s in spans if s.get("trace") == trace_id),
                    key=lambda s: float(s.get("start") or 0.0))
    if not tspans:
        return f"no spans for trace {trace_id!r}"
    ids = {s.get("span"): s for s in tspans}

    def depth(s: dict) -> int:
        d = 0
        p = s.get("parent")
        seen = set()
        while p and p in ids and p not in seen:
            seen.add(p)
            d += 1
            p = ids[p].get("parent")
        return d

    t0 = min(float(s.get("start") or 0.0) for s in tspans)
    root = next((s for s in tspans if not s.get("parent")), tspans[0])
    rl = root.get("labels") or {}
    out: List[str] = []
    w = out.append
    w(f"== trace {trace_id} (request "
      f"{rl.get('request_id', '?')}, status "
      f"{root.get('status', '?')}, {len(tspans)} spans) ==")
    orphan_ids = {s.get("span") for s in tspans
                  if s.get("parent") and s["parent"] not in ids}
    for s in tspans:
        lab = s.get("labels") or {}
        ind = "  " * depth(s)
        rel = (float(s.get("start") or 0.0) - t0) * 1e3
        extras = " ".join(
            f"{k}={lab[k]}" for k in ("request_id", "replica", "role",
                                      "tier")
            if lab.get(k) is not None)
        mark = "  ORPHAN (parent unresolved in trace)" \
            if s.get("span") in orphan_ids else ""
        w(f"  +{rel:9.3f}ms  {ind}{s.get('name', '?')}"
          f"  [{float(s.get('dur') or 0.0) * 1e3:.3f}ms"
          f" {s.get('status', '?')}]"
          + (f"  {extras}" if extras else "") + mark)
        for e in s.get("events") or []:
            erel = (float(e.get("ts") or 0.0) - t0) * 1e3
            attrs = ", ".join(f"{k}={v}" for k, v in e.items()
                              if k not in ("ts", "name"))
            w(f"  +{erel:9.3f}ms  {ind}  . {e.get('name')}"
              + (f"  ({attrs})" if attrs else ""))
    cp = critpath if critpath is not None else _load_critpath()
    if cp is not None:
        d = cp.stage_decomposition(tspans, trace_id=trace_id)
        w("  -- critical path (stages sum to E2E; the prefix up to")
        w("     'prefill' sums to TTFT) --")
        cum = 0.0
        for stage, secs in d["stages"]:
            cum += secs
            w(f"  {stage:<18}{secs * 1e3:>11.3f}ms"
              f"   cum {cum * 1e3:>11.3f}ms")
        ttft = d.get("ttft")
        w("  TTFT "
          + (f"{ttft * 1e3:.3f}ms" if ttft is not None else "-")
          + f"   E2E {d['e2e'] * 1e3:.3f}ms")
        aux = d.get("aux") or {}
        if aux.get("orphans"):
            w(f"  ORPHAN SPANS: {aux['orphans']} "
              "(broken trace-propagation chain)")
        if aux.get("spec_ticks"):
            w(f"  speculation: {aux['spec_ticks']} verify ticks, "
              f"{aux['spec_accepted']} drafts accepted "
              "(folded into the decode stage)")
    return "\n".join(out)


# --------------------------------------------------------------- rendering --
def render(spans: List[dict], top_requests: int = 5,
           waterfall_steps: int = 8, request_id: Optional[str] = None) \
        -> str:
    a = analyze(spans)
    reqs: List[Request] = a["requests"]
    out = []
    w = out.append

    if request_id is not None:
        tid = resolve_trace(spans, request_id)
        if tid is not None:
            return render_waterfall(spans, tid)
        match = [r for r in reqs if r.id == request_id]
        if not match:
            return f"no serve.request span with request_id={request_id!r}"
        for r in match:
            w(f"== request {r.id} ({r.status}, prompt_len="
              f"{r.prompt_len}, e2e {r.e2e * 1e3:.2f}ms"
              + (f", {len(r.chunks)} prefill chunks" if r.chunks
                 else "") + ") ==")
            chunk_i = 0
            spec_i = 0
            for e in r.span.get("events") or []:
                rel = (e["ts"] - r.start) * 1e3
                name = e["name"]
                if name == "prefill_chunk":
                    # number the chunk spans so the TTFT decomposition
                    # of a chunked request reads chunk-by-chunk
                    name = f"prefill_chunk[{chunk_i}]"
                    chunk_i += 1
                elif name == "spec":
                    # number the verify ticks so multi-token decode
                    # progress reads tick-by-tick
                    name = f"spec[{spec_i}]"
                    spec_i += 1
                attrs = ", ".join(f"{k}={v}" for k, v in e.items()
                                  if k not in ("ts", "name"))
                w(f"  +{rel:9.3f}ms  {name}"
                  + (f"  ({attrs})" if attrs else ""))
        return "\n".join(out)

    # ---- SLO percentiles -------------------------------------------
    ttft = [r.ttft for r in reqs if r.ttft is not None]
    per_tok = [d for r in reqs for d in r.per_token]
    e2e = [r.e2e for r in reqs if r.status not in ("queued",)]
    step_t = [float(s.get("dur") or 0.0) for s in a["steps"]]
    if ttft or per_tok or e2e or step_t:
        w("== SLO percentiles ==")
        if ttft:
            w(_pct_row("TTFT", ttft))
        ingest = [r.ingest for r in reqs if r.ingest is not None]
        if ingest:
            w(_pct_row("chunk ingest", ingest))
        if per_tok:
            w(_pct_row("per-token", per_tok))
        if e2e:
            w(_pct_row("request e2e", e2e))
        if step_t:
            w(_pct_row("train step", step_t))

    # ---- per-tier SLO split (multi-tenant front end) ----------------
    tiers = sorted({r.tier for r in reqs if r.tier is not None})
    if tiers:
        w("== per-tier SLO ==")
        for tier in tiers:
            sub = [r for r in reqs if r.tier == tier]
            t_ttft = [r.ttft for r in sub if r.ttft is not None]
            t_e2e = [r.e2e for r in sub]
            if t_ttft:
                w(_pct_row(f"{tier} TTFT", t_ttft))
            if t_e2e:
                w(_pct_row(f"{tier} e2e", t_e2e))

    # ---- per-replica utilization (replica pool) ---------------------
    replicas = sorted({r.replica for r in reqs if r.replica is not None})
    if replicas:
        w("== per-replica ==")
        w(f"  {'replica':<12}{'role':<9}{'devices':>9}{'requests':>9}"
          f"{'tokens':>8}{'busy ms':>10}{'ttft p99':>11}{'e2e p99':>11}")
        for rep in replicas:
            sub = [r for r in reqs if r.replica == rep]
            toks = sum(r.tokens or 0 for r in sub)
            busy = sum(r.e2e for r in sub)
            r_ttft = [r.ttft for r in sub if r.ttft is not None]
            devs = next((r.devices for r in sub
                         if r.devices is not None), "-")
            role = next((r.role for r in sub if r.role is not None), "-")
            w(f"  {rep:<12}{role:<9}{devs:>9}{len(sub):>9}{toks:>8}"
              f"{busy * 1e3:>10.1f}"
              f"{percentile(r_ttft, 0.99) * 1e3:>9.2f}ms"
              f"{percentile([r.e2e for r in sub], 0.99) * 1e3:>9.2f}ms")

    # ---- what the admission rounds' prefills forwarded and padded ----
    rounds = [s.get("labels") or {} for s in spans
              if s.get("name") == "serve.prefill"
              and (s.get("labels") or {}).get("padded")]
    if rounds:
        fwd, pad = (sum(lab.get(k, 0) for lab in rounds)
                    for k in ("tokens", "padded"))
        secs = sum(float(lab.get("seconds", 0.0)) for lab in rounds)
        w("== prefills (admission rounds that ran a program) ==")
        w(f"  rounds={len(rounds)}  tokens forwarded={fwd}  positions "
          f"computed={pad}  padding={100.0 * (1 - fwd / pad):.1f}%")
        w(f"  dispatch to first tokens {secs * 1e3:.2f}ms"
          f" ({secs * 1e6 / max(fwd, 1):.1f}us a token)  decoding slots"
          f" found waiting={sum(lab.get('stalled', 0) for lab in rounds)}")

    # ---- disaggregated handoffs (router.request spans) --------------
    hos = a["handoffs"]
    if hos:
        w("== disaggregated handoff ==")
        n_bytes = sum(h["bytes"] for h in hos)
        imported = sum(h["imported"] for h in hos)
        reused = sum(h["reused"] for h in hos)
        w(f"  handoffs        {len(hos)}"
          f"   bytes {n_bytes}   pages imported {imported}"
          f" / reused {reused}"
          f"   readmitted {sum(1 for h in hos if h['readmitted'])}")
        lat = [h["latency"] for h in hos if h["latency"] is not None]
        if lat:
            w(_pct_row("handoff latency", lat))
        by_reason: Dict[str, int] = {}
        for h in hos:
            for rs in h["fallbacks"]:
                by_reason[rs] = by_reason.get(rs, 0) + 1
        if by_reason:
            w("  fallbacks       " + "  ".join(
                f"{k}={v}" for k, v in sorted(by_reason.items())))

    # ---- request outcomes + slowest table --------------------------
    if reqs:
        outcomes: Dict[str, int] = {}
        for r in reqs:
            outcomes[r.status] = outcomes.get(r.status, 0) + 1
        w("== requests ==")
        w("  outcomes        " + "  ".join(
            f"{k}={v}" for k, v in sorted(outcomes.items())))
        sp_prop = sum(r.spec_proposed for r in reqs)
        sp_acc = sum(r.spec_accepted for r in reqs)
        if sp_prop:
            sp_ticks = sum(len(r.spec) for r in reqs)
            w(f"  speculation     proposed={sp_prop}  accepted={sp_acc}"
              f"  accept_rate={sp_acc / sp_prop:.3f}"
              f"  tokens/verify-tick="
              f"{(sp_acc + sp_ticks) / max(sp_ticks, 1):.2f}")
        w(f"  {'request':<10}{'status':<12}{'prompt':>7}{'tokens':>7}"
          f"{'chunks':>7}{'spec':>7}{'wait ms':>9}{'ttft ms':>9}"
          f"{'e2e ms':>10}")
        for r in sorted(reqs, key=lambda r: -r.e2e)[:top_requests]:
            w(f"  {r.id:<10}{r.status:<12}"
              f"{r.prompt_len if r.prompt_len is not None else '?':>7}"
              f"{r.tokens if r.tokens is not None else '?':>7}"
              f"{len(r.chunks) if r.chunks else '-':>7}"
              f"{r.spec_accepted if r.spec else '-':>7}"
              f"{r.queue_wait * 1e3 if r.queue_wait is not None else 0:>9.2f}"
              f"{r.ttft * 1e3 if r.ttft is not None else 0:>9.2f}"
              f"{r.e2e * 1e3:>10.2f}")

    # ---- step waterfall --------------------------------------------
    steps = a["steps"]
    if steps:
        w("== train step waterfall (last %d) ==" %
          min(waterfall_steps, len(steps)))
        phases = ("train.data", "train.dispatch", "train.loss_sync")
        w(f"  {'step':>6}  {'total ms':>9}  " + "  ".join(
            f"{p.split('.')[1]:>11}" for p in phases))
        for s in steps[-waterfall_steps:]:
            kids = {c.get("name"): float(c.get("dur") or 0.0)
                    for c in a["children"].get(s.get("span"), [])}
            n = (s.get("labels") or {}).get("step", "?")
            if s.get("rank") is not None:   # merged fleet pool: name
                n = f"{n}:r{s['rank']}"     # the writing rank
            total = float(s.get("dur") or 0.0) * 1e3
            cols = "  ".join(f"{kids.get(p, 0.0) * 1e3:9.2f}ms"
                             for p in phases)
            anom = " ANOMALOUS" if (s.get("labels") or {}).get(
                "anomalous") else ""
            w(f"  {n:>6}  {total:>9.2f}  {cols}{anom}")

    # ---- per-site table --------------------------------------------
    if a["sites"]:
        w("== span sites ==")
        w(f"  {'site':<24}{'count':>7}{'mean ms':>10}{'p99 ms':>10}"
          f"{'max ms':>10}")
        for name in sorted(a["sites"]):
            ds = a["sites"][name]
            w(f"  {name:<24}{len(ds):>7}"
              f"{(sum(ds) / len(ds)) * 1e3:>10.2f}"
              f"{percentile(ds, 0.99) * 1e3:>10.2f}"
              f"{max(ds) * 1e3:>10.2f}")

    return "\n".join(out) if out else "(no spans found)"


# ------------------------------------------------------------ chrome trace --
def to_chrome_trace(spans: List[dict]) -> dict:
    """Standalone copy of tracing.to_chrome_trace (this tool must run
    without a paddle_tpu install)."""
    tids: Dict[str, int] = {}
    out = []
    for s in spans:
        key = s.get("trace") or s.get("span") or s.get("name", "?")
        tid = tids.setdefault(key, len(tids) + 1)
        args = dict(s.get("labels") or {})
        args["status"] = s.get("status", "ok")
        args["trace"] = s.get("trace")
        out.append({"ph": "X", "cat": "span", "name": s.get("name", "?"),
                    "ts": float(s.get("start", 0.0)) * 1e6,
                    "dur": max(float(s.get("dur") or 0.0), 0.0) * 1e6,
                    "pid": 1, "tid": tid, "args": args})
        for e in s.get("events") or []:
            out.append({"ph": "i", "s": "t",
                        "name": f"{s.get('name', '?')}:{e.get('name')}",
                        "ts": float(e.get("ts", 0.0)) * 1e6,
                        "pid": 1, "tid": tid,
                        "args": {k: v for k, v in e.items()
                                 if k not in ("ts", "name")}})
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def expand_inputs(paths: List[str], dirs: List[str]) -> List[str]:
    """Positional files plus each --dir's telemetry files (a directory
    given positionally works too). ``.1`` rotation siblings are NOT
    listed — load_spans folds them in per file."""
    import glob as _glob
    files: List[str] = []
    for p in list(paths):
        if os.path.isdir(p):
            dirs = dirs + [p]
        else:
            files.append(p)
    for d in dirs:
        files.extend(sorted(_glob.glob(os.path.join(d,
                                                    "telemetry*.jsonl"))))
    # de-dup, order-preserving (a file named positionally AND via --dir)
    seen = set()
    out = []
    for f in files:
        key = os.path.abspath(f)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*",
                    help="telemetry JSONL file(s), flight dump(s) "
                         "(output/flight_<pid>.json), and/or "
                         "directories of per-rank files")
    ap.add_argument("--dir", action="append", default=[],
                    help="a launcher log directory: every "
                         "telemetry*.jsonl in it joins the span pool "
                         "(telemetry_rank<k>.jsonl fleet layout); "
                         "repeatable")
    ap.add_argument("--requests", type=int, default=5,
                    help="slowest-request table size")
    ap.add_argument("--steps", type=int, default=8,
                    help="waterfall rows (last N train steps)")
    ap.add_argument("--request", default=None,
                    help="print one request's full event timeline")
    ap.add_argument("--chrome", default=None,
                    help="also write Chrome-trace/Perfetto JSON here")
    ap.add_argument("--recovery", action="store_true",
                    help="incident-timeline view: last heartbeat -> "
                         "hang detection -> kill -> restart epoch -> "
                         "resume, from launch.* spans + heartbeats")
    ap.add_argument("--heartbeat", action="append", default=[],
                    help="additional heartbeat JSONL file(s) for "
                         "--recovery (e.g. <log_dir>/"
                         "heartbeat_rank0.jsonl); repeatable")
    a = ap.parse_args(argv)
    files = expand_inputs(a.paths, list(a.dir))
    if not files:
        print("no input files (pass telemetry JSONL paths and/or "
              "--dir <log_dir>)", file=sys.stderr)
        return 1
    spans = []
    missing = 0
    for path in files:
        try:
            spans.extend(load_spans(path))
        except FileNotFoundError:
            print(f"no such file: {path}", file=sys.stderr)
            missing += 1
    if missing == len(files):
        return 1
    if len(files) > 1:
        # merged multi-rank pools interleave chronologically, so the
        # "last N steps" views mean the same thing they do for one file
        spans.sort(key=lambda s: float(s.get("start") or 0.0))
    if a.recovery:
        hb_files = list(files) + list(a.heartbeat)
        controls: List[dict] = []
        fleet_events: List[dict] = []
        goodput: Dict[str, float] = {}
        for d in list(a.dir) + [p for p in a.paths if os.path.isdir(p)]:
            import glob as _glob
            hb_files.extend(sorted(_glob.glob(
                os.path.join(d, "heartbeat*.jsonl"))))
            # the mitigation audit stream + the fleet event log live
            # beside the heartbeats in the launcher log dir
            for rec in _read_optional(os.path.join(d, "control.jsonl")):
                if rec.get("kind") == "control":
                    controls.append(rec)
            for rec in _read_optional(os.path.join(d, "fleet.jsonl")):
                if rec.get("kind") == "fleet":
                    fleet_events.append(rec)
        for path in files:
            for rec in _read_optional(path):
                if rec.get("kind") == "control":
                    controls.append(rec)
                elif rec.get("name") == "robustness.goodput":
                    arm = (rec.get("labels") or {}).get("arm")
                    if arm:
                        goodput[str(arm)] = float(rec.get("value")
                                                  or 0.0)
        controls.sort(key=lambda c: (c.get("ts") or 0, c.get("seq")
                                     or 0))
        beats = load_heartbeats(hb_files)
        print(render_recovery(spans, beats, controls=controls,
                              fleet_events=fleet_events,
                              goodput=goodput))
    else:
        print(render(spans, top_requests=a.requests,
                     waterfall_steps=a.steps, request_id=a.request))
        if a.request is None:
            aux = {"control": [], "breaches": [], "slo": [],
                   "exemplars": []}
            for path in files:
                try:
                    one = load_aux(path)
                except FileNotFoundError:
                    continue
                for k in aux:
                    aux[k].extend(one[k])
            sec = render_slo_control(aux)
            if sec:
                print(sec)
    if a.chrome:
        with open(a.chrome, "w") as f:
            json.dump(to_chrome_trace(spans), f)
        print(f"chrome trace written: {a.chrome} "
              "(chrome://tracing or https://ui.perfetto.dev)",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

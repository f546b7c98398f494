"""Run one cell of the benchmark once, as `benchmarks/run.py` does, and
print beside its result line what the serve loop's own account says of
the same window: the tick ring's sums (prefill seconds, tokens forwarded
and padded, tokens handed out, stalled tokens), the gc log, and how they
agree with what the runner counted and, in a traced run, with the
device's time in the prefill programs; for a model with an indexer, how
many of the prefill kernel's key blocks held a real key
(`dsa.prefill_key_blocks`); the decode steps dispatched and the slots
that rode them with no request (`serving.idle_slot_steps`).

    python3 tools/serve_account.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1> [--out <file.json>]

Run from the root of the checkout to be measured (the chip tool's
command). A tree without the ring's fields or the log (a parent commit)
prints None for them, and its collections are stamped by a hook of this
tool's own. The LAST line of standard output is the benchmark's result
line; the one before it is `{"account": ...}`.
"""
import time
T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.getcwd()
sys.path.insert(0, ROOT)


class _KeepTrace:
    """`shutil` for the harness, which clears a cell's trace before the
    run and after its readers: the second clearing waits for this tool."""

    def __init__(self):
        self.calls, self.kept = 0, None

    def __getattr__(self, name):
        return getattr(shutil, name)

    def rmtree(self, path, **kw):
        self.calls += 1
        if self.calls == 1:
            shutil.rmtree(path, **kw)
        else:
            self.kept = path


def own_gc_log():
    """Pauses of a tree whose program keeps no gc log."""
    log, t0 = [], [0.0]

    def hook(phase, info):
        if phase == "start":
            t0[0] = time.perf_counter()
            return
        seconds = time.perf_counter() - t0[0]
        if info["generation"] == 2 or seconds >= 1e-3:
            log.append({"t": t0[0], "generation": info["generation"],
                        "seconds": seconds, "collected": info["collected"]})

    gc.callbacks.append(hook)
    return lambda since, until: [e for e in log if since <= e["t"] < until]


def ring_sums(since, seconds):
    """`prefill_account.sums` of the ticks that began in `seconds` from
    `since`, with their count and their `serve.prefill` stage seconds
    beside (the stage holds operand building and the prefix cache's
    insert besides what `pf_s` stamps)."""
    from benchmarks.lib import prefill_account, stage_gaps
    record = {"w0": since, "window_s": seconds}
    out = prefill_account.sums(record)
    if out is None:
        return None
    ticks = stage_gaps.window_ticks(record)
    del out["stalls_s"]
    out["ticks"] = len(ticks)
    out["prefill_ticks"] = sum(1 for t in ticks if t.get("pf_s", 0) > 0)
    out["stage_prefill_s"] = sum(t["stages"].get("serve.prefill", 0.0)
                                 for t in ticks)
    return out


def where_the_window_went(since, seconds):
    """Seconds of the window's ticks by stage, and its longest ticks
    without a prefill (a stall has to be in one of the two)."""
    from benchmarks.lib import stage_gaps
    ticks = stage_gaps.window_ticks({"w0": since, "window_s": seconds})
    by_stage = {"dur": sum(t["dur"] for t in ticks)}
    for t in ticks:
        for name, s in t["stages"].items():
            by_stage[name] = by_stage.get(name, 0.0) + s
    bare = sorted((t for t in ticks if not t.get("prefill")),
                  key=lambda t: -t["dur"])[:5]
    return {"seconds_by_stage": by_stage,
            "longest_without_prefill": [
                {"at": t["t0"] - since, "dur": t["dur"],
                 "active": t.get("active"), "stages": t["stages"]}
                for t in bare]}


def compile_sums(runtime, until):
    """{kind: [events, seconds]} of the compile log before `until`:
    what set-up traced, lowered, compiled, and found in the cache."""
    out, by_sig = {}, {}
    for e in runtime.compile_log(until=until):
        n = out.setdefault(e["kind"], [0, 0.0])
        n[0] += 1
        n[1] += e["seconds"]
        if e["kind"] != "trace":
            sig = by_sig.setdefault(str(e["sig"]), {})
            sig[e["kind"]] = round(sig.get(e["kind"], 0.0)
                                   + (e["seconds"] or 1.0), 3)
    out["by_sig"] = dict(sorted(
        by_sig.items(), key=lambda kv: -kv[1].get("compile", 0.0))[:24])
    return out


def traced_prefills(trace_dir):
    """On the profiler's clock alone: the complete `serve.prefill`
    annotations of the `numbers` trace, and the device's time in the
    prefill programs that started under one."""
    from benchmarks.lib import readers, stage_gaps, trace_reduce
    prefill = re.compile(readers.PREFILL)
    planes = trace_reduce.load(os.path.join(trace_dir, "numbers"))
    anns, mods = [], []
    for p in planes:
        if trace_reduce.DEVICE_PLANE.match(p["name"]):
            mods += [(s, d) for n, s, d in
                     p["lines"].get(trace_reduce.MODULES_LINE, [])
                     if prefill.search(trace_reduce.program_name(n))]
            continue
        for line in p["lines"].values():
            if any(stage_gaps.stage_name(n) == "serve.tick"
                   for n, _, _ in line):
                anns += [(n, s, d) for n, s, d in line
                         if stage_gaps.stage_name(n) == "serve.prefill"]
    under = sum(d for s, d in mods
                if any(a <= s < a + ad for _, a, ad in anns))
    return {"annotations": len(anns), "annotation_s": sum(d for *_, d in anns),
            "programs": len(mods), "program_s": sum(d for _, d in mods),
            "program_s_under_annotations": under,
            "first_annotation": anns[0][0][:300] if anns else None}


def prefill_key_blocks():
    """`dsa.prefill_key_blocks{kind}` over the whole run (warm-up,
    window and drain) and `attended` over `bucket`: the share of the
    masked flash kernel's (row, tile, key block) steps that still
    compute. None for a cell, or a tree, that counts none."""
    from paddle_tpu.observability import metrics
    n = {s.labels.get("kind"): s.value for s in
         metrics.counter("dsa.prefill_key_blocks").samples()}
    if not n.get("bucket"):
        return None
    return dict(n, share=n.get("attended", 0) / n["bucket"])


def decode_steps():
    """`serving.decode_steps` beside `serving.idle_slot_steps` over the
    whole run: the steps dispatched and the slots that rode them with no
    request (None on a tree that does not count them)."""
    from paddle_tpu.observability import metrics

    def total(name):
        m = metrics.get_registry().get(name)
        return None if m is None else sum(s.value for s in m.samples())

    return {"steps": total("serving.decode_steps"),
            "idle_slot_steps": total("serving.idle_slot_steps")}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from benchmarks.lib import harness, stage_gaps
    from paddle_tpu.observability import runtime
    read_gc = getattr(runtime, "gc_log", None)
    gc_source = "program" if read_gc else "tool"
    if read_gc is None:
        own = own_gc_log()
        read_gc = lambda since=None, until=None: own(since, until)  # noqa: E731
    kept, seen = _KeepTrace(), {}
    brief = harness.brief

    def keep_record(rec, *a, **kw):
        seen["rec"] = rec
        return brief(rec, *a, **kw)

    harness.brief, harness.shutil = keep_record, kept
    line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                            bool(args.trace), T_PROCESS_START)
    rec = seen["rec"]
    w0 = T_PROCESS_START + rec["metrics"]["setup_s"]
    w1 = w0 + args.seconds
    window = {"w0": w0, "window_s": args.seconds}
    account = {
        "workload": args.workload, "seed": args.seed, "trace": args.trace,
        "window": [w0, w1], "ring": ring_sums(w0, args.seconds),
        "tick_host_ms": {q: stage_gaps.tick_host_ms(window, q)
                         for q in (50, 99, 100)},
        "ticks": where_the_window_went(w0, args.seconds),
        "compile_before_window": compile_sums(runtime, w0),
        "gc_source": gc_source,
        "gc_in_window": read_gc(since=w0, until=w1),
        "gc_before_window": [e for e in read_gc(since=0.0, until=w0)
                             if e["generation"] == 2][-3:],
        "gc_after_window": [e for e in read_gc(since=w1, until=w1 + 1e9)
                            if e["generation"] == 2][:2],
        "runner": {k: rec.get(k) for k in (
            "attempted", "failed", "tokens_out", "requests_sent",
            "prompt_tokens_sent", "pred_stats", "pred_stats_window")},
        "metrics": rec["metrics"], "timings": rec.get("timings"),
        "prefill_key_blocks": prefill_key_blocks(),
        "decode_steps": decode_steps(),
    }
    if rec.get("runner") == "serve_open":
        account["runner"]["sample_prompt_tokens"] = sum(
            len(p) for p, _ in rec.get("finished", []))
    if args.trace and rec.get("trace_window"):
        # the `numbers` trace: the mix's `trace_s` from the tracer's start
        t0 = rec["trace_window"][0]
        t1 = t0 + float(harness.find_cell(ROOT, args.workload)["mix"][
            "trace_s"])
        account["traced"] = {"numbers_window": [t0, t1],
                             "ring": ring_sums(t0, t1 - t0)}
        if kept.kept:
            account["traced"].update(traced_prefills(kept.kept))
    if kept.kept:
        shutil.rmtree(kept.kept, ignore_errors=True)
    print(json.dumps({"account": account}, default=str), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"account": account, "line": line}, f, default=str)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

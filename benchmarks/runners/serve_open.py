"""Runner `serve_open`: an open loop against one replica. Requests
arrive on the mix's schedule whether or not earlier ones have finished;
each is timed from when it was due. Warm-up traffic at the same rate
brings the batch to steady state, the requests due inside the window
are the sample, and load goes on until the sample has drained.
"""
from __future__ import annotations

import threading
import time

from benchmarks.lib import loadgen, serve, stats
from benchmarks.lib.harness import memory_peak_bytes


def run(ctx):
    from paddle_tpu.serving import Router
    marks = [("start", ctx["t_process_start"]), ("imports", serve.clock())]
    model, pred, n_params = serve.build(ctx)
    marks.append(("weights_and_pool", serve.clock()))
    serve.warm(pred, ctx["mix"], ctx["seed"], ctx["cfg"]["vocab_size"])
    marks.append(("programs_warm", serve.clock()))
    router = Router([pred])
    rec = offer(ctx, router, pred, ctx["mix"])
    rec["params"] = n_params
    rec["setup_breakdown_s"] = serve.spans(
        marks + [("warmup_traffic", rec.pop("w0"))])
    del router, pred, model
    serve.release()
    return rec


def _thirds(sample, penalty_ms):
    """Median wait for the first token in each third of the window's
    requests, in order of arrival: a backlog that grows shows as a last
    third slower than the first."""
    out = []
    n = len(sample)
    for k in range(3):
        part = sample[k * n // 3:(k + 1) * n // 3]
        out.append(stats.percentile(
            [(r.t_events[0] - r.due) * 1e3 if r.ok else penalty_ms
             for r in part], 50))
    return out


def offer(ctx, router, pred, mix):
    """Warm-up traffic, the window and the drain against a router that
    is up; stops the router before it returns."""
    cfg, seed, seconds = ctx["cfg"], ctx["seed"], ctx["seconds"]
    vocab = cfg["vocab_size"]
    programs_warm = serve.programs(pred)
    warmup_s, drain_s = float(mix["warmup_s"]), float(mix["drain_max_s"])
    sched = loadgen.open_schedule(mix, warmup_s + seconds + drain_s,
                                  seconds)
    timeout_s = warmup_s + seconds + drain_s + 30.0

    tracer = serve.Tracer(ctx["trace_dir"], mix, seconds)
    sampler = serve.GaugeSampler() if ctx["trace_dir"] else None
    t0 = serve.clock() + 0.05
    w0, w1 = t0 + warmup_s, t0 + warmup_s + seconds
    tracer.start(w0)
    reqs, threads, sample, sample_threads = [], [], [], []
    programs_w0 = None
    for i, (due, p_len, o_len) in enumerate(sched):
        due_t = t0 + due
        if due_t >= w1 and all(r.t_end is not None for r in sample):
            break
        time.sleep(max(0.0, due_t - serve.clock()))
        if programs_w0 is None and due_t >= w0:
            programs_w0 = serve.programs(pred)
            if sampler:
                sampler.start()
        req = serve.Req(due_t, loadgen.tokens(seed, 1, i, p_len, vocab),
                        o_len)
        serve.send(router, req)
        th = threading.Thread(target=serve.consume, args=(req, timeout_s),
                              daemon=True)
        th.start()
        reqs.append(req)
        threads.append(th)
        if w0 <= due_t < w1:
            sample.append(req)
            sample_threads.append(th)
    deadline = max(serve.clock(), w1) + drain_s
    for th in sample_threads:
        th.join(timeout=max(0.0, deadline - serve.clock()))
    programs_end = serve.programs(pred)
    occupancy = sampler.stop() if sampler else None
    tracer.join()
    peak = memory_peak_bytes()
    stats_end = dict(pred.stats)
    waits = serve.stage_waits([r.handle.span.trace_id for r in sample]) \
        if ctx["trace_dir"] else []
    serve.shut_down(router, reqs)
    for th in threads:
        th.join(timeout=5.0)

    done = [r for r in sample if r.ok]
    failed = len(sample) - len(done)
    missed_gaps = sum(r.out_len - 1 for r in sample if not r.ok)
    penalty_ms = seconds * 1e3
    ttft = [(r.t_events[0] - r.due) * 1e3 for r in done]
    gaps = [(b - a) * 1e3 for r in done
            for a, b in zip(r.t_events, r.t_events[1:])]
    late = [(r.sent - r.due) * 1e3 for r in sample]
    rec = {
        "runner": "serve_open",
        "attempted": len(sample), "failed": failed,
        "statuses": sorted({str(r.status) for r in sample}),
        "metrics": {
            "ttft_p95_ms": stats.percentile(
                stats.with_failures(ttft, failed, penalty_ms), 95),
            "tpot_p95_ms": stats.percentile(
                stats.with_failures(gaps, missed_gaps, penalty_ms), 95),
            "setup_s": w0 - ctx["t_process_start"]},
        "timings": {"ttft_ms": stats.summary(ttft),
                    "tpot_ms": stats.summary(gaps),
                    "late_ms": stats.summary(late)},
        "ttft_thirds": _thirds(sample, penalty_ms),
        "late_ms": late, "router_wait_s": waits,
        "occupancy": occupancy,
        "requests_sent": len(reqs),
        "tokens_out": sum(len(r.tokens) for r in done),
        "window_s": seconds, "rate_per_s": mix["rate_per_s"], "w0": w0,
        "trace_window": tracer.window,
        "programs": serve.program_record(programs_warm, programs_w0,
                                         programs_end),
        "use_ragged": bool(pred.use_ragged), "fallbacks": serve.fallbacks(),
        "pred_stats": stats_end, "memory_peak_bytes": peak,
        "mean_decode_ctx": serve.mean_decode_ctx(done),
        "geometry": serve.geometry(pred, cfg),
        "finished": [(r.prompt, r.tokens) for r in done],
    }
    return rec

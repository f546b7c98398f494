"""Runner `serve_closed`: callers that wait for each reply. Each of the
mix's clients plays sessions of several turns over a shared system
prompt, sending a turn as soon as the previous answer has arrived. The
first session of client c starts at turn c mod `turns`: set-up puts the
system prompts and those sessions' earlier turns into the prefix cache
(prefills only, the earlier answers are stand-ins drawn from the seed),
so that the window opens on a mix of turn numbers and a filled cache.
The metric is the output tokens that arrived inside the window, over
the window: all the work of the window, whichever request it is for.
"""
from __future__ import annotations

import threading
import time

from benchmarks.lib import loadgen, serve, stats
from benchmarks.lib.harness import memory_peak_bytes


def _earlier_turns(session, sys_prompts, seed, vocab, cid, n_turns):
    """The history of `session` after `n_turns` turns, its answers
    stand-ins drawn from the seed; yields the history after each turn."""
    sys_k, user_lens, answer_lens = session
    history = list(sys_prompts[sys_k])
    for t in range(n_turns):
        history = history + loadgen.tokens(
            seed, 10 + cid, t + 1, user_lens[t] + answer_lens[t], vocab)
        yield history


def _client(router, sessions, sys_prompts, mix, seed, vocab, cid, state):
    max_len = int(mix["max_context"])
    serial = 0
    for n, session in enumerate(sessions):
        sys_k, user_lens, answer_lens = session
        skip = cid % int(mix["turns"]) if n == 0 else 0
        history = list(sys_prompts[sys_k])
        for history in _earlier_turns(session, sys_prompts, seed, vocab,
                                      cid, skip):
            pass                      # set-up has put these in the cache
        for u, a in list(zip(user_lens, answer_lens))[skip:]:
            if state["stop"].is_set():
                return
            if len(history) + u + a > max_len:
                break
            serial += 1
            prompt = history + loadgen.tokens(seed, 10 + cid, 100 + serial,
                                              u, vocab)
            req = serve.Req(serve.clock(), prompt, a)
            state["live"][cid] = req
            serve.send(router, req)
            serve.consume(req, state["timeout_s"])
            state["done"][cid].append(req)
            if not req.ok:
                break
            history = prompt + req.tokens


def run(ctx):
    from paddle_tpu.serving import Router
    cfg, mix, seed, seconds = ctx["cfg"], ctx["mix"], ctx["seed"], \
        ctx["seconds"]
    vocab, n_clients = cfg["vocab_size"], int(mix["clients"])
    marks = [("start", ctx["t_process_start"]), ("imports", serve.clock())]
    model, pred, n_params = serve.build(ctx)
    marks.append(("weights_and_pool", serve.clock()))
    serve.warm(pred, mix, seed, vocab)
    sys_prompts = [loadgen.tokens(seed, 2, k, mix["system_len"], vocab)
                   for k in range(mix["system_prompts"])]
    pools = loadgen.session_pool(mix, n_clients)
    for sp in sys_prompts:            # the window opens on a filled cache
        pred.generate([sp], max_new_tokens=1)
    for c in range(n_clients):        # and on a mix of turn numbers
        for history in _earlier_turns(pools[c][0], sys_prompts, seed, vocab,
                                      c, c % int(mix["turns"])):
            pred.generate([history], max_new_tokens=1)
    marks.append(("programs_warm", serve.clock()))
    programs_warm = serve.programs(pred)
    router = Router([pred])
    warmup_s = float(mix["warmup_s"])
    state = {"stop": threading.Event(), "live": [None] * n_clients,
             "done": [[] for _ in range(n_clients)],
             "timeout_s": warmup_s + seconds + 60.0}
    tracer = serve.Tracer(ctx["trace_dir"], mix, seconds)
    sampler = serve.GaugeSampler() if ctx["trace_dir"] else None
    t0 = serve.clock()
    w0, w1 = t0 + warmup_s, t0 + warmup_s + seconds
    tracer.start(w0)
    threads = [threading.Thread(
        target=_client, daemon=True,
        args=(router, pools[c], sys_prompts, mix, seed, vocab, c, state))
        for c in range(n_clients)]
    for th in threads:
        th.start()
    time.sleep(max(0.0, w0 - serve.clock()))
    programs_w0 = serve.programs(pred)
    stats_w0 = dict(pred.stats)
    if sampler:
        sampler.start()
    time.sleep(max(0.0, w1 - serve.clock()))
    state["stop"].set()
    programs_end = serve.programs(pred)
    stats_end = dict(pred.stats)
    occupancy = sampler.stop() if sampler else None
    tracer.join()
    peak = memory_peak_bytes()
    live = [r for r in state["live"] if r is not None]
    serve.shut_down(router, live)
    for th in threads:
        th.join(timeout=60.0)

    finished = [r for c in state["done"] for r in c]
    ended = [r for r in finished
             if r.t_end is not None and w0 <= r.t_end <= w1]
    done = [r for r in ended if r.ok]
    # a client whose join timed out has its last request only in `live`
    every = {id(r): r for r in finished + live}.values()
    tokens_out = sum(1 for r in every for t in r.t_events if w0 <= t < w1)
    e2e = [(r.t_end - r.sent) * 1e3 for r in done]
    ttft = [(r.t_events[0] - r.sent) * 1e3 for r in done]
    gaps = [(b - a) * 1e3 for r in done
            for a, b in zip(r.t_events, r.t_events[1:])]
    rec = {
        "runner": "serve_closed", "params": n_params,
        "attempted": len(ended), "failed": len(ended) - len(done),
        "statuses": sorted({str(r.status) for r in ended}),
        "metrics": {"serve_tokens_per_s": tokens_out / seconds,
                    "setup_s": w0 - ctx["t_process_start"]},
        "timings": {"request_ms": stats.summary(e2e),
                    "ttft_ms": stats.summary(ttft),
                    "tpot_ms": stats.summary(gaps)},
        "occupancy": occupancy, "tokens_out": tokens_out,
        "prompt_tokens_sent": sum(len(r.prompt) for r in finished
                                  if w0 <= r.sent < w1),
        "window_s": seconds, "clients": n_clients,
        "setup_breakdown_s": serve.spans(marks + [("warmup_traffic", w0)]),
        "trace_window": tracer.window,
        "programs": serve.program_record(programs_warm, programs_w0,
                                         programs_end),
        "use_ragged": bool(pred.use_ragged), "fallbacks": serve.fallbacks(),
        "pred_stats": stats_end,
        "pred_stats_window": {k: stats_end[k] - stats_w0.get(k, 0)
                              for k in stats_end
                              if isinstance(stats_end[k], (int, float))},
        "memory_peak_bytes": peak,
        "mean_decode_ctx": serve.mean_decode_ctx(done),
        "geometry": serve.geometry(pred, cfg),
        "finished": [(r.prompt, r.tokens) for r in done],
    }
    del router, pred, model, threads, state, live, ended, done
    serve.release()
    return rec

"""What the two serve runners share: the predictor behind a router,
warm-up of the programs a mix will use, a client that stamps its own
clock on every stream event, gauge sampling, and freeing the program's
state before the reference runs.
"""
from __future__ import annotations

import gc
import os
import shutil
import threading
import time

from benchmarks.lib import loadgen

clock = time.perf_counter


class Req:
    """One request as its client saw it (all times on `clock`);
    `t_events[i]` is when token i arrived."""
    __slots__ = ("due", "sent", "prompt", "out_len", "t_events", "t_end",
                 "status", "tokens", "handle")

    def __init__(self, due, prompt, out_len):
        self.due, self.prompt, self.out_len = due, prompt, out_len
        self.sent = None
        self.t_events, self.tokens = [], []
        self.t_end, self.status, self.handle = None, None, None

    @property
    def ok(self):
        return self.status == "ok" and len(self.tokens) == self.out_len


def send(router, req):
    req.sent = clock()
    req.handle = router.submit(req.prompt, max_new_tokens=req.out_len)


def consume(req, timeout_s):
    """Read the request's stream to its end; one event a decode tick,
    which may carry several tokens."""
    try:
        for ev in req.handle.stream(timeout=timeout_s):
            t = clock()
            if ev.kind == "token":
                toks = ev.span or (ev.token,)
                req.t_events.extend([t] * len(toks))
                req.tokens.extend(toks)
            else:
                req.t_end, req.status = t, ev.status
    except TimeoutError:
        req.t_end, req.status = clock(), "timeout"
        req.handle.cancel()


def build(ctx):
    """The model from the seed and the predictor at the configuration's
    geometry; every other setting is the predictor's default."""
    from paddle_tpu.inference import ContinuousBatchingPredictor
    model, n_params = ctx["builder"].build(ctx["cfg"], ctx["seed"])
    pred = ContinuousBatchingPredictor(model, **ctx["cfg"]["serve"])
    return model, pred, n_params


def warm(pred, mix, seed, vocab):
    """Compile every program the mix can reach, through the predictor's
    own `generate`: `prefill` = [[n, bucket]] batched full prefills,
    `suffix` = [[suffix bucket, prefix-page bucket]] suffix prefills over
    cached pages (the decode step and the page copy come with them)."""
    spec = mix.get("warm", {})
    top = pred.max_seq_len - 2
    serial = [0]

    def toks(n):
        serial[0] += 1
        return loadgen.tokens(seed, 900, serial[0], n, vocab)

    for n, bucket in spec.get("prefill", []):
        pred.generate([toks(min(bucket, top)) for _ in range(n)],
                      max_new_tokens=2)
    page = pred.page
    for sb, wpb in spec.get("suffix", []):
        covered = wpb * page
        if covered + sb > top:
            covered = (wpb // 2 + 1) * page
        base = toks(covered)
        pred.generate([base], max_new_tokens=1)
        pred.generate([base + toks(min(sb, top - covered))],
                      max_new_tokens=2)
    if spec.get("suffix"):
        base = toks(2 * page + page // 2)      # ends inside a page:
        pred.generate([base], max_new_tokens=1)    # copy-on-write next
        pred.generate([base + toks(page)], max_new_tokens=2)
    if pred.prefix_cache is not None:
        pred.prefix_cache.clear(pred.pool)


class GaugeSampler:
    """Samples the serve loop's gauges every `period_s` between start()
    and stop(): in-flight requests over slots, page utilisation."""

    def __init__(self, period_s=0.01):
        from paddle_tpu.observability import metrics
        self._g = {"in_flight": metrics.gauge("serving.in_flight"),
                   "slots": metrics.gauge("serving.slots"),
                   "page_util": metrics.gauge("serving.page_utilization")}
        self.period_s = period_s
        self.samples = {"occupancy": [], "page_util": []}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.wait(self.period_s):
            slots = self._g["slots"].value() or 1.0
            self.samples["occupancy"].append(
                self._g["in_flight"].value() / slots)
            self.samples["page_util"].append(self._g["page_util"].value())

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)
        return self.samples


class Tracer:
    """Two traces at the window's start, one after the other, run from a
    thread of its own so that the load never waits for it. `numbers`
    lasts the mix's `trace_s` seconds with the Python tracer off: every
    device number is read from it (the Python tracer slows the serve
    loop's tick by about a quarter, which would show as idle time).
    `names` lasts `trace_names_s` seconds with it on, and is read only
    to name the idle gaps by the host's frames."""

    def __init__(self, trace_dir, mix, seconds):
        self.trace_dir = trace_dir
        first = min(seconds, float(mix["trace_s"]))
        self.stages = [("numbers", first, 0),
                       ("names", min(seconds - first,
                                     float(mix.get("trace_names_s", 0))), 1)]
        self.start_at = self.window = None
        self._thread = threading.Thread(target=self._run, daemon=True)
        if trace_dir:
            self._prime()

    def _trace(self, name, seconds, python):
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = python
        jax.profiler.start_trace(os.path.join(self.trace_dir, name),
                                 profiler_options=options)
        time.sleep(seconds)
        jax.profiler.stop_trace()

    def _prime(self):
        """The profiler's first start stops every Python thread while it
        loads (2 s were seen, which queued a dozen requests and sent an
        unseen batch shape to the compiler inside the window): pay that
        here, during set-up, with a trace that is thrown away."""
        self._trace("prime", 0.0, 1)
        shutil.rmtree(os.path.join(self.trace_dir, "prime"),
                      ignore_errors=True)

    def _run(self):
        time.sleep(max(0.0, self.start_at - clock()))
        t0 = clock()
        for name, seconds, python in self.stages:
            if seconds > 0:
                self._trace(name, seconds, python)
        self.window = (t0, clock())

    def start(self, at):
        self.start_at = at
        if self.trace_dir:
            self._thread.start()

    def join(self):
        if self.trace_dir:
            self._thread.join()


def mean_decode_ctx(done):
    """Cached tokens a decode step attends to for one slot, averaged
    over the decode steps of `done` requests (a request of p prompt and
    o output tokens contributes o steps at p + o/2 on average)."""
    steps = sum(len(r.tokens) for r in done)
    if not steps:
        return None
    return sum((len(r.prompt) + len(r.tokens) / 2.0) * len(r.tokens)
               for r in done) / steps


def geometry(pred, cfg):
    import numpy as np
    heads = cfg["num_attention_heads"]
    return {"slots": pred.B, "page_size": pred.page,
            "q_heads": heads, "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // heads,
            "itemsize": int(np.dtype(pred.pool.k[0].dtype).itemsize)}


def spans(marks):
    """[(name, t)] -> {name: seconds since the previous mark}."""
    return {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}


def programs(pred):
    """The program signatures the predictor has compiled so far."""
    return set(getattr(pred, "_traced_sigs", ()))


def program_record(after_warm, at_window, at_end):
    """Counts for the record; a signature that first appears between the
    window's start and the run's end compiled where nothing should."""
    late = sorted(str(s) for s in at_end - (at_window or at_end))
    return {"after_warm": len(after_warm), "at_end": len(at_end),
            "compiled_in_window": len(late), "new_in_window": late}


def fallbacks():
    from paddle_tpu.observability import metrics
    return {",".join(f"{k}={v}" for k, v in sorted(s.labels.items())):
            s.value for s in
            metrics.counter("kernels.pallas_fallbacks").samples()}


def stage_waits(trace_ids):
    """Router-side wait of each finished request, from the program's
    own spans: the critical path's admission + dispatch + queue stages
    (seconds). Requests whose spans left the flight ring are skipped."""
    from paddle_tpu.observability import critpath, tracing
    by_trace = {}
    for s in tracing.flight_recorder().spans():
        by_trace.setdefault(s.get("trace"), []).append(s)
    waits = []
    for tid in trace_ids:
        spans = by_trace.get(tid)
        if not spans:
            continue
        d = critpath.stage_decomposition(spans, trace_id=tid)
        st = dict(d["stages"])
        if "queue" in st:
            waits.append(sum(st.get(k, 0.0) for k in
                             ("admission", "dispatch", "queue")))
    return waits


def shut_down(router, live):
    """Cancel what is still streaming and stop the replica (its serve
    loop evicts a cancelled request at its next tick)."""
    for req in live:
        if req.handle is not None and req.t_end is None:
            req.handle.cancel()
    router.shutdown(timeout=60.0)


def release():
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()

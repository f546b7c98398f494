"""One general traffic generator. A mix is a data file of parameters
(`benchmarks/traffic/<mix>.json`); this module turns it and a seed into
requests. The lengths, arrival gaps and session shapes of a mix are
fixed, drawn from the mix's own `shape_seed`, in a fixed order; the
run's seed fills in the token values (and makes the weights), so every
seed offers the same work at the same times. Another arrival sequence
is another mix file with another `shape_seed`.

The length and session arithmetic follows `tools/trace_replay.py`'s
`synthesize` (lognormal lengths, Zipf-chosen shared prefixes, sessions
of several turns); arrivals are exponential gaps and not counts a bin.
"""
from __future__ import annotations

import math

import numpy as np


def _rng(*words):
    return np.random.default_rng([int(w) & 0xFFFFFFFF for w in words]
                                 + [int(words[-1]) >> 32])


def lognormal_ints(rng, n, spec):
    """n lengths: exp(N(ln median, sigma)), rounded, clipped to
    [lo, hi]. `spec` = {"median", "sigma", "lo", "hi"}."""
    x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["lo"], spec["hi"]).astype(np.int64)


def tokens(seed, stream, index, n, vocab):
    """n token ids in [2, vocab), a function of (seed, stream, index)."""
    return _rng(stream, index, seed).integers(2, vocab, n).tolist()


def open_schedule(mix, horizon_s, period_s):
    """Open loop: [(due_s, prompt_len, out_len)] over `horizon_s`
    seconds at `mix["rate_per_s"]`. One cycle of `period_s` seconds (the
    window's length) is drawn from `shape_seed`: exponential gaps
    (Poisson arrivals, coefficient of variation 1) scaled to fill the
    cycle exactly, and a length pair for each arrival. The schedule
    repeats the cycle from its start, whatever the seed: the warm-up
    plays the cycle's head and the window one whole cycle from there,
    so every seed's window holds the same arrivals and sizes in the
    same order. (The cycle used to start where the seed chose; runs of
    different starts then read `ttft_p95_ms` twice as far apart as two
    runs of one start.)"""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * period_s)))
    shape = _rng(1, mix["shape_seed"])
    gaps = shape.exponential(1.0 / rate, n)
    gaps *= period_s / gaps.sum()
    p_len = lognormal_ints(shape, n, mix["prompt_len"])
    o_len = lognormal_ints(shape, n, mix["output_len"])
    out, due, i = [], 0.0, 0
    while due < horizon_s:
        j = i % n
        due += float(gaps[j])
        out.append((due, int(p_len[j]), int(o_len[j])))
        i += 1
    return out


def zipf_weights(n, s):
    w = np.array([1.0 / (k ** s) for k in range(1, n + 1)])
    return w / w.sum()


def session_pool(mix, n_clients):
    """Closed loop: for each client a list of session shapes (system
    prompt index, user lengths, answer lengths), all from `shape_seed`:
    every seed plays the same sessions and fills in its own tokens."""
    per = int(mix["sessions_per_client"])
    shape = _rng(1, mix["shape_seed"])
    sysw = zipf_weights(mix["system_prompts"], mix["system_zipf_s"])
    turns = int(mix["turns"])
    return [[(int(shape.choice(len(sysw), p=sysw)),
              lognormal_ints(shape, turns, mix["user_len"]).tolist(),
              lognormal_ints(shape, turns, mix["answer_len"]).tolist())
             for _ in range(per)] for _ in range(n_clients)]

"""Check `served_tokens`: the comparison that decides `correct` for a
served model. A cell's limits file names its check (`"check"`), and the
harness loads `benchmarks/checks/<check>.py` and calls
`decide(root, found, seed, record, control)`; this one reads the
runner's `record["finished"]` = [(prompt, served tokens)].

After the window, a sample of finished requests (drawn from the seed
over the whole list, the longest always in it) is replayed through the
plain reference, teacher-forced on the served tokens. At every served position the gap
`best reference logit - reference logit of the served token` is taken:
0 where the program's greedy token is the reference's argmax, small
where bfloat16 rounding flipped a near-tie of random weights, large
where a kernel, a mask, a page or a precision is wrong. The numbers
held to limits are the widest gap and the mean gap over positions.

With `control` (True for both, or a tuple of names) the same positions
are read once more for the reference computed in 8 bits (int8, fp8):
the gap of the token that the lower precision puts first. The control
has to fail the limits.
"""
from __future__ import annotations

import numpy as np


def draw_sample(finished, seed, n):
    """`n` of `finished` [(prompt, served)]: the longest, and one drawn
    from the seed out of each of n - 1 equal stretches of the list. The
    runners list requests in order of arrival or client by client, so
    the sample spreads over the window, its slots and its clients."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][0]) + len(finished[i][1]))
    rest = [i for i in range(len(finished)) if i != longest]
    k = min(max(0, n - 1), len(rest))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    pick = [rest[int((j + u) * len(rest) / k)]      # k == 0: no draw
            for j, u in enumerate(rng.random(k))]
    return [finished[i] for i in [longest] + pick]


CONTROLS = ("int8", "fp8")


def gaps(reference, cfg, seed, sample, control=False):
    """Per-position gaps over `sample`; returns a dict of arrays."""
    prog, top, agree = [], [], []
    quants = CONTROLS if control is True else tuple(control or ())
    ctl = {q: [] for q in quants}
    for prompt, served in sample:
        if not served:
            continue
        ids = list(prompt) + list(served[:-1])
        rows = np.arange(len(prompt) - 1, len(ids))
        ref = reference.logits_at(cfg, seed, ids, rows)
        best = ref.max(axis=-1)
        tok = np.asarray(served)
        prog.append(best - ref[np.arange(len(tok)), tok])
        agree.append(ref.argmax(axis=-1) == tok)
        top.append(best)
        for q in quants:
            low = reference.logits_at(cfg, seed, ids, rows, quant=q)
            ctok = low.argmax(axis=-1)
            ctl[q].append(best - ref[np.arange(len(ctok)), ctok])
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros((0,))
    return {"program": cat(prog), "best": cat(top), "agree": cat(agree),
            "control": {q: cat(v) for q, v in ctl.items()}}


def numbers(g):
    return {"gap_max": float(g.max()) if g.size else None,
            "gap_mean": float(g.mean()) if g.size else None}


def decide(root, found, seed, record, control=False):
    """The check's record: every number compared beside its limit, and
    `correct`. No finished request, or no served token, is not
    correct."""
    from benchmarks.lib import harness
    cfg, limits = found["cfg"], found["limits"]["limits"]
    reference = harness.load_module(root, "reference", cfg["reference"])
    return compare(reference, cfg, seed, record["finished"], limits,
                   found["limits"]["requests_compared"], control)


def compare(reference, cfg, seed, finished, limits, n_sample,
            control=False):
    sample = draw_sample(finished, seed, n_sample)
    g = gaps(reference, cfg, seed, sample, control)
    got = numbers(g["program"])
    compared = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    correct = bool(g["program"].size) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())
    rec = {"correct": correct, "compared": compared,
           "requests_compared": len(sample),
           "positions_compared": int(g["program"].size),
           "argmax_share": float(g["agree"].mean())
           if g["agree"].size else None,
           "largest_reference_logit": float(g["best"].max())
           if g["best"].size else None}
    if control:
        rec["control"], rec["control_fails"] = {}, {}
        for q in g["control"]:
            c = numbers(g["control"][q])
            rec["control"][q] = {k: {"value": c[k], "limit": limits[k],
                                     "fails": c[k] is not None
                                     and c[k] > limits[k]} for k in limits}
            rec["control_fails"][q] = any(
                v["fails"] for v in rec["control"][q].values())
    return rec

"""Check `served_and_drafted`: decides `correct` for a model served with
its own drafter on. Two halves, both held to limits:

(a) `served_tokens`' comparison, unchanged in kind: the served tokens of
    a sample of finished requests against the reference's `logits_at`,
    teacher-forced (`gap_max`, `gap_mean`).
(b) the same two numbers for the DRAFTED tokens of the same requests
    against the reference's `draft_logits_at` (`draft_gap_max`,
    `draft_gap_mean`): the best reference draft logit less the reference
    draft logit of the token the program drafted, at every tick's draft.

Greedy speculation is lossless and, with weights from a seed, accepted
at chance: without (b) a draft pass computed in a lower precision, or
not at all, would change no number the cell reports. It reads the
runner's `record["finished"]` = [(prompt, served)] and
`record["drafted"]` = [[[i, token]]] in the same order: `token` was
drafted for the place of served token i (runner `serve_open_drafted`),
by the module's row `len(prompt) + i - 2`.

With `control` the same positions are read once more for the reference
computed in 8 bits; the control has to fail EACH half.
"""
from __future__ import annotations

import numpy as np

HALVES = {"served": ("gap_max", "gap_mean"),
          "drafted": ("draft_gap_max", "draft_gap_mean")}


def draft_gaps(reference, cfg, seed, sample, quants=()):
    """Per-draft gaps over `sample` [(prompt, served, drafted)]."""
    prog, agree = [], []
    ctl = {q: [] for q in quants}
    for prompt, served, drafted in sample:
        if not drafted:
            continue
        ids = list(prompt) + list(served[:-1])
        at, tok = (np.asarray(x) for x in zip(*drafted))
        rows = len(prompt) + at - 2
        ref = reference.draft_logits_at(cfg, seed, ids, rows)
        best = ref.max(axis=-1)
        prog.append(best - ref[np.arange(len(tok)), tok])
        agree.append(ref.argmax(axis=-1) == tok)
        for q in quants:
            low = reference.draft_logits_at(cfg, seed, ids, rows, quant=q)
            ctl[q].append(best - ref[np.arange(len(tok)),
                                     low.argmax(axis=-1)])
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros((0,))
    return {"program": cat(prog), "agree": cat(agree),
            "control": {q: cat(v) for q, v in ctl.items()}}


def decide(root, found, seed, record, control=False):
    from benchmarks.lib import harness
    cfg = found["cfg"]
    reference = harness.load_module(root, "reference", cfg["reference"])
    served = harness.load_module(root, "checks", "served_tokens")
    return compare(reference, served, cfg, seed,
                   [(p, s, d) for (p, s), d in zip(record["finished"],
                                                   record["drafted"])],
                   found["limits"]["limits"],
                   found["limits"]["requests_compared"], control)


def compare(reference, served, cfg, seed, finished, limits, n_sample,
            control=False):
    """The check's record: every number compared beside its limit, and
    `correct`. No served token, or no drafted one, is not correct."""
    quants = served.CONTROLS if control is True else tuple(control or ())
    sample = served.draw_sample(finished, seed, n_sample)
    a = served.gaps(reference, cfg, seed, [s[:2] for s in sample], quants)
    b = draft_gaps(reference, cfg, seed, sample, quants)

    def numbers(served_gaps, drafted_gaps):
        d = served.numbers(drafted_gaps)
        return dict(served.numbers(served_gaps), draft_gap_max=d["gap_max"],
                    draft_gap_mean=d["gap_mean"])

    got = numbers(a["program"], b["program"])
    compared = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    correct = bool(a["program"].size) and bool(b["program"].size) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())
    share = lambda x: float(x.mean()) if x.size else None
    rec = {"correct": correct, "compared": compared,
           "requests_compared": len(sample),
           "positions_compared": int(a["program"].size),
           "drafts_compared": int(b["program"].size),
           "argmax_share": share(a["agree"]),
           "draft_argmax_share": share(b["agree"]),
           "largest_reference_logit": float(a["best"].max())
           if a["best"].size else None}
    if quants:
        rec["control"], rec["control_fails"] = {}, {}
        for q in quants:
            c = numbers(a["control"][q], b["control"][q])
            rec["control"][q] = {k: {"value": c[k], "limit": limits[k],
                                     "fails": c[k] is not None
                                     and c[k] > limits[k]} for k in limits}
            rec["control_fails"][q] = all(
                any(rec["control"][q][k]["fails"] for k in keys
                    if k in limits) for keys in HALVES.values())
    return rec

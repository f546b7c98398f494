"""Runner `serve_open_drafted`: `serve_open`'s open loop against one
replica whose model drafts for itself, with a client that also keeps
what each tick's verify was given (`StreamEvent.drafted`). The record
has `serve_open`'s keys plus `drafted`: for each finished request, in
the order of `finished`, [[i, token]] = the token drafted for the place
of served token i (accepted or not).

The loop itself is `serve_open`'s, run on a copy of that module whose
`serve` parts are `lib/serve.py`'s with the request and the `consume`
below in place of its own: one schedule, one window, one record.
"""
from __future__ import annotations

import os
import types

from benchmarks.lib import harness, serve


class Req(serve.Req):
    """`serve.Req` with `drafted`: [(index of the served token it was
    proposed for, drafted token)]."""
    __slots__ = ("drafted",)

    def __init__(self, due, prompt, out_len):
        super().__init__(due, prompt, out_len)
        self.drafted = []


def consume(req, timeout_s):
    """`serve.consume`, keeping each token event's drafts: `drafted[i]`
    was proposed for the place of `span[i]`."""
    try:
        for ev in req.handle.stream(timeout=timeout_s):
            t = serve.clock()
            if ev.kind == "token":
                toks = ev.span or (ev.token,)
                req.drafted.extend(
                    (len(req.tokens) + i, d)
                    for i, d in enumerate(getattr(ev, "drafted", ())))
                req.t_events.extend([t] * len(toks))
                req.tokens.extend(toks)
            else:
                req.t_end, req.status = t, ev.status
    except TimeoutError:
        req.t_end, req.status = serve.clock(), "timeout"
        req.handle.cancel()


def _open_loop():
    """A private copy of the `serve_open` runner that sends `Req`s and
    reads them with `consume`, and the list its requests are kept in."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    made = []

    def make(*args):
        made.append(Req(*args))
        return made[-1]

    loop = harness.load_module(root, "runners", "serve_open")
    loop.serve = types.SimpleNamespace(**dict(vars(serve), Req=make,
                                              consume=consume))
    return loop, made


def _with_drafts(rec, made):
    """`made` is in the order sent, and `finished` a subsequence of it
    (`serve_open` keeps the window's requests in that order): each
    finished request is the next one made with its prompt and tokens."""
    left = iter(made)
    rec["drafted"] = []
    for prompt, toks in rec["finished"]:
        for r in left:
            if r.prompt == prompt and r.tokens == toks:
                break
        else:
            raise LookupError("a finished request that was never sent")
        rec["drafted"].append([list(d) for d in r.drafted])
    rec["runner"] = "serve_open_drafted"
    return rec


def offer(ctx, router, pred, mix):
    loop, made = _open_loop()
    return _with_drafts(loop.offer(ctx, router, pred, mix), made)


def run(ctx):
    loop, made = _open_loop()
    return _with_drafts(loop.run(ctx), made)

"""Token streaming primitives for the serving front end.

`ContinuousBatchingPredictor.generate()` is return-at-end: the caller
sees nothing until every request in the call finishes. Interactive
serving needs tokens as decode ticks complete; this module defines the
stream surface both the predictor (`generate_stream`) and the router
(`RequestHandle.stream`) expose:

- :class:`StreamEvent` — one stream element: a decoded token (kind
  ``"token"``) or a request's terminal record (kind ``"end"``, carrying
  the final status). Timestamps come from the PR-5 span events (the
  request span's ``first_token``/``token`` events are the stream's
  timing source, so trace_report and the live stream agree on TTFT).
- :class:`TokenStream` — the iterator `generate_stream` returns.
  Wraps the serve-loop generator; `cancel(r)` evicts one request at
  the next loop iteration (its KV pages return to the pool,
  ``last_status[r] == "cancelled"``), and abandoning/closing the
  iterator cancels everything still pending the same way — a consumer
  that stops iterating cannot leak pages or slots.
- :class:`ServeRequest` — the dynamic-intake work item
  (`ContinuousBatchingPredictor.serve_stream`): per-request prompt,
  token budget, tier, deadline, and an opaque `meta` the router uses
  to map stream events back to its handles.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional

__all__ = ["StreamEvent", "TokenStream", "ServeRequest"]


class StreamEvent(NamedTuple):
    """One element of a token stream.

    `request` is the index within the originating call (or the running
    intake index for `serve_stream`); `index` is the 1-based ordinal of
    the token within its request (0 on "end"); `ts` is the span-event
    wall-clock timestamp when tracing is enabled, else time.time() at
    emission; `status` is the terminal status on "end" events (ok /
    deadline / shed / cancelled / watchdog / rejected_*); `meta` is the
    ServeRequest.meta passthrough (None for the list-based APIs).

    **Token spans.** One "token" event is emitted per DECODE TICK, not
    per token: with speculative decoding a tick commits several tokens
    at once, and `span` carries the whole tuple in order. `token` is
    the span's LAST token and `index` its ordinal, so single-token
    consumers keep working unchanged (`span == (token,)` on ordinary
    ticks). Consumers that must see every token iterate `span`; the
    first span token's ordinal is ``index - len(span) + 1``.

    **Drafted tokens.** When the served model drafts for itself (a
    multi-token-prediction module behind ``spec_draft_tokens``),
    `drafted` carries what the tick's verify was given: ``drafted[i]``
    was proposed for the place of ``span[i]``, accepted (then the two
    are equal and the span goes on) or not. A client that wants the
    drafts reads the field; nothing else changes for one that does
    not. Empty on every other tick."""
    request: int
    kind: str                      # "token" | "end"
    token: Optional[int] = None
    index: int = 0
    ts: float = 0.0
    status: Optional[str] = None
    meta: object = None
    span: tuple = ()
    drafted: tuple = ()


class ServeRequest(NamedTuple):
    """Dynamic-intake work item for ContinuousBatchingPredictor
    .serve_stream: one request with its own budget/tier/deadline.
    `deadline_s` is seconds from the moment the serve loop first sees
    the request. `meta` rides through to every StreamEvent.
    `sampling` is an optional generation.sampling.SamplingParams —
    per-request temperature/top-k/top-p/seed served as batched operands
    by the on-device sampling decode program (the predictor must be
    constructed with ``sampling_enabled=True``; None = greedy).
    `trace` is an optional observability.TraceContext: the serve loop
    parents its ``serve.request`` span on it so the replica's spans
    join the submitter's trace instead of minting a fresh one (None =
    local root under ``serve.generate``)."""
    prompt: List[int]
    max_new_tokens: int = 32
    tier: Optional[str] = None
    deadline_s: Optional[float] = None
    meta: object = None
    sampling: object = None
    trace: object = None


class TokenStream:
    """Iterator over a serve loop's StreamEvents with cancellation.

    Produced by `generate_stream` / `serve_stream`. Iterating drives
    the serve loop (admission, decode dispatch, resolution) — the loop
    only advances while the consumer pulls. `results`/`status` are
    filled in place as requests finish and are complete once the
    iterator is exhausted; `drain()` consumes the rest and returns
    `results`.

    Cancellation: `cancel(r)` marks one request (None = all); at the
    serve loop's next iteration the request is evicted, its pages are
    released, and an "end" event with status "cancelled" is emitted.
    `close()` (also called by the generator protocol when the consumer
    abandons the iterator) cancels every still-pending request
    synchronously — pool refcounts return to baseline.
    """

    def __init__(self, gen, results: List, status: List, cancel_set: set):
        self._gen = gen
        self.results = results
        self.status = status
        self._cancel = cancel_set
        self._closed = False

    def __iter__(self):
        return self

    def __next__(self) -> StreamEvent:
        try:
            return next(self._gen)
        except StopIteration:
            self._closed = True
            raise

    def cancel(self, request: Optional[int] = None):
        """Cancel one request (or all with None). Takes effect at the
        serve loop's next iteration; safe to call from another thread
        than the consumer's (set.add is atomic under the GIL)."""
        if request is None:
            self._cancel.add("*")
        else:
            self._cancel.add(int(request))

    def close(self):
        """Cancel everything still pending and finish the loop NOW:
        runs the generator's cleanup (page release, span end, status
        "cancelled") synchronously."""
        if self._closed:
            return
        self._closed = True
        self._cancel.add("*")
        # advance once so the loop observes the cancel and evicts with
        # page release (generator .close() alone would only unwind)
        try:
            for _ in self._gen:
                pass
        except Exception:
            pass
        self._gen.close()

    def drain(self) -> List:
        """Consume the remaining events and return `results`."""
        for _ in self:
            pass
        return self.results

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

"""The per-layer readers of the serve loop's prefill account, of the
tokens its ticks handed out and of the collector's pauses
(`lib/prefill_account.py`), against a hand-made ring and log with known
answers, and against a ring and a program that lack them.

Run by hand, from the repository's root:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""
import collections
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks.lib import harness, prefill_account  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
OPEN = ["dsllm7b-chat-open", "granite4h-chat-open",
        "keye2-longprompt-open", "ling3f-longdoc-open"]
CLOSED = ["mistral7b-sessions-closed"]
NEW = {"serve.prefill_wall_share_pct.open": ("%", "ttft_p95_ms", OPEN),
       "serve.prefill_wall_share_pct.closed": ("%", "serve_tokens_per_s",
                                               CLOSED),
       "serve.prefill_us_per_token.open": ("us", "ttft_p95_ms", OPEN),
       "serve.prefill_us_per_token.closed": ("us", "serve_tokens_per_s",
                                             CLOSED),
       "serve.prefill_pad_pct.open": ("%", "ttft_p95_ms", OPEN),
       "serve.prefill_pad_pct.closed": ("%", "serve_tokens_per_s", CLOSED),
       "serve.stalled_token_pct.open": ("%", "tpot_p95_ms", OPEN),
       "serve.decode_stall_p95_ms.open": ("ms", "tpot_p95_ms", OPEN),
       "host.gc_pause_max_ms.open": ("ms", "ttft_p95_ms", OPEN),
       "host.gc_pause_max_ms.closed": ("ms", "serve_tokens_per_s", CLOSED)}
W0, WINDOW_S = 100.0, 10.0


def _tick(t0, name="serve.tick", **fields):
    """A record as the program's ring keeps it: flat."""
    rec = {"name": name, "replica": "", "t0": t0, "dur": 0.01,
           "active": 1, "admitted": 0, "prefill": "pf_s" in fields,
           "serve.resolve": 0.002, "serve.resolve.wait": 0.001}
    rec.update(fields)
    return rec


def _ring(monkeypatch, ticks):
    from paddle_tpu.observability import tracing
    monkeypatch.setattr(tracing, "_ticks", collections.deque(
        sorted(ticks, key=lambda t: t["t0"])))


ACCOUNT = [
    # before the window and at its end: never read
    _tick(W0 - 1.0, pf_s=5.0, pf_tokens=9, pf_padded=9, tokens=50, first=0),
    _tick(W0 + WINDOW_S, pf_s=5.0, pf_tokens=9, pf_padded=9, tokens=50,
          first=0),
    # another loop's tick inside it: never read
    _tick(W0 + 2.0, name="train.tick", pf_s=7.0, tokens=70, first=0),
    # one program: 2 firsts, 3 slots' tokens waited 0.2 s
    _tick(W0 + 0.5, pf_n=2, pf_s=0.2, pf_tokens=300, pf_padded=512,
          pf_stalled=3, tokens=5, first=2),
    # two programs in one pass (0.1 + 0.5 s): 1 first, 6 tokens waited
    _tick(W0 + 3.0, pf_n=3, pf_s=0.6, pf_tokens=900, pf_padded=1536,
          pf_stalled=12, tokens=7, first=1),
    # a prefill nobody waited for: its one token is a first
    _tick(W0 + 4.0, pf_n=1, pf_s=0.4, pf_tokens=100, pf_padded=256,
          pf_stalled=0, tokens=1, first=1),
    # a pass that left the loop early notes nothing
    _tick(W0 + 9.5),
] + [_tick(W0 + 1.0 + 0.01 * k, tokens=4, first=0) for k in range(10)]


def _record(**kw):
    rec = {"trace_window": (W0, W0 + 4.5), "window_s": WINDOW_S}
    rec.update(kw)
    return rec


def test_sums_are_the_windows_ticks_alone(monkeypatch):
    _ring(monkeypatch, ACCOUNT)
    s = prefill_account.sums(_record())
    assert s["pf_s"] == pytest.approx(1.2)
    assert (s["pf_tokens"], s["pf_padded"], s["pf_chunk"]) == (1300, 2304, 0)
    assert (s["tokens"], s["first"], s["gap_tokens"]) == (53, 4, 49)
    assert s["stalled_tokens"] == 9
    assert sorted(s["stalls_s"]) == [0.2] * 3 + [0.6] * 6


def test_the_six_quantities_by_hand(monkeypatch):
    _ring(monkeypatch, ACCOUNT)
    rec = _record()
    assert prefill_account.wall_share_pct(rec) == pytest.approx(12.0)
    assert prefill_account.us_per_token(rec) == pytest.approx(1.2e6 / 1300)
    assert prefill_account.pad_pct(rec) == pytest.approx(
        100.0 * (1 - 1300 / 2304))
    assert prefill_account.stalled_token_pct(rec) == pytest.approx(
        100.0 * 9 / 49)
    # nine stalled tokens: position 7.6 of 0..8 lies among the 0.6 s ones
    assert prefill_account.decode_stall_p95_ms(rec) == pytest.approx(600.0)
    # the window's own start, where a runner keeps it, comes first
    late = _record(w0=W0 + 3.5, window_s=6.5)
    assert prefill_account.wall_share_pct(late) == pytest.approx(
        100.0 * 0.4 / 6.5)
    assert prefill_account.stalled_token_pct(late) is None   # no gap token
    assert prefill_account.decode_stall_p95_ms(late) is None


def test_chunk_tokens_silence_the_seconds_a_token(monkeypatch):
    _ring(monkeypatch, ACCOUNT + [
        _tick(W0 + 5.0, pf_tokens=64, pf_chunk=64, pf_padded=128,
              tokens=3, first=0)])
    rec = _record()
    assert prefill_account.us_per_token(rec) is None
    assert prefill_account.pad_pct(rec) == pytest.approx(
        100.0 * (1 - 1364 / 2432))
    assert prefill_account.wall_share_pct(rec) == pytest.approx(12.0)
    assert prefill_account.stalled_token_pct(rec) == pytest.approx(
        100.0 * 9 / 52)


def test_a_window_with_ticks_and_no_prefill(monkeypatch):
    _ring(monkeypatch, [_tick(W0 + 1.0, tokens=4, first=0)])
    rec = _record()
    assert prefill_account.wall_share_pct(rec) == 0.0
    assert prefill_account.stalled_token_pct(rec) == 0.0
    assert prefill_account.us_per_token(rec) is None
    assert prefill_account.pad_pct(rec) is None
    assert prefill_account.decode_stall_p95_ms(rec) is None


@pytest.mark.parametrize("fn", ["wall_share_pct", "us_per_token", "pad_pct",
                                "stalled_token_pct", "decode_stall_p95_ms"])
def test_a_ring_without_the_fields_reads_nothing(monkeypatch, fn):
    """A parent commit's ticks: stages and the three older notes."""
    _ring(monkeypatch, [_tick(W0 + 0.1 * k) for k in range(50)])
    assert getattr(prefill_account, fn)(_record()) is None
    assert getattr(prefill_account, fn)({"window_s": 10.0}) is None
    _ring(monkeypatch, [])
    assert getattr(prefill_account, fn)(_record()) is None


def test_the_longest_pause_that_began_in_the_window(monkeypatch):
    from paddle_tpu.observability import runtime

    def ev(t, generation, seconds):
        return {"t": t, "generation": generation, "seconds": seconds,
                "collected": 0}

    monkeypatch.setattr(runtime, "_gc_log", collections.deque([
        ev(W0 - 1.0, 2, 0.5), ev(W0 + 1.0, 1, 0.002), ev(W0 + 5.0, 2, 0.3),
        ev(W0 + 6.0, 2, 0.25), ev(W0 + WINDOW_S + 1.0, 2, 0.9)]))
    assert prefill_account.gc_pause_max_ms(_record()) == pytest.approx(300.0)
    assert prefill_account.gc_pause_max_ms(
        _record(w0=W0 + 7.0, window_s=3.0)) == 0.0
    assert prefill_account.gc_pause_max_ms({"window_s": 10.0}) is None
    monkeypatch.delattr(runtime, "gc_log")       # a parent commit
    assert prefill_account.gc_pause_max_ms(_record()) is None


def test_the_real_program_fills_what_the_readers_read():
    """Prompts of 5 and 11 tokens and one of 7 behind them, budgets 9, 2
    and 2, on two slots: three programs of 8, 16 and 8 positions, and the
    third finds one slot owed a token."""
    import time
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingPredictor
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving.streaming import ServeRequest
    paddle.seed(0)
    pred = ContinuousBatchingPredictor(
        LlamaForCausalLM(LlamaConfig.tiny()), max_batch_size=2,
        page_size=8, max_seq_len=64)
    prompts = [list(range(a, a + n)) for a, n in ((10, 5), (30, 11), (60, 7))]
    w0 = time.perf_counter()
    reqs = [ServeRequest(p, n) for p, n in zip(prompts, (9, 2, 2))]
    events = list(pred._serve(reqs, None, [], [], set(), None, 9))
    rec = {"w0": w0, "window_s": time.perf_counter() - w0}
    assert sum(len(e.span) for e in events if e.kind == "token") == 13
    s = prefill_account.sums(rec)
    assert (s["pf_tokens"], s["pf_padded"]) == (23, 32)
    assert (s["tokens"], s["first"], s["stalled_tokens"]) == (13, 3, 1)
    assert prefill_account.pad_pct(rec) == pytest.approx(100 * 9 / 32)
    assert prefill_account.stalled_token_pct(rec) == pytest.approx(10.0)
    assert 0 < prefill_account.wall_share_pct(rec) <= 100
    assert prefill_account.us_per_token(rec) > 0
    assert prefill_account.decode_stall_p95_ms(rec) > 0
    assert prefill_account.gc_pause_max_ms(rec) >= 0.0


# ------------------------------------------------------------ the table --

def test_every_new_metric_has_its_file_and_its_cells():
    table = {m["name"]: m for m in BENCH["per_layer"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    reports = {e["name"]: set(e.get("workloads", cells))
               for e in BENCH["end_to_end"]}
    for name, (unit, moves, workloads) in NEW.items():
        m = table[name]
        assert (m["unit"], m["moves"], m["workloads"]) == \
            (unit, moves, workloads), name
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["layer"] == "serve loop, host"
        assert set(workloads) <= reports[moves], name
        mod = harness.load_module(ROOT, "layer_metrics", name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
            (name, unit, m["layer"], moves)
        assert mod.read({"window_s": 10.0}, {}) is None
    assert [m["name"] for m in BENCH["per_layer"]][-len(NEW):] == list(NEW)

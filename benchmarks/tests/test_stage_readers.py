"""The per-layer readers of the serve loop's tick ring, the compile log
and the stage annotations (`lib/stage_gaps.py`), against a hand-made
ring, log and trace with known answers, and against the recorded trace.

Run by hand, from the repository's root:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""
import collections
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks.lib import harness, stage_gaps, trace_reduce  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW = ["serve.tick_host_ms.open", "serve.tick_host_ms.closed",
       "serve.tick_host_p99_ms.open", "device.idle_named_pct.open",
       "device.idle_named_pct.closed", "device.idle_admit_pct.open",
       "device.idle_dispatch_pct.open", "serve.retraces_in_window.open",
       "serve.retraces_in_window.closed", "setup.jit_trace_s",
       "setup.jit_compile_s"]
W0, WINDOW_S = 100.0, 10.0


def _tick(t0, dur, wait=0.0, name="serve.tick"):
    """A record as the program's ring keeps it: flat, the stages'
    seconds under their dotted names."""
    return {"name": name, "replica": "", "t0": t0, "dur": dur,
            "active": 1, "admitted": 0, "prefill": False,
            "serve.resolve": wait + 0.001, "serve.resolve.wait": wait}


@pytest.fixture
def ring(monkeypatch):
    """101 ticks in the window with host times 1..101 ms (each waits
    20 ms on the device beside), two outside it, one of another loop."""
    from paddle_tpu.observability import tracing
    ticks = [_tick(W0 - 5.0, 9.0, 0.0), _tick(W0 + WINDOW_S, 9.0, 0.0),
             _tick(W0 + 1.0, 7.0, 0.0, name="train.tick")]
    ticks += [_tick(W0 + 0.05 * k, 0.020 + 0.001 * (k + 1), 0.020)
              for k in range(101)]
    monkeypatch.setattr(tracing, "_ticks", collections.deque(
        sorted(ticks, key=lambda t: t["t0"])))


@pytest.fixture
def log(monkeypatch):
    from paddle_tpu.observability import runtime

    def ev(t, kind, seconds=0.0, sig=None):
        return (t, kind, seconds, sig)      # as the program keeps them

    monkeypatch.setattr(runtime, "_compile_log", collections.deque([
        ev(10.0, "trace", 2.0), ev(11.0, "lower", 1.0),
        ev(12.0, "compile", 4.0), ev(12.5, "cache_hit"),
        ev(20.0, "trace", 3.0), ev(21.0, "cache_miss"),
        ev(21.5, "compile", 0.5),
        # inside the window: one unwarmed shape (its nested traces too)
        ev(W0 + 2.0, "trace", 0.7, "('prefill', (8, 256), (8, 16))"),
        ev(W0 + 2.1, "trace", 0.1, "('prefill', (8, 256), (8, 16))"),
        ev(W0 + 4.0, "compile", 2.0, "('prefill', (8, 256), (8, 16))"),
        # the reference check compiles after the window: never counts
        ev(W0 + WINDOW_S + 0.5, "trace", 9.0),
        ev(W0 + WINDOW_S + 1.0, "compile", 9.0)]))


def _record(**kw):
    rec = {"trace_window": (W0, W0 + 4.5), "window_s": WINDOW_S}
    rec.update(kw)
    return rec


# ------------------------------------------------------------ tick ring --

def test_tick_host_time_is_the_tick_less_the_wait(ring):
    rec = _record()
    assert len(stage_gaps.window_ticks(rec)) == 101
    assert stage_gaps.tick_host_ms(rec, 50) == pytest.approx(51.0)
    assert stage_gaps.tick_host_ms(rec, 99) == pytest.approx(100.0)
    assert stage_gaps.tick_host_ms(rec, 100) == pytest.approx(101.0)
    # the window's own start, where a runner keeps it, comes first
    assert len(stage_gaps.window_ticks(_record(w0=W0 + 2.5,
                                               window_s=7.5))) == 51
    assert stage_gaps.tick_host_ms(_record(window_s=None), 50) is None
    assert stage_gaps.tick_host_ms({"window_s": 10.0}, 50) is None


def test_no_ticks_in_the_window_reads_nothing(ring):
    assert stage_gaps.tick_host_ms(_record(w0=500.0), 50) is None


# ---------------------------------------------------------- compile log --

def test_compile_log_is_cut_by_the_window(log):
    rec = _record()
    assert stage_gaps.retraces_in_window(rec) == 2.0
    assert stage_gaps.setup_seconds(rec, ("trace",)) == pytest.approx(5.0)
    assert stage_gaps.setup_seconds(rec, ("lower", "compile")) == \
        pytest.approx(5.5)
    # a sound run: nothing traced between the window's start and its end
    assert stage_gaps.retraces_in_window(_record(w0=W0 + 5.0,
                                                 window_s=5.0)) == 0.0
    assert stage_gaps.retraces_in_window({"window_s": 10.0}) is None


def test_an_unwarmed_shape_in_the_window_counts_and_later_ones_do_not():
    """The real program: a prefill bucket the warm-up never compiled,
    sent inside the window, is one or more retraces; one sent after the
    window's end (where the reference check compiles) is none."""
    import time
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingPredictor
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    pred = ContinuousBatchingPredictor(
        LlamaForCausalLM(LlamaConfig.tiny()), max_batch_size=2,
        page_size=8, max_seq_len=64)
    pred.generate([[3, 4, 5]], max_new_tokens=3)          # warm: bucket 8
    w0 = time.perf_counter()
    pred.generate([[5, 4, 3]], max_new_tokens=3)
    sound = {"w0": w0, "window_s": time.perf_counter() - w0}
    assert stage_gaps.retraces_in_window(sound) == 0.0
    w0 = time.perf_counter()
    pred.generate([list(range(2, 22))], max_new_tokens=3)     # bucket 32
    rec = {"w0": w0, "window_s": time.perf_counter() - w0}
    pred.generate([list(range(2, 12))], max_new_tokens=3)     # bucket 16
    assert stage_gaps.retraces_in_window(rec) >= 1.0
    from paddle_tpu.observability import runtime
    tagged = [e for e in runtime.compile_log(rec["w0"], rec["w0"]
                                             + rec["window_s"])
              if e["sig"] and e["sig"].startswith("('prefill', (1, 32)")]
    assert [e["kind"] for e in tagged].count("compile") == 1
    assert stage_gaps.tick_host_ms(rec, 50) > 0
    assert stage_gaps.setup_seconds(rec, ("trace",)) > 0


# -------------------------------------------------------- idle, by stage --

def _planes():
    """One device: ops busy [0,1] [2,3] [3.5,4] [6,7] [9,10]: gaps
    (1,2) (3,3.5) (4,6) (7,9), 5.5 s idle in a window of 10. The serve
    thread ticks from 1.5 to 4.3 and from 4.5 to 8.5 (the trace's edges
    and 0.2 s between the ticks are under no stage): admit [1.5,2.5]
    (prefill [1.6,2.4] inside), dispatch [4.5,5.0], admit [5.0,5.5],
    dispatch [5.5,7.5]. Another thread's `serve.admit` names nothing."""
    dev = {"name": "/device:TPU:0", "lines": {"XLA Ops": [
        ["%a = f32[] add()", 0.0, 1.0], ["%b = f32[] add()", 2.0, 1.0],
        ["%c = f32[] add()", 3.5, 0.5], ["%d = f32[] add()", 6.0, 1.0],
        ["%e = f32[] add()", 9.0, 1.0], ["%z = f32[] add()", 9.5, 0.0]]}}
    serve = [["serve.tick", 1.5, 2.8], ["serve.admit", 1.5, 1.0],
             ["serve.prefill#n=2,bucket=256,traces=ab,cd#", 1.6, 0.8],
             ["serve.tick", 4.5, 4.0], ["serve.dispatch", 4.5, 0.5],
             ["serve.admit", 5.0, 0.5], ["serve.dispatch", 5.5, 2.0],
             ["PjitFunction(_raw_decode_step)", 5.6, 0.1]]
    other = [["serve.admit", 0.0, 10.0], ["consume", 0.0, 10.0]]
    host = {"name": "/host:CPU", "lines": {"serve-loop": serve,
                                           "client": other}}
    return [dev, host]


def test_idle_seconds_fall_under_the_stages_that_cover_them():
    r = stage_gaps.idle_of_planes(_planes())
    assert r["window_s"] == pytest.approx(10.0)
    assert r["idle_s"] == pytest.approx(5.5)
    by = r["by_stage"]
    # gap (1,2): admit from 1.5; gap (3,3.5): under the tick alone;
    # gap (4,6) STRADDLES the first tick's end, 0.2 s under no stage,
    # dispatch, admit and dispatch again;
    # gap (7,9): dispatch to 7.5, the tick to 8.5, nothing after
    assert by["serve.admit"] == pytest.approx(0.5 + 0.5)
    assert by["serve.prefill"] == pytest.approx(0.4)
    assert by["serve.dispatch"] == pytest.approx(0.5 + 0.5 + 0.5)
    assert by["serve.tick"] == pytest.approx(0.5 + 0.5 + 1.8 + 1.5)
    assert r["named_s"] == pytest.approx(4.3)
    # the edges before the first tick and after the last are left out
    assert r["idle_in_ticks_s"] == pytest.approx(0.5 + 0.5 + 2.0 + 1.5)
    assert "consume" not in by and "PjitFunction(_raw_decode_step)" not in by


def test_a_trace_without_annotations_or_without_a_device_reads_nothing():
    dev, host = _planes()
    bare = dict(host, lines={"client": host["lines"]["client"]})
    assert stage_gaps.idle_of_planes([dev, bare]) is None
    assert stage_gaps.idle_of_planes([host]) is None
    assert stage_gaps.idle_of_planes([]) is None


def test_recorded_trace_with_stages_laid_over_it_against_a_grid():
    planes = harness.load_json(os.path.join(DATA, "planes_small.json"))
    dev = [p for p in planes if trace_reduce.DEVICE_PLANE.match(p["name"])]
    ops = dev[0]["lines"][trace_reduce.OPS_LINE]
    t0 = min(s for _, s, _ in ops)
    t1 = max(s + d for _, s, d in ops)
    third = (t1 - t0) / 3
    planes = planes + [{"name": "/host:serve", "lines": {"loop": [
        ["serve.tick", t0 - 1.0, t1 - t0 + 2.0],
        ["serve.admit", t0, third],
        ["serve.dispatch", t0 + 2 * third, third]]}}]
    r = stage_gaps.idle_of_planes(planes)
    step = 1e-6
    n = int(np.ceil((t1 - t0) / step)) + 1
    busy = np.zeros(n, bool)
    for _, s, d in ops:
        busy[int(round((s - t0) / step)):int(round((s + d - t0) / step))] = 1
    idle = ~busy[:int(round((t1 - t0) / step))]
    k = len(idle) // 3
    assert r["window_s"] == pytest.approx(t1 - t0)
    assert r["idle_s"] == pytest.approx(idle.sum() * step, rel=0.05)
    assert r["named_s"] == pytest.approx(r["idle_s"])
    assert r["by_stage"]["serve.admit"] == pytest.approx(
        idle[:k].sum() * step, rel=0.05, abs=2e-5)
    assert r["by_stage"]["serve.dispatch"] == pytest.approx(
        idle[2 * k:].sum() * step, rel=0.05, abs=2e-5)


# ------------------------------------------------- the readers, by name --

@pytest.fixture
def traced_root(tmp_path, monkeypatch):
    """A benchmark root whose `numbers` trace holds `_planes()`."""
    d = tmp_path / "benchmarks" / "out" / "some-cell" / "trace" / "numbers"
    d.mkdir(parents=True)
    monkeypatch.setattr(trace_reduce, "load", lambda path: _planes()
                        if os.path.samefile(path, d) else [])
    monkeypatch.setattr(stage_gaps, "_reduced", {})
    return str(tmp_path)


@pytest.mark.parametrize("name,want", [
    ("serve.tick_host_ms.open", 51.0),
    ("serve.tick_host_ms.closed", 51.0),
    ("serve.tick_host_p99_ms.open", 100.0),
    ("device.idle_named_pct.open", 100 * 4.3 / 4.5),
    ("device.idle_named_pct.closed", 100 * 4.3 / 4.5),
    ("device.idle_admit_pct.open", 10.0),
    ("device.idle_dispatch_pct.open", 15.0),
    ("serve.retraces_in_window.open", 2.0),
    ("serve.retraces_in_window.closed", 2.0),
    ("setup.jit_trace_s", 5.0),
    ("setup.jit_compile_s", 5.5),
])
def test_reader_by_name(name, want, ring, log, traced_root):
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    reader = harness.load_module(ROOT, "layer_metrics", name)
    assert (reader.NAME, reader.UNIT) == (name, entry["unit"])
    assert (reader.LAYER, reader.MOVES) == (entry["layer"], entry["moves"])
    cells = {w["name"] for w in BENCH["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    end = {m["name"]: m for m in BENCH["end_to_end"]}[entry["moves"]]
    assert set(entry["workloads"]) <= set(end.get("workloads", cells))
    got = reader.read(_record(root=traced_root), {})
    assert got == pytest.approx(want)


def test_every_new_reader_is_in_the_table_once():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW):] == NEW          # appended, in the issue's order
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_from_a_program_without_the_instrument(
        name, monkeypatch, tmp_path):
    """The parent commit has no tick ring, no compile log and no stage
    annotation: each reader gives None and does not raise."""
    from paddle_tpu.observability import runtime, tracing
    monkeypatch.delattr(tracing, "ticks")
    monkeypatch.delattr(runtime, "compile_log")
    monkeypatch.setattr(stage_gaps, "_reduced", {})
    reader = harness.load_module(ROOT, "layer_metrics", name)
    assert reader.read(_record(root=str(tmp_path)), {}) is None
    d = tmp_path / "benchmarks" / "out" / "c" / "trace" / "numbers"
    d.mkdir(parents=True)
    dev, host = _planes()
    bare = dict(host, lines={"client": host["lines"]["client"]})
    monkeypatch.setattr(trace_reduce, "load", lambda path: [dev, bare])
    assert reader.read(_record(root=str(tmp_path)), {}) is None


def test_the_newest_numbers_trace_is_the_one_read(tmp_path):
    old = tmp_path / "benchmarks" / "out" / "a" / "trace" / "numbers"
    new = tmp_path / "benchmarks" / "out" / "b" / "trace" / "numbers"
    old.mkdir(parents=True)
    new.mkdir(parents=True)
    os.utime(old, (1.0, 1.0))
    assert os.path.samefile(stage_gaps.numbers_dir(str(tmp_path)), new)
    assert stage_gaps.numbers_dir(str(tmp_path / "nothing")) is None

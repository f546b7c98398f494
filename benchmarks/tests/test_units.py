"""Unit tests of the yardstick's own arithmetic (no chip, no model).

Run by hand, from the repository's root:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""
import math
import os
import statistics
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import (harness, loadgen, readers, stats,  # noqa: E402
                            trace_reduce)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHAT = harness.load_json(os.path.join(ROOT, "benchmarks", "traffic",
                                      "chat-open.json"))
SESS = harness.load_json(os.path.join(ROOT, "benchmarks", "traffic",
                                      "sessions-closed.json"))


# ------------------------------------------------------------- loadgen --

def test_tokens_follow_the_seed_and_the_schedule_does_not():
    assert loadgen.tokens(7, 1, 2, 16, 1000) == \
        loadgen.tokens(7, 1, 2, 16, 1000)
    assert loadgen.tokens(7, 1, 2, 16, 1000) != \
        loadgen.tokens(8, 1, 2, 16, 1000)
    assert loadgen.tokens(2**31 + 5, 1, 2, 16, 1000) != \
        loadgen.tokens(5, 1, 2, 16, 1000)
    a = loadgen.open_schedule(CHAT, 60, 36)
    assert a == loadgen.open_schedule(CHAT, 60, 36)
    assert a != loadgen.open_schedule(dict(CHAT, shape_seed=1), 60, 36)


def test_the_schedule_repeats_one_cycle_that_fills_the_window():
    period = 36.0
    n = round(CHAT["rate_per_s"] * period)
    a = loadgen.open_schedule(CHAT, 3 * period, period)
    assert a[n - 1][0] == pytest.approx(period)
    assert [x[1:] for x in a[:n]] == [x[1:] for x in a[n:2 * n]]
    gaps = np.diff([0.0] + [d for d, _, _ in a])
    assert gaps[:n] == pytest.approx(gaps[n:2 * n])
    # any window of one period holds each arrival of the cycle once
    inside = [x[1:] for x in a if 10.0 <= x[0] < 10.0 + period]
    assert sorted(inside) == sorted(x[1:] for x in a[:n])


def test_open_schedule_draws_the_stated_distributions():
    s = loadgen.open_schedule(dict(CHAT, rate_per_s=50.0), 400, 400)
    p = [x[1] for x in s]
    o = [x[2] for x in s]
    assert min(p) >= CHAT["prompt_len"]["lo"] and max(p) <= CHAT["prompt_len"]["hi"]
    assert min(o) >= CHAT["output_len"]["lo"] and max(o) <= CHAT["output_len"]["hi"]
    assert abs(statistics.median(p) - CHAT["prompt_len"]["median"]) < 12
    assert abs(statistics.median(o) - CHAT["output_len"]["median"]) < 8
    gaps = np.diff([d for d, _, _ in s])
    assert abs(gaps.mean() - 1 / 50.0) < 0.002
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1      # Poisson arrivals


def test_session_pool_shapes():
    pools = loadgen.session_pool(SESS, SESS["clients"])
    assert pools == loadgen.session_pool(SESS, SESS["clients"])
    assert len(pools) == SESS["clients"]
    flat = [s for c in pools for s in c]
    assert len(flat) == SESS["clients"] * SESS["sessions_per_client"]
    share0 = sum(1 for s in flat if s[0] == 0) / len(flat)
    assert 0.3 < share0 < 0.65          # Zipf(1.1) over 4: 0.45 to the first
    assert all(len(s[1]) == SESS["turns"] for s in flat)
    for lens, spec in ((1, "user_len"), (2, "answer_len")):
        xs = [x for s in flat for x in s[lens]]
        assert min(xs) >= SESS[spec]["lo"] and max(xs) <= SESS[spec]["hi"]
        assert abs(statistics.median(xs) - SESS[spec]["median"]) < \
            0.1 * SESS[spec]["median"]
    worst = SESS["system_len"] + SESS["turns"] * (
        SESS["user_len"]["hi"] + SESS["answer_len"]["hi"])
    assert worst <= SESS["max_context"]


# --------------------------------------------------------------- stats --

def test_percentile_and_failures():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == pytest.approx(50.5)
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile([], 95) is None
    assert stats.percentile([3.0], 95) == 3.0
    # a failed request misses: it enters with the window's length
    with_f = stats.with_failures([10.0] * 90, 10, 30000.0)
    assert len(with_f) == 100
    assert stats.percentile(with_f, 95) == 30000.0
    assert stats.percentile(stats.with_failures([10.0] * 99, 1, 30000.0),
                            95) == 10.0
    assert stats.summary([1.0, 2.0, 3.0])["n"] == 3


def test_quartile_spread_is_the_contracts():
    v = [100, 101, 102, 103, 104, 105]
    q1, _, q3 = statistics.quantiles(v, n=4)
    assert stats.quartile_spread(v) == pytest.approx(
        (q3 - q1) / statistics.median(v))


# -------------------------------------------------------- trace reduce --

def _planes():
    dev = {"name": "/device:TPU:0", "lines": {
        "XLA Modules": [("jit__raw_decode_step(1)", 0.0, 0.010),
                        ("jit__raw_decode_step(1)", 0.020, 0.012),
                        ("jit__raw_prefill(2)", 0.040, 0.030)],
        "XLA Ops": [("fusion.1", 0.000, 0.004), ("ragged.7", 0.004, 0.006),
                    ("fusion.1", 0.020, 0.005), ("ragged.7", 0.024, 0.008),
                    ("all-gather.3", 0.040, 0.010),
                    ("fusion.9", 0.045, 0.025)]}}
    # the host's threads are recorded while the profiler starts and
    # stops, before the first device op and after the last
    host = {"name": "/host:CPU", "lines": {
        "python": [("profiler_start", -0.500, 0.300),
                   ("admission_round", 0.011, 0.008),
                   ("sleep", 0.033, 0.006),
                   ("profiler_stop", 0.080, 0.600)]}}
    return [dev, host]


def test_reduce_busy_union_idle_programs_kernels_exposed():
    r = trace_reduce.reduce_planes(_planes())
    assert r["devices"] == 1
    # the device's window, first op to last op, not the host's 1.18 s
    assert r["window_s"] == pytest.approx(0.070)
    assert readers.idle_pct({}, r) == pytest.approx(100 * 0.018 / 0.070)
    # busy: [0, .010] + [.020, .032] + [.040, .070] (the all-gather and
    # the fusion overlap: a union, not a sum)
    assert r["busy_s"] == pytest.approx(0.010 + 0.012 + 0.030)
    assert r["programs"]["_raw_decode_step"]["calls"] == 2
    assert r["programs"]["_raw_decode_step"]["median_s"] == \
        pytest.approx(0.011)
    calls, total, med = trace_reduce.time_of(r, "ops", r"ragged")
    assert (calls, total, med) == (2, pytest.approx(0.014),
                                   pytest.approx(0.007))
    assert trace_reduce.time_of(r, "ops", r"no_such_kernel") is None
    # the collective runs 10 ms, 5 of them under the fusion
    assert r["collective_exposed_s"] == pytest.approx(0.005)
    gaps = dict(map(tuple, r["breakdown"]["idle_gaps"]))
    assert gaps["admission_round"] == pytest.approx(0.010)
    assert gaps["sleep"] == pytest.approx(0.008)
    assert r["breakdown"]["device_ops"][0][0] == "fusion.9"


def test_reduce_recorded_trace_against_brute_force():
    path = os.path.join(DATA, "planes_small.json")
    planes = harness.load_json(path)
    r = trace_reduce.reduce_planes(planes)
    dev = [p for p in planes if trace_reduce.DEVICE_PLANE.match(p["name"])]
    assert dev and r["devices"] == len(dev)
    ops = dev[0]["lines"][trace_reduce.OPS_LINE]
    t0 = min(s for _, s, _ in ops)
    step = 1e-6
    n = int(math.ceil((max(s + d for _, s, d in ops) - t0) / step)) + 1
    grid = np.zeros(n, bool)
    for _, s, d in ops:
        grid[int(round((s - t0) / step)):int(round((s + d - t0) / step))] = 1
    assert r["busy_s_per_device"][0] == pytest.approx(grid.sum() * step,
                                                      rel=0.02)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["window_s"] == pytest.approx(
        max(s + d for _, s, d in ops) - t0)
    assert trace_reduce.time_of(r, "programs", r"_raw_decode_step")
    # host events before and after the device's ops change no number
    late = [{"name": "/host:CPU", "lines": {"python3": [
        ["start_trace", t0 - 1.0, 0.9], ["stop_trace", t0 + 5.0, 0.7]]}}]
    wide = trace_reduce.reduce_planes(planes + late)
    assert (wide["window_s"], wide["busy_s"]) == (r["window_s"], r["busy_s"])
    assert readers.idle_pct({}, wide) == pytest.approx(
        100 * (1 - grid.sum() * step / r["window_s"]), abs=2.0)


# ------------------------------------------------------------- kernels --

def test_ragged_paged_bytes_against_a_hand_count():
    k = harness.load_module(ROOT, "kernels", "ragged_paged")
    # two slots, 17 and 32 cached tokens, page 16: 2 + 2 pages; MHA 32x128
    # bf16: a page of K is 16 x 32 x 128 x 2 B = 131072 B, K and V 262144 B
    kv = 4 * 262144
    q_out = 2 * 32 * 128 * 2 * 2
    assert k.bytes_per_call([17, 32], 16, 32, 128, 32, 2) == kv + q_out
    assert k.flops_per_call([17, 32], 32, 128) == 4 * 32 * 128 * 49
    peaks = harness.peaks_for("TPU v5 lite")
    assert k.least_seconds([17, 32], 16, 32, 128, 32, 2, peaks) == \
        pytest.approx((kv + q_out) / 819e9)


def test_peaks_table_refuses_an_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        harness.peaks_for("cpu")


def test_benchmark_json_names_files_that_exist():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        found = harness.find_cell(ROOT, w["name"])
        harness.load_module(ROOT, "runners", found["mix"]["runner"])
        harness.load_module(ROOT, "models", found["cfg"]["builder"])
        for m in found["per_layer"]:
            mod = harness.load_module(ROOT, "layer_metrics", m["name"])
            assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
                (m["name"], m["unit"], m["layer"], m["moves"])
        assert any(m["name"] == "setup_s" for m in found["end_to_end"])
        assert len(found["end_to_end"]) >= 2 and found["per_layer"]

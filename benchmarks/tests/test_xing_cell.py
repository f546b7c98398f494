"""The cell `xing4-code-open` as new files: tiny through the harness on
the CPU (the 8-bit control has to fail), the configuration against the
published keys, the four per-layer readers on a hand-made trace summary,
and the streams' traffic against a hand count.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_xing_cell.py -q -p no:cacheprovider
"""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks import rehearse  # noqa: E402
from benchmarks.lib import harness  # noqa: E402

CELL = "xing4-code-open"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module", autouse=True)
def _kernels():
    rehearse.interpret_kernels()


def test_the_cell_tiny_through_the_harness_and_its_control_fails(tmp_path):
    """Tiny, float32, one dense and two expert layers, four streams of
    128 (whole lanes: both stream kernels in interpret mode), 16
    slots."""
    from paddle_tpu.observability import metrics
    root = rehearse.tiny_root(str(tmp_path))
    lost = lambda: sum(s.value for s in metrics.counter(
        "kernels.pallas_fallbacks").samples()
        if s.labels.get("kernel", "").startswith("mhc_"))
    before = lost()
    line = harness.run_cell(root, CELL, 3_000_000_001, 4.0, False,
                            time.perf_counter(), require_tpu=False,
                            control=("int8",))
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["correct"], line["check"]
    assert line["control_fails"] == {"int8": True}, line["check"]
    assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert lost() == before
    total = lambda n: sum(s.value for s in metrics.counter(n).samples())
    assert total("mhc.maps") > 0 and total("mla.keys_live") > 0
    assert total("moe.assignments_local") == total("moe.assignments") > 0
    reader = harness.load_module(ROOT, "layer_metrics",
                                 "mhc.sinkhorn_err_max.open")
    assert 1e-7 < reader.read({}, {}) < 0.1


def test_the_configuration_keeps_the_published_keys():
    """Every key of the catalog row's config, but the two the cut
    changes, is in the file as published; no width, no expert and no row
    of the vocabulary is among them."""
    found = harness.find_cell(ROOT, CELL)
    cfg, mix = found["cfg"], found["mix"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        assert cfg["source"] == row["source_url"]
        same = {k: v for k, v in row["config"].items()
                if k not in cfg["reduced"]}
        assert {k: cfg[k] for k in same} == same
        assert cfg["published"] == {k: row["config"][k]
                                    for k in cfg["reduced"]}
    assert sorted(cfg["reduced"]) == ["first_k_dense_replace",
                                      "num_hidden_layers"]
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (6, 1, 64, 131072)
    assert "experts_held" not in cfg            # the whole bank
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    for key in ("assumed", "stands_for", "memory_plan", "published"):
        assert cfg[key]
    for key in ("hc_per_sublayer", "hc_maps", "sinkhorn_order", "hc_head",
                "mhc_init", "rope", "mtp", "router_bias_std",
                "initializer_range", "eos", "context_served", "ep_size"):
        assert cfg["assumed"][key], key
    assert cfg["serve"] == {"max_batch_size": 32, "page_size": 16,
                            "max_seq_len": 8192, "num_pages": 16384}
    assert sorted(map(tuple, mix["warm"]["prefill"])) == [
        (1, b) for b in (512, 1024, 2048, 4096, 8192)]
    assert (mix["shape_seed"], mix["prompt_len"], mix["output_len"]) == (
        2308, {"median": 2048, "sigma": 0.7, "lo": 512, "hi": 7936},
        {"median": 64, "sigma": 0.6, "lo": 16, "hi": 256})
    assert (mix["warmup_s"], mix["drain_max_s"], mix["trace_s"],
            mix["trace_names_s"]) == (10.0, 20.0, 3.0, 1.5)
    # the cycle reaches every bucket it warms
    from benchmarks.lib import loadgen
    lengths = {p for _, p, _ in loadgen.open_schedule(mix, 51.0, 51.0)}
    bucket = lambda n: 1 << (n - 1).bit_length()
    assert {bucket(n) for n in lengths} <= {512, 1024, 2048, 4096, 8192}
    bench = found["bench"]
    mine = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert {"ttft_p95_ms", "tpot_p95_ms", "setup_s", "mhc.stream_roofline",
            "mhc.prefill_share_pct.open", "mhc.decode_share_pct.open",
            "mhc.sinkhorn_err_max.open", "step.decode_ms.open",
            "moe.expert_load_max_over_mean.open", "setup.jit_trace_s",
            "step.prefill_share_pct.open"} <= mine
    assert not {"mla.decode_roofline", "moe.grouped_matmul_roofline",
                "moe.held_banks_roofline"} & mine


def test_stream_traffic_against_a_hand_count():
    kernel = harness.load_module(ROOT, "kernels", "mhc_stream")
    assert kernel.bytes_per_token(4, 3584, 2) == 10 * 3584 * 2 == 71680
    assert kernel.flops_per_token(4, 3584) == 2 * 24 * 14336
    assert kernel.least_seconds(1, 4, 3584, 2, PEAKS) == 71680 / 819e9


def _trace():
    """Two prefill programs (buckets 2048 and 4096) and 50 decode steps
    of a six-layer model: 12 `mhc.pre` and 12 `mhc.post` a program."""
    op = lambda calls, total: {"calls": calls, "total_s": total,
                               "median_s": total / calls}
    return {"ops": {
        "mhc.pre:custom-call:bf16[2048,3584]": op(12, 12 * 100e-6),
        "mhc.post:custom-call:bf16[2048,14336]": op(12, 12 * 200e-6),
        "mhc.pre:custom-call:bf16[4096,3584]": op(12, 12 * 200e-6),
        "mhc.post:custom-call:bf16[4096,14336]": op(12, 12 * 400e-6),
        "mhc.pre:custom-call:bf16[32,3584]": op(600, 600 * 5e-6),
        "mhc.post:custom-call:bf16[32,14336]": op(600, 600 * 3e-6),
        "mla.attend:custom-call:bf16[32,32,640]": op(300, 300 * 40e-6),
        "fusion:fusion:bf16[2048,14336]": op(12, 1.0)},
        "programs": {"_raw_prefill": op(2, 0.18),
                     "_raw_decode_step": op(50, 50 * 12e-3)}}


def test_the_readers_on_a_hand_made_summary():
    rec = {"peaks": PEAKS, "root": ROOT,
           "geometry": {"slots": 32, "itemsize": 2}}
    read = lambda name: harness.load_module(
        ROOT, "layer_metrics", name).read(rec, _trace())
    spent = 12 * (100 + 200 + 200 + 400) * 1e-6
    least = 12 * (2048 + 4096) * 71680 / 819e9
    assert abs(read("mhc.stream_roofline") - 100 * least / spent) < 1e-9
    assert 55 < read("mhc.stream_roofline") < 65
    assert abs(read("mhc.prefill_share_pct.open")
               - 100 * spent / 0.18) < 1e-9
    assert abs(read("mhc.decode_share_pct.open")
               - 100 * 600 * 8e-6 / 0.6) < 1e-9
    # a program without the scopes (the parent): nothing to read
    bare = {"ops": {"fusion:fusion:bf16[2048,14336]": _trace()["ops"][
        "fusion:fusion:bf16[2048,14336]"]}, "programs": _trace()["programs"]}
    for name in ("mhc.stream_roofline", "mhc.prefill_share_pct.open",
                 "mhc.decode_share_pct.open"):
        assert harness.load_module(ROOT, "layer_metrics", name).read(
            rec, bare) is None

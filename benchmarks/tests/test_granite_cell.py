"""The cell `granite4h-chat-open` as new files: tiny through the
harness on the CPU (the 8-bit control has to fail), the two kernels'
byte counts against hand counts, and the four per-layer readers on a
recorded trace summary.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_granite_cell.py -q -p no:cacheprovider
"""
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

CELL = "granite4h-chat-open"
DATA = os.path.join(ROOT, "benchmarks", "tests", "data")


@pytest.fixture(scope="module", autouse=True)
def _kernels():
    rehearse.interpret_kernels()


def test_the_cell_tiny_through_the_harness_and_its_control_fails(tmp_path):
    root = rehearse.tiny_root(str(tmp_path))
    line = harness.run_cell(root, CELL, 3_000_000_001, 4.0, False,
                            time.perf_counter(), require_tpu=False,
                            control=("int8",))
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["correct"], line["check"]
    assert line["control_fails"] == {"int8": True}, line["check"]
    assert line["check"]["control"]["int8"]["gap_mean"]["fails"]
    assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}


def test_the_configuration_keeps_the_published_keys():
    """Every key of the catalog row's config, but the four the cut
    changes, is in the file as published."""
    cfg = harness.find_cell(ROOT, CELL)["cfg"]
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768,
        "logits_scaling": 16, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_key_value_heads": 8,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["layer_types", "num_hidden_layers",
                                      "num_local_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (10, 36, 50176)
    assert cfg["published"]["num_local_experts"] == 72
    assert cfg["experts_held"] == list(range(36))
    # one whole period of the published pattern
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4


# ------------------------------------------------------------- kernels --

def test_ssm_state_update_bytes_against_a_hand_count():
    k = harness.load_module(ROOT, "kernels", "ssm_state_update")
    # 64 slots x 128 heads x 64 x 128 float32 = 268,435,456 B, read and
    # written; x in and y out 64 x 8192 x 2 B each, B and C 64 x 128 x
    # 2 B each, dt 64 x 128 x 4 B
    state = 64 * 128 * 64 * 128 * 4
    small = 2 * 64 * 8192 * 2 + 2 * 64 * 128 * 2 + 64 * 128 * 4
    assert state == 268_435_456 and small == 2_162_688
    assert k.bytes_per_call(64, 128, 64, 128, 1, 2) == 2 * state + small
    assert k.flops_per_call(64, 128, 64, 128) == 6 * 67_108_864
    peaks = harness.peaks_for("TPU v5 lite")
    # bound by memory: 0.66 ms a layer
    assert k.least_seconds(64, 128, 64, 128, 1, 2, peaks) == \
        pytest.approx((2 * state + small) / 819e9)
    assert k.least_seconds(64, 128, 64, 128, 1, 2, peaks) == \
        pytest.approx(0.658e-3, rel=0.01)


def test_grouped_matmul_bytes_against_a_hand_count():
    k = harness.load_module(ROOT, "kernels", "moe_grouped")
    # 64 tokens x top-10 over 72 experts, 36 held: 320 rows expected
    # here; a held expert is missed by all 64 tokens with probability
    # (62/72)^64 = 7.0e-5, so all 36 are touched but for 0.0025 of one
    assert k.local_rows(64, 36, 72, 10) == 320
    touched = k.experts_touched(64, 36, 72, 10)
    assert touched == pytest.approx(36 * (1 - (62 / 72) ** 64))
    assert 35.99 < touched < 36
    one_expert = 3 * 4096 * 768 * 2                     # 18,874,368 B
    rows = 320 * (4096 + 1536 + 768 + 4096) * 2         # 6,717,440 B
    assert k.bytes_per_layer(64, 4096, 768, 36, 72, 10, 2) == \
        pytest.approx(touched * one_expert + rows)
    assert k.flops_per_layer(64, 4096, 768, 36, 72, 10) == \
        2 * 320 * 3 * 4096 * 768
    peaks = harness.peaks_for("TPU v5 lite")
    # bound by memory at decode: 0.84 ms a layer
    assert k.least_seconds(64, 4096, 768, 36, 72, 10, 2, peaks) == \
        pytest.approx(0.838e-3, rel=0.01)
    # one token touches ten experts at most, five of them here
    assert k.experts_touched(1, 36, 72, 10) == pytest.approx(5.0)
    # a prompt's 8192 tokens are bound by compute
    assert k.least_seconds(8192, 4096, 768, 36, 72, 10, 2, peaks) == \
        pytest.approx(2 * 40960 * 3 * 4096 * 768 / 197e12)


# ------------------------------------------- readers, recorded summary --

@pytest.fixture(scope="module")
def recorded():
    """The reduced trace of a chip run of the cell (cut to the ops the
    readers look at), and a record as the runner leaves it."""
    trace = harness.load_json(os.path.join(DATA,
                                           "granite4h_trace_summary.json"))
    record = {"root": ROOT, "peaks": harness.peaks_for("TPU v5 lite"),
              # the recorded run's: 3 to 4.5 of 64 slots in flight
              "occupancy": {"occupancy": [0.046875, 0.0625, 0.0703125,
                                          0.0546875]},
              "geometry": {"slots": 64, "page_size": 16, "q_heads": 32,
                           "kv_heads": 8, "head_dim": 128, "itemsize": 2}}
    return record, trace


def _reader(name):
    return harness.load_module(ROOT, "layer_metrics", name)


def test_state_update_roofline_reads_the_fused_update(recorded):
    record, trace = recorded
    op = trace["ops"]["multiply_reduce_fusion:fusion:f32[65,128,64]"]
    assert op["calls"] == 9 * 128           # a Mamba layer a step
    k = harness.load_module(ROOT, "kernels", "ssm_state_update")
    # mean occupancy 0.05859 of 64 slots = 3.75 slots carry a request
    least = k.least_seconds(3.75, 128, 64, 128, 1, 2, record["peaks"])
    got = _reader("ssm.state_update_roofline").read(record, trace)
    assert got == pytest.approx(100 * least / op["median_s"])
    assert 4 < got < 6      # the program updates all 65 rows in 0.818 ms
    full = dict(record, occupancy={"occupancy": [1.0]})
    assert 75 < _reader("ssm.state_update_roofline").read(full, trace) < 100


def test_grouped_matmul_roofline_reads_the_two_kernels(recorded):
    record, trace = recorded
    up = trace["ops"]["ragged-dot-none:custom-call:bf16[640,1536]"]
    down = trace["ops"]["ragged-dot-none:custom-call:bf16[640,4096]"]
    assert up["calls"] == down["calls"] == 10 * 128     # a layer a step
    k = harness.load_module(ROOT, "kernels", "moe_grouped")
    least = k.least_seconds(3.75, 4096, 768, 36, 72, 10, 2,
                            record["peaks"])
    got = _reader("moe.grouped_matmul_roofline").read(record, trace)
    assert got == pytest.approx(
        100 * least / (up["total_s"] / up["calls"]
                       + down["total_s"] / down["calls"]))
    assert 40 < got < 60    # 15.5 experts' weights in 0.74 ms
    # the prefill's grouped matmuls ([2560 | 5120 | 10240, ...]) are
    # other ops: they do not enter
    assert any(n.startswith("ragged-dot-none") and "[5120," in n
               for n in trace["ops"])


def test_readers_find_nothing_where_the_program_has_nothing(recorded):
    """A parent commit's trace: no such fusion, no grouped kernel, no
    counter. The readers return None and do not raise."""
    record, trace = recorded
    bare = dict(trace, ops={n: v for n, v in trace["ops"].items()
                            if "reduce" not in n and "ragged" not in n})
    assert _reader("ssm.state_update_roofline").read(record, bare) is None
    assert _reader("moe.grouped_matmul_roofline").read(record, bare) is None
    assert _reader("ssm.state_update_roofline").read(
        dict(record, occupancy=None), trace) is None


def test_prefill_share_and_expert_load_readers(recorded):
    record, trace = recorded
    share = _reader("step.prefill_share_pct.open").read(record, trace)
    assert share == pytest.approx(
        100 * trace["programs"]["_raw_prefill"]["total_s"] / trace["busy_s"])
    from paddle_tpu.observability import metrics
    ctr = metrics.counter("moe.expert_tokens")
    before = {s.labels.get("expert"): s.value for s in ctr.samples()}
    for e, n in (("0", 30), ("1", 10), ("2", 20)):
        ctr.inc(n, expert=e)
    after = {s.labels.get("expert"): s.value for s in ctr.samples()}
    want = max(after.values()) / (sum(after.values()) / len(after))
    got = _reader("moe.expert_load_max_over_mean.open").read(record, trace)
    assert got == pytest.approx(want)
    if not before:
        assert got == pytest.approx(30 / 20)

"""The cell `keye2-longprompt-open` as new files: tiny through the
harness on the CPU (the 8-bit control has to fail), the configuration
against the published keys, and the four per-layer readers on a
recorded trace summary (the two kernels' byte counts are held to hand
counts in tier-1: `tests/test_keye_vl2.py`).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_keye_cell.py -q -p no:cacheprovider
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

CELL = "keye2-longprompt-open"
DATA = os.path.join(ROOT, "benchmarks", "tests", "data")


@pytest.fixture(scope="module", autouse=True)
def _kernels():
    rehearse.interpret_kernels()


def test_the_cell_tiny_through_the_harness_and_its_control_fails(tmp_path):
    """Tiny, float32 (the tiny limits file says why), contexts past the
    tiny `topk`, the decode kernels in interpret mode."""
    from paddle_tpu.observability import metrics
    root = rehearse.tiny_root(str(tmp_path))
    line = harness.run_cell(root, CELL, 3_000_000_001, 4.0, False,
                            time.perf_counter(), require_tpu=False,
                            control=("int8",))
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["correct"], line["check"]
    assert line["control_fails"] == {"int8": True}, line["check"]
    assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    kernels = {s.labels["kernel"]: s.value for s in
               metrics.counter("kernels.paged_decode").samples()}
    assert kernels.get("paged_sparse_attention")
    total = lambda n: sum(s.value for s in metrics.counter(n).samples())
    assert 0 < total("dsa.keys_selected") < total("dsa.keys_live")


def test_the_configuration_keeps_the_published_keys():
    """Every key of the catalog row's config, but the four the cut
    changes, is in the file as published; no width is among the four."""
    cfg = harness.find_cell(ROOT, CELL)["cfg"]
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "KeyeVL2", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "num_local_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["num_local_experts"], cfg["vocab_size"]) \
        == (12, 16, 16, 18992)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "num_local_experts": 128,
                                "vocab_size": 151936}
    assert cfg["experts_held"] == list(range(16))
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    # the warm list holds every (rows, bucket) a prefill program can be:
    # two prompts at most, buckets 4096 to 16384
    mix = harness.find_cell(ROOT, CELL)["mix"]
    assert sorted(map(tuple, mix["warm"]["prefill"])) == [
        (n, b) for n in (1, 2) for b in (4096, 8192, 16384)]
    assert mix["prompt_len"]["lo"] > cfg["sa_config"]["topk"]


# ------------------------------------------- readers, recorded summary --

@pytest.fixture(scope="module")
def recorded():
    """The reduced trace of a chip run of the cell (my chip run, PR 33,
    seed 3000003302 at 0.6 req/s; cut to the ops the readers look at),
    and a record as the runner leaves it."""
    trace = harness.load_json(os.path.join(DATA,
                                           "keye2_trace_summary.json"))
    record = {"root": ROOT, "peaks": harness.peaks_for("TPU v5 lite"),
              # the recorded run's: 1.6 of 32 slots in flight on average
              "occupancy": {"occupancy": [0.03125, 0.0625, 0.0625, 0.0419]},
              "mean_decode_ctx": 6973.66,
              # `serve.geometry` divides hidden by heads: head_dim 64 is
              # what the runner records for this cell, and is not read
              "geometry": {"slots": 32, "page_size": 16, "q_heads": 32,
                           "kv_heads": 4, "head_dim": 64, "itemsize": 2}}
    return record, trace


def _reader(name):
    return harness.load_module(ROOT, "layer_metrics", name)


def test_the_two_rooflines_read_the_scoped_kernels(recorded):
    record, trace = recorded
    scores = trace["ops"]["dsa.indexer:custom-call:f32[32,1,16384]"]
    attend = trace["ops"]["dsa.attend:custom-call:bf16[32,32,128]"]
    assert scores["calls"] == 12 * 101          # a layer a step
    assert attend["calls"] in (12 * 101, 12 * 101 - 1)
    # mean occupancy 0.0495 of 32 slots rounds to 2 slots of 6973.66 keys
    ki = harness.load_module(ROOT, "kernels", "dsa_indexer")
    ka = harness.load_module(ROOT, "kernels", "dsa_sparse_attend")
    ctx = [6973.66] * 2
    got = _reader("dsa.indexer_roofline").read(record, trace)
    assert got == pytest.approx(100 * ki.least_seconds(
        ctx, 16, 64, 2, record["peaks"]) / scores["median_s"])
    got_a = _reader("dsa.sparse_attend_roofline").read(record, trace)
    # head_dim 128 from the configuration, not the record's 64
    assert got_a == pytest.approx(100 * ka.least_seconds(
        ctx, 2048, 4, 128, 32, 2, record["peaks"]) / attend["median_s"])
    assert 0 < got < 100 and 0 < got_a < 100
    # every slot occupied at the same kernel time would still be under
    # the roofline: the count is the least bytes
    full = dict(record, occupancy={"occupancy": [1.0]})
    assert _reader("dsa.indexer_roofline").read(full, trace) < 100


def test_select_share_sums_scoring_and_selection_of_the_decode_step(recorded):
    record, trace = recorded
    got = _reader("dsa.select_share_pct.open").read(record, trace)
    ops, step = trace["ops"], trace["programs"]["_raw_decode_step"]
    want = (ops["dsa.indexer:custom-call:f32[32,1,16384]"]["total_s"]
            + ops["convert_reduce_fusion:fusion:s32[32]"]["total_s"]
            + sum(v["total_s"] for n, v in ops.items()
                  if n.endswith("[32,16384]"))
            + sum(v["total_s"] for n, v in ops.items()
                  if n != "convert_reduce_fusion:fusion:s32[32]"
                  and n.endswith(("_fusion:fusion:s32[32]",
                                  "_fusion:fusion:u32[32]"))))
    assert got == pytest.approx(100 * want / step["total_s"])
    assert 15 < got < 30        # 22 % of an 8.6 ms step at two slots
    # the prefill's scoring and selection have other shapes
    assert "dsa.indexer:custom-call:f32[1,512,16384]" in ops


def test_readers_find_nothing_where_the_program_has_nothing(recorded):
    """A parent commit's trace: no scoped kernel, no counter. The
    readers return None and do not raise."""
    record, trace = recorded
    bare = dict(trace, ops={n: v for n, v in trace["ops"].items()
                            if not n.startswith("dsa.")})
    for name in ("dsa.indexer_roofline", "dsa.sparse_attend_roofline",
                 "dsa.select_share_pct.open"):
        assert _reader(name).read(record, bare) is None
    unsampled = dict(record, occupancy=None)
    assert _reader("dsa.indexer_roofline").read(unsampled, trace) is None
    assert _reader("dsa.sparse_attend_roofline").read(unsampled,
                                                      trace) is None


def test_selected_share_reads_the_two_counters(recorded):
    from paddle_tpu.observability import metrics
    record, trace = recorded
    live, chosen = (metrics.counter("dsa.keys_live"),
                    metrics.counter("dsa.keys_selected"))
    total = lambda c: sum(s.value for s in c.samples())
    before = total(live), total(chosen)
    live.inc(7000 * 12)
    chosen.inc(2048 * 12)
    got = _reader("dsa.selected_share_pct.open").read(record, trace)
    assert got == pytest.approx(
        100 * (before[1] + 2048 * 12) / (before[0] + 7000 * 12))
    if not before[0]:
        assert got == pytest.approx(100 * 2048 / 7000)

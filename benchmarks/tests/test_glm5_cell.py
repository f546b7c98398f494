"""The cell `glm5-longprompt-open` as new files: tiny through the
harness on the CPU (the 8-bit control has to fail), the configuration
against the published keys, and the four per-layer readers on a recorded
trace summary (the two new kernel counts are held to hand counts in
`tests/test_glm_moe_dsa.py`).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_glm5_cell.py -q -p no:cacheprovider
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

CELL = "glm5-longprompt-open"
DATA = os.path.join(ROOT, "benchmarks", "tests", "data")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module", autouse=True)
def _kernels():
    rehearse.interpret_kernels()


def test_the_cell_tiny_through_the_harness_and_its_control_fails(tmp_path):
    """Tiny, float32, one dense and two expert layers, prompts past the
    tiny `topk` of 32, the index-score and latent kernels in interpret
    mode."""
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
    assert kernels.get("paged_sparse_latent_attention")
    total = lambda n: sum(s.value for s in metrics.counter(n).samples())
    assert 0 < total("dsa.keys_selected") < total("dsa.keys_live")
    assert total("mla.keys_live") == total("dsa.keys_live")
    assert 0 < total("moe.assignments_local") <= total("moe.assignments")


def test_the_configuration_keeps_the_published_keys():
    """Every key of the catalog row's config, but the four the cut
    changes, is in the file as published; no width is among the four."""
    cfg = harness.find_cell(ROOT, CELL)["cfg"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
        assert cfg["source"] == row["source_url"]
        same = {k: v for k, v in row["config"].items()
                if k not in cfg["reduced"]}
        assert {k: cfg[k] for k in same} == same
        assert cfg["published"] == {k: row["config"][k]
                                    for k in cfg["reduced"]}
    assert sorted(cfg["reduced"]) == ["first_k_dense_replace",
                                      "n_routed_experts",
                                      "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (6, 1, 16, 19360)
    assert cfg["published"] == {"num_hidden_layers": 78,
                                "first_k_dense_replace": 3,
                                "n_routed_experts": 256,
                                "vocab_size": 154880}
    # rank 0 of 16, an eighth of the vocabulary; the guide's floors
    assert cfg["experts_held"] == list(range(16))
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "index_head_dim",
                "index_n_heads", "index_topk", "num_experts_per_tok",
                "num_attention_heads"):
        assert key not in cfg["reduced"]
    found = harness.find_cell(ROOT, CELL)
    mix = found["mix"]
    # one prompt a program; the cycle's 20 prompts are 4267 to 15872 long
    assert sorted(map(tuple, mix["warm"]["prefill"])) == [
        (1, b) for b in (8192, 16384)]
    assert mix["rate_per_s"] == 0.4
    assert (mix["shape_seed"], mix["prompt_len"], mix["output_len"]) == (
        2306, {"median": 8192, "sigma": 0.45, "lo": 4096, "hi": 15872},
        {"median": 160, "sigma": 0.6, "lo": 32, "hi": 512})
    assert (mix["warmup_s"], mix["drain_max_s"], mix["trace_s"],
            mix["trace_names_s"]) == (10.0, 20.0, 3.0, 1.5)
    serve = cfg["serve"]
    assert serve == {"max_batch_size": 32, "page_size": 16,
                     "max_seq_len": 16384, "num_pages": 16384}
    # the cell is listed wherever its readers find something to read
    bench = found["bench"]
    mine = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert {"ttft_p95_ms", "tpot_p95_ms", "setup_s",
            "mla_dsa.indexer_roofline", "mla_dsa.sparse_decode_roofline",
            "mla_dsa.prefill_attend_roofline",
            "mla_dsa.prefill_select_share_pct.open",
            "dsa.select_share_pct.open", "dsa.selected_share_pct.open",
            "moe.expert_load_max_over_mean.open", "step.decode_ms.open",
            "setup.jit_trace_s", "setup.jit_compile_s"} <= mine
    assert not {"dsa.indexer_roofline", "dsa.sparse_attend_roofline",
                "mla.decode_roofline"} & mine


# ------------------------------------------- readers, recorded summary --

# the recorded run's line: `serve.batch_occupancy_pct.open` and the
# run's `mean_decode_ctx`
RECORDED = {"occupancy": [0.04289], "mean_decode_ctx": 8196.38}


@pytest.fixture(scope="module")
def recorded():
    """The reduced trace of a chip run of the cell (my chip run, PR 39,
    seed 3000003923 traced at 0.4 req/s on the final tree's `git
    archive`; cut to the ops the readers look at), and a record as the
    runner leaves it."""
    trace = harness.load_json(os.path.join(DATA, "glm5_trace_summary.json"))
    record = {"root": ROOT, "peaks": harness.peaks_for("TPU v5 lite"),
              "occupancy": {"occupancy": RECORDED["occupancy"]},
              "mean_decode_ctx": RECORDED["mean_decode_ctx"],
              # `serve.geometry` divides hidden by heads: head_dim 96 is
              # what the runner records for this cell, and is not read
              "geometry": {"slots": 32, "page_size": 16, "q_heads": 64,
                           "kv_heads": 64, "head_dim": 96, "itemsize": 2}}
    return record, trace


def _reader(name):
    return harness.load_module(ROOT, "layer_metrics", name)


def test_the_two_decode_rooflines_read_the_scoped_kernels(recorded):
    record, trace = recorded
    steps = trace["programs"]["_raw_decode_step"]["calls"]
    scores = trace["ops"]["dsa.indexer:custom-call:f32[32,1,16384]"]
    attend = trace["ops"]["mla.attend:custom-call:bf16[32,64,640]"]
    # six layers a step (the trace cuts the first and last step)
    assert scores["calls"] == attend["calls"]
    assert 6 * (steps - 1) <= attend["calls"] <= 6 * steps
    slots = round(RECORDED["occupancy"][0] * 32)
    ctx = [RECORDED["mean_decode_ctx"]] * slots
    ki = harness.load_module(ROOT, "kernels", "dsa_indexer")
    ka = harness.load_module(ROOT, "kernels", "mla_sparse_decode")
    got_i = _reader("mla_dsa.indexer_roofline").read(record, trace)
    assert got_i == pytest.approx(100 * ki.least_seconds(
        ctx, 32, 128, 2, record["peaks"]) / scores["median_s"])
    got_a = _reader("mla_dsa.sparse_decode_roofline").read(record, trace)
    assert got_a == pytest.approx(100 * ka.least_seconds(
        ctx, 2048, 64, 512, 64, 2, record["peaks"]) / attend["median_s"])
    # one slot in flight on average: both kernels walk all 32
    assert 0 < got_i < 5 and 0 < got_a < 5
    # every slot occupied at the same kernel times is still under 100
    full = dict(record, occupancy={"occupancy": [1.0]})
    assert _reader("mla_dsa.indexer_roofline").read(full, trace) < 100
    assert _reader("mla_dsa.sparse_decode_roofline").read(full, trace) < 100
    # cell 4's generic reader finds the decode step's selection here too
    assert 5 < _reader("dsa.select_share_pct.open").read(record, trace) < 20


def test_prefill_roofline_counts_its_chunks_from_the_trace(recorded):
    record, trace = recorded
    got = _reader("mla_dsa.prefill_attend_roofline").read(record, trace)
    attend = trace["ops"]["mla.attend:custom-call:bf16[1,64,1,512,256]"]
    selected = sum(v["calls"] for n, v in trace["ops"].items()
                   if n.startswith("dsa.indexer:custom-call:f32[1,512,"))
    # two prefills in the traced 3 s, six layers: a chunk under a
    # selection ran the score kernel too, the others lie in a prompt's
    # first 2048 tokens (four chunks a prompt a layer)
    assert selected == 143 and attend["calls"] - selected == 44
    k = harness.load_module(ROOT, "kernels", "mla_sparse_prefill")
    pairs = k.chunk_pairs(44, 143, 512, 2048)
    assert got == pytest.approx(100 * k.least_seconds(
        pairs, 187 * 512, 64, 256, 256, 2, record["peaks"])
        / attend["total_s"])
    assert 5 < got < 30
    # the decode kernel carries the same scope's name and another shape
    assert "mla.attend:custom-call:bf16[32,64,640]" in trace["ops"]


def test_select_share_reads_the_conditional_that_holds_the_selection(
        recorded):
    record, trace = recorded
    got = _reader("mla_dsa.prefill_select_share_pct.open").read(record, trace)
    whole = sum(v["total_s"] for n, v in trace["ops"].items()
                if n.startswith("conditional:conditional:pred[1,512,"))
    assert got == pytest.approx(
        100 * whole / trace["programs"]["_raw_prefill"]["total_s"])
    assert 3 < got < 15
    # a program without the conditional is read by its pieces: the score
    # kernel and the ops of the selection's shapes, never twice
    flat = dict(trace, ops={n: v for n, v in trace["ops"].items()
                            if not n.startswith("conditional:")})
    pieces = _reader("mla_dsa.prefill_select_share_pct.open").read(record,
                                                                   flat)
    assert 0 < pieces < got


def test_readers_find_nothing_where_the_program_has_nothing(recorded):
    """A parent commit's trace: no scoped kernel. The readers return
    None and do not raise."""
    record, trace = recorded
    bare = dict(trace, ops={n: v for n, v in trace["ops"].items()
                            if n.startswith("ragged-dot")})
    names = ("mla_dsa.indexer_roofline", "mla_dsa.sparse_decode_roofline",
             "mla_dsa.prefill_attend_roofline",
             "mla_dsa.prefill_select_share_pct.open")
    for name in names:
        assert _reader(name).read(record, bare) is None
    unsampled = dict(record, occupancy=None)
    for name in names[:2]:
        assert _reader(name).read(unsampled, trace) is None

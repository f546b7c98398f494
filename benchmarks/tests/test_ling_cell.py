"""The cell `ling3f-longdoc-open` as new files: tiny through the harness
on the CPU (the 8-bit control has to fail), the configuration against
the published keys, the three kernels' bytes and operations against hand
counts, and the four per-layer readers on a recorded trace summary.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_ling_cell.py -q -p no:cacheprovider
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
from benchmarks.lib import harness, kda_ops  # noqa: E402

CELL = "ling3f-longdoc-open"
DATA = os.path.join(ROOT, "benchmarks", "tests", "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module", autouse=True)
def _kernels():
    rehearse.interpret_kernels()


def test_the_cell_tiny_through_the_harness_and_its_control_fails(tmp_path):
    """Tiny, float32, two KDA layers around one MLA layer and a third
    after it, prompts past several segments of the chunked recurrence,
    the latent decode kernel in interpret mode."""
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
    assert kernels.get("paged_latent_attention")
    total = lambda n: sum(s.value for s in metrics.counter(n).samples())
    assert total("kda.rows_live") > 0 and total("mla.keys_live") > 0
    assert 0 < total("moe.assignments_local") <= total("moe.assignments")


def test_the_configuration_keeps_the_published_keys():
    """Every key of the catalog row's config, but the three the cut
    changes, is in the file as published; no width is among the three."""
    cfg = harness.find_cell(ROOT, CELL)["cfg"]
    zeros = lambda n, rest: [0] * n + rest
    published = {
        "expert_swiglu_limit_list": zeros(35, [4] * 7),
        "share_expert_swiglu_limit_list": zeros(34, [5] * 6 + [7, 7]),
        "first_k_dense_replace": 2,
        "gated_attention_proj_granularity_type": "head_wise",
        "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2560, "intermediate_size": 6144,
        "kda_lower_bound": -5, "kda_safe_gate": True, "kv_lora_rank": 512,
        "layer_group_size": 6, "linear_silu": True,
        "max_position_embeddings": 262144, "max_window_layers": 20,
        "moe_intermediate_size": 768, "moe_router_enable_expert_bias": True,
        "moe_shared_expert_intermediate_size": 768,
        "mtp_loss_scaling_factor": 0, "mtp_use_kda": False, "n_group": 8,
        "no_kda_lora": True, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
        "num_nextn_predict_layers": 1, "num_shared_experts": 1,
        "partial_rotary_factor": 0.5, "q_lora_rank": None,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 6000000, "rotary_dim": 64,
        "routed_scaling_factor": 2.5, "scale_router_input": False,
        "score_function": "sigmoid", "scoring_func": "sigmoid",
        "seq_aux": True, "short_conv_kernel_size": 4,
        "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "noaux_tc", "up_proj_norm": False, "use_bias": False,
        "use_kda_lora": False, "use_mla_nope": False, "use_nGPT": False,
        "use_qk_norm": True, "use_qkv_bias": False, "v_head_dim": 128,
        "value_norm": False, "model_type": "bailing_hybrid"}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (7, 128, 39296)
    assert cfg["published"] == {"num_hidden_layers": 42, "num_experts": 512,
                                "vocab_size": 157184}
    # groups 0 and 1 of the router's 8, a quarter of the vocabulary
    assert cfg["experts_held"] == list(range(128))
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    # neither clamp list reaches the seven layers run
    assert not any(cfg["expert_swiglu_limit_list"][:7]
                   + cfg["share_expert_swiglu_limit_list"][:7])
    found = harness.find_cell(ROOT, CELL)
    mix = found["mix"]
    assert sorted(map(tuple, mix["warm"]["prefill"])) == [
        (n, b) for n in (1, 2) for b in (4096, 8192, 16384)]
    assert (mix["shape_seed"], mix["prompt_len"], mix["output_len"]) == (
        2305, {"median": 6144, "sigma": 0.45, "lo": 3072, "hi": 12288},
        {"median": 160, "sigma": 0.6, "lo": 32, "hi": 512})
    serve = cfg["serve"]
    assert serve["num_pages"] * serve["page_size"] == 32 * 12800


def test_layer_kinds_of_the_cut_are_one_period_and_one_more():
    lw = harness.load_module(ROOT, "reference", "ling_hybrid").lw
    cfg = harness.find_cell(ROOT, CELL)["cfg"]
    assert lw.kinds(cfg) == ["kda"] * 5 + ["mla", "kda"]
    whole = dict(cfg, num_hidden_layers=42)
    assert lw.kinds(whole).count("mla") == 7


# ----------------------------------------------- kernels, by hand counts --

def test_state_update_counts_are_the_definitions():
    k = harness.load_module(ROOT, "kernels", "kda_state_update")
    # one slot, one head, a 128 x 128 float32 state: 65536 B read and
    # written; q, k, v, o 4 x 128 bfloat16; g 128 float32; beta 4 B
    assert k.bytes_per_call(1, 1, 128, 128, 2) \
        == 2 * 65536 + 4 * 128 * 2 + 128 * 4 + 4
    assert k.flops_per_call(1, 1, 128, 128) == 7 * 128 * 128
    # the cell's layer at 20 occupied slots: 84 MB, bound by memory
    b = k.bytes_per_call(20, 32, 128, 128, 2)
    assert b == 20 * 32 * (131072 + 1540)
    assert k.least_seconds(20, 32, 128, 128, 2, PEAKS) \
        == pytest.approx(b / 819e9)
    assert k.flops_per_call(20, 32, 128, 128) / 197e12 < b / 819e9


def test_chunk_counts_are_the_recurrences():
    k = harness.load_module(ROOT, "kernels", "kda_chunk")
    # a token of a head: the recurrence's 7 x 128 x 128 operations; q,
    # k, v in and o out 4 x 128 bfloat16, g 128 float32, beta 4 B
    assert k.flops_per_call(1, 1, 128, 128) == 7 * 128 * 128
    assert k.bytes_per_call(1, 1, 128, 128, 2) == 4 * 128 * 2 + 128 * 4 + 4
    # a 16384-token prompt in one layer: 60 GFLOP, 0.8 GB; by the bytes
    f = k.flops_per_call(16384, 32, 128, 128)
    b = k.bytes_per_call(16384, 32, 128, 128, 2)
    assert f == 16384 * 32 * 114688 and b == 16384 * 32 * 1540
    assert k.least_seconds(16384, 32, 128, 128, 2, PEAKS) \
        == pytest.approx(max(f / 197e12, b / 819e9)) \
        == pytest.approx(b / 819e9)


def test_latent_decode_counts_read_a_row_once():
    k = harness.load_module(ROOT, "kernels", "mla_decode")
    # two slots of 1000 and 3000 rows of 576 bfloat16 numbers, read
    # ONCE for all 32 heads; 32 queries of 576 in and 32 latents of 512
    # back a slot
    ctx = [1000, 3000]
    assert k.bytes_per_call(ctx, 32, 512, 64, 2) \
        == 4000 * 576 * 2 + 2 * 32 * 576 * 2 + 2 * 32 * 512 * 2
    assert k.flops_per_call(ctx, 32, 512, 64) \
        == 4000 * 32 * (2 * 576 + 2 * 512)
    least = k.least_seconds(ctx, 32, 512, 64, 2, PEAKS)
    assert least == pytest.approx(k.bytes_per_call(ctx, 32, 512, 64, 2)
                                  / 819e9)
    # K and V arrays of 32 heads of 192 and 128 would be 17 times that
    assert 4000 * 32 * (192 + 128) * 2 > 17 * 4000 * 576 * 2


def test_kda_ops_are_told_by_their_shapes():
    trace = {"ops": {
        "multiply_reduce_fusion:fusion:bf16[33,32,128]": 1,
        "copy-done:copy-done:f32[33,32,128,128]": 2,
        "fusion:fusion:bf16[33,3,12288]": 3,
        "mla.attend:custom-call:bf16[32,32,640]": 4,
        "fusion:fusion:bf16[32,2560]": 5,
        "tuple:tuple:s32[33]": 6,
        "fusion:fusion:f32[2,32,128,128]": 7,
        "fusion:fusion:f32[16,2,32,64,128]": 8,
        "fusion:fusion:f32[1,32,16,4,64,128]": 9,
        "custom-call:custom-call:bf16[2,32,16384,192]": 10,
        "fusion:fusion:bf16[2,1024,12288]": 11,
        "ragged-dot-none:custom-call:bf16[16384,1536]": 12}}
    assert sorted(kda_ops.decode_ops(trace, 32).values()) == [1, 2, 3]
    assert sorted(v for _, v in kda_ops.chunk_ops(trace, 32, 128).values()) \
        == [7, 8, 9]
    assert kda_ops.chunk_ops(trace, 32, 128)[
        "fusion:fusion:f32[16,2,32,64,128]"][0] == 2


# ------------------------------------------- readers, recorded summary --

@pytest.fixture(scope="module")
def recorded():
    """The reduced trace of a chip run of the cell (my chip run, PR 35,
    seed 3000003531 traced at 1.2 req/s on the final tree's `git
    archive`; cut to the ops the readers look at), and a record as the
    runner leaves it."""
    trace = harness.load_json(os.path.join(DATA,
                                           "ling3f_trace_summary.json"))
    record = {"root": ROOT, "peaks": harness.peaks_for("TPU v5 lite"),
              "occupancy": {"occupancy": RECORDED["occupancy"]},
              "mean_decode_ctx": RECORDED["mean_decode_ctx"],
              # `serve.geometry` divides hidden by heads: head_dim 80 is
              # what the runner records for this cell, and is not read
              "geometry": {"slots": 32, "page_size": 16, "q_heads": 32,
                           "kv_heads": 32, "head_dim": 80, "itemsize": 2}}
    return record, trace


# the recorded run's line: `serve.batch_occupancy_pct.open` and the
# run's `mean_decode_ctx`
RECORDED = {"occupancy": [0.177144], "mean_decode_ctx": 7077.07}


def _reader(name):
    return harness.load_module(ROOT, "layer_metrics", name)


def test_the_two_decode_rooflines_read_the_scoped_kernels(recorded):
    record, trace = recorded
    steps = trace["programs"]["_raw_decode_step"]["calls"]
    state = trace["ops"]["kda.state_update:custom-call:f32[33,32,128,128]"]
    attend = trace["ops"]["mla.attend:custom-call:bf16[32,32,640]"]
    assert state["calls"] == 6 * steps and attend["calls"] == steps
    slots = RECORDED["occupancy"][0] * 32
    ks = harness.load_module(ROOT, "kernels", "kda_state_update")
    km = harness.load_module(ROOT, "kernels", "mla_decode")
    got = _reader("kda.state_update_roofline").read(record, trace)
    assert got == pytest.approx(100 * ks.least_seconds(
        slots, 32, 128, 128, 2, record["peaks"]) / state["median_s"])
    got_m = _reader("mla.decode_roofline").read(record, trace)
    assert got_m == pytest.approx(100 * km.least_seconds(
        [RECORDED["mean_decode_ctx"]] * round(slots), 32, 512, 64, 2,
        record["peaks"]) / attend["median_s"])
    assert 0 < got < 100 and 0 < got_m < 100
    # the prefill's flash kernel carries the same scope's name: not read
    assert any(n.startswith("mla.attend:") and not n.endswith("[32,32,640]")
               for n in trace["ops"])
    # every slot occupied at the same kernel times is still under 100
    full = dict(record, occupancy={"occupancy": [1.0]})
    assert _reader("mla.decode_roofline").read(full, trace) < 100


def test_step_share_sums_the_ops_that_lead_with_the_pools_rows(recorded):
    record, trace = recorded
    got = _reader("kda.step_share_pct.open").read(record, trace)
    want = sum(v["total_s"] for n, v in trace["ops"].items()
               if "[33," in n and not n.startswith(("copy-start",
                                                    "slice-start")))
    assert got == pytest.approx(
        100 * want / trace["programs"]["_raw_decode_step"]["total_s"])
    assert 5 < got < 60


def test_chunk_roofline_counts_its_tokens_from_the_trace(recorded):
    record, trace = recorded
    got = _reader("kda.chunk_roofline").read(record, trace)
    ops = kda_ops.chunk_ops(trace, 32, 128)
    seconds = sum(v["total_s"] for _, v in ops.values())
    # the scan's carried state, once a chunk a layer, by rows of 1 and 2
    steps = {r: max(v["calls"] for n, (rows, v) in ops.items()
                    if rows == r and n.endswith(f"f32[{r},32,128,128]"))
             for r in (1, 2)}
    token_layers = 64 * (steps[1] + 2 * steps[2])
    # six KDA layers of the traced prefills' buckets (the trace's edges
    # cut a prefill, so not whole buckets): tens of thousands of tokens
    assert 6 * 4096 < token_layers < 6 * 6 * 32768
    kc = harness.load_module(ROOT, "kernels", "kda_chunk")
    assert got == pytest.approx(100 * kc.least_seconds(
        token_layers, 32, 128, 128, 2, record["peaks"]) / seconds)
    assert 0 < got < 100


def test_readers_find_nothing_where_the_program_has_nothing(recorded):
    """A parent commit's trace: no scoped kernel, no state rows. The
    readers return None and do not raise."""
    record, trace = recorded
    bare = dict(trace, ops={n: v for n, v in trace["ops"].items()
                            if n.startswith("ragged-dot")})
    names = ("kda.state_update_roofline", "kda.chunk_roofline",
             "mla.decode_roofline", "kda.step_share_pct.open")
    for name in names:
        assert _reader(name).read(record, bare) is None
    unsampled = dict(record, occupancy=None)
    for name in names[:1] + names[2:3]:
        assert _reader(name).read(unsampled, trace) is None

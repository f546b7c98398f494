"""The cell `pangu-reason-open` as new files: tiny through the harness
on the CPU (both halves of the new check sound, the 8-bit control
failing each), the check's own arithmetic on a made-up record, the
runner's record, the configuration against the published keys, and the
five per-layer readers on a made-up trace summary (the kernel count is
held to a hand count in `tests/test_openpangu_moe.py`).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_pangu_cell.py -q -p no:cacheprovider
"""
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks import rehearse  # noqa: E402
from benchmarks.lib import harness  # noqa: E402

CELL = "pangu-reason-open"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module", autouse=True)
def _kernels():
    rehearse.interpret_kernels()
    yield
    # the cells' tests read process-wide totals, and this cell counts
    # `mla.keys_live` without the `dsa.*` twin cell 6's test holds it to
    from paddle_tpu.observability import metrics
    metrics.get_registry().reset()


def test_the_cell_tiny_through_the_harness_and_its_control_fails(tmp_path):
    """Tiny, float32, one dense and two expert layers and the MTP layer,
    the drafter on, the span kernel in interpret mode."""
    from paddle_tpu.observability import metrics
    root = rehearse.tiny_root(str(tmp_path))
    line = harness.run_cell(root, CELL, 3_000_000_001, 4.0, False,
                            time.perf_counter(), require_tpu=False,
                            control=("int8",))
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["correct"], line["check"]
    chk = line["check"]
    assert set(chk["compared"]) == {"gap_max", "gap_mean", "draft_gap_max",
                                    "draft_gap_mean"}
    assert chk["drafts_compared"] > 100
    assert chk["argmax_share"] == chk["draft_argmax_share"] == 1.0
    assert line["control_fails"] == {"int8": True}, chk
    ctl = chk["control"]["int8"]
    assert ctl["gap_mean"]["fails"] and ctl["draft_gap_mean"]["fails"]
    assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    kernels = {s.labels["kernel"]: s.value for s in
               metrics.counter("kernels.paged_decode").samples()}
    assert kernels.get("paged_latent_attention")
    total = lambda n: sum(s.value for s in metrics.counter(n).samples())
    assert total("mtp.drafts_proposed") > 100
    assert total("mtp.tokens_committed") == total("mtp.drafts_proposed") \
        + total("mtp.drafts_accepted")
    assert 0 < total("moe.assignments_local") <= total("moe.assignments")
    reader = harness.load_module(ROOT, "layer_metrics", "mtp.accept_pct.open")
    assert reader.read({}, {}) == pytest.approx(
        100 * total("mtp.drafts_accepted") / total("mtp.drafts_proposed"))


class _Reference:
    """Logits that put token (row + 1) % 7 first, by 1.0; the draft
    logits put (row + 2) % 7 first. `quant` shifts every choice by one."""
    @staticmethod
    def _at(rows, shift, quant):
        out = np.zeros((len(rows), 7), np.float32)
        out[np.arange(len(rows)),
            (np.asarray(rows) + shift + (quant is not None)) % 7] = 1.0
        return out

    def logits_at(self, cfg, seed, ids, rows, quant=None):
        return self._at(rows, 1, quant)

    def draft_logits_at(self, cfg, seed, ids, rows, quant=None):
        assert max(rows) <= len(ids) - 2
        return self._at(rows, 2, quant)


def test_the_check_reads_each_drafts_own_row():
    """A request of 3 prompt tokens and 5 served: served token i sits at
    position 3 + i and is predicted by row 2 + i; a draft for its place
    was made by the MTP module's row 1 + i. One wrong draft fails the
    drafted half alone; the control fails each half."""
    both = harness.load_module(ROOT, "checks", "served_and_drafted")
    served_tokens = harness.load_module(ROOT, "checks", "served_tokens")
    limits = {"gap_max": 0.5, "gap_mean": 0.1, "draft_gap_max": 0.5,
              "draft_gap_mean": 0.3}
    prompt = [9, 9, 9]
    served = [(2 + i + 1) % 7 for i in range(5)]
    # ticks: [1 token], [2 tokens: accepted], [1], [1]: no draft for
    # served[0] (the prefill's) nor for served[2] (behind an accepted one)
    drafted = [[i, (1 + i + 2) % 7] for i in (1, 3, 4)]
    rec = both.compare(_Reference(), served_tokens, {}, 1,
                       [(prompt, served, drafted)], limits, 4,
                       control=("int8",))
    assert rec["correct"], rec
    assert (rec["positions_compared"], rec["drafts_compared"]) == (5, 3)
    assert rec["control_fails"] == {"int8": True}
    wrong = [[1, 0]] + drafted[1:]
    rec = both.compare(_Reference(), served_tokens, {}, 1,
                       [(prompt, served, wrong)], limits, 4)
    assert not rec["correct"]
    assert rec["compared"]["gap_max"]["value"] == 0.0
    assert rec["compared"]["draft_gap_max"]["value"] == 1.0
    assert rec["compared"]["draft_gap_mean"]["value"] == pytest.approx(1 / 3)
    # no drafted token at all is not correct either
    rec = both.compare(_Reference(), served_tokens, {}, 1,
                       [(prompt, served, [])], limits, 4)
    assert not rec["correct"] and rec["drafts_compared"] == 0


def test_the_runner_keeps_each_events_drafts():
    runner = harness.load_module(ROOT, "runners", "serve_open_drafted")
    from paddle_tpu.serving.streaming import StreamEvent

    class Handle:
        def stream(self, timeout):
            yield StreamEvent(0, "token", 5, 1, span=(5,))
            yield StreamEvent(0, "token", 7, 3, span=(6, 7), drafted=(6,))
            yield StreamEvent(0, "token", 8, 4, span=(8,), drafted=(2,))
            yield StreamEvent(0, "end", status="ok")

    req = runner.Req(0.0, [1, 2], 4)
    req.handle = Handle()
    runner.consume(req, 1.0)
    assert req.tokens == [5, 6, 7, 8] and req.ok
    assert req.drafted == [(1, 6), (3, 2)]
    assert len(req.t_events) == 4
    loop, made = runner._open_loop()
    assert loop.serve.consume is runner.consume
    assert isinstance(loop.serve.Req(0.0, [1], 1), runner.Req) and made
    # joined by what was sent and served, in the order sent: a copy of
    # the lists joins too, and a request that did not finish is passed
    unfinished = runner.Req(0.0, [9, 9], 4)
    rec = runner._with_drafts(
        {"finished": [(list(req.prompt), list(req.tokens))]},
        [unfinished, req])
    assert rec["drafted"] == [[[1, 6], [3, 2]]]
    with pytest.raises(LookupError):
        runner._with_drafts({"finished": [([3], [4])]}, [req])
    assert rec["runner"] == "serve_open_drafted"


def test_the_configuration_keeps_the_published_keys():
    """Every key of the catalog row's config, but the four the cut
    changes, is in the file as published; no width is among the four."""
    found = harness.find_cell(ROOT, CELL)
    cfg, mix, bench = found["cfg"], found["mix"], found["bench"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "openPangu-Ultra-MoE-718B")
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
            cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 1, 16, 19200)
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "first_k_dense_replace": 3,
                                "n_routed_experts": 256,
                                "vocab_size": 153600}
    # the MTP layer is built, and the drafter is on at its depth
    assert cfg["num_nextn_predict_layers"] == 1
    assert "mtp" not in cfg["assumed"]
    assert cfg["serve"] == {"max_batch_size": 32, "page_size": 16,
                            "max_seq_len": 4096, "num_pages": 8192,
                            "spec_draft_tokens": 1}
    # rank 0 of 16, an eighth of the vocabulary; the guide's floors
    assert cfg["experts_held"] == list(range(16))
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
                "num_attention_heads"):
        assert key not in cfg["reduced"]
    for key in ("stands_for", "memory_plan", "assumed"):
        assert cfg[key]
    assert mix["runner"] == "serve_open_drafted"
    assert (mix["shape_seed"], mix["prompt_len"], mix["output_len"]) == (
        2307, {"median": 768, "sigma": 0.6, "lo": 256, "hi": 2048},
        {"median": 384, "sigma": 0.6, "lo": 128, "hi": 1024})
    assert (mix["warmup_s"], mix["drain_max_s"], mix["trace_s"],
            mix["trace_names_s"]) == (10.0, 30.0, 3.0, 1.5)
    assert sorted(map(tuple, mix["warm"]["prefill"])) == [
        (1, b) for b in (256, 512, 1024, 2048)]
    assert found["limits"]["check"] == "served_and_drafted"
    assert set(found["limits"]["limits"]) == {
        "gap_max", "gap_mean", "draft_gap_max", "draft_gap_mean"}
    # the cell is listed wherever its readers find something to read
    mine = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert {"ttft_p95_ms", "tpot_p95_ms", "setup_s", "mtp.accept_pct.open",
            "step.verify_ms.open", "mtp.draft_share_pct.open",
            "mla.verify_roofline", "moe.expert_load_max_over_mean.open",
            "step.prefill_share_pct.open", "setup.jit_trace_s",
            "device.idle_pct.open"} <= mine
    assert not {"step.decode_ms.open", "mla.decode_roofline"} & mine
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == [
        "mtp.accept_pct.open", "step.verify_ms.open",
        "mtp.draft_share_pct.open", "mla.verify_roofline",
        "moe.held_banks_roofline"]
    assert all(m["moves"] == "tpot_p95_ms" for m in new)


# --------------------------------------------- readers, made-up summary --

def _trace(verify_ms=12.0, draft_ms=3.0, attend_us=120.0, ticks=100):
    prog = lambda ms, n: {"calls": n, "total_s": n * ms * 1e-3,
                          "median_s": ms * 1e-3}
    return {"programs": {"_raw_mtp_step": prog(verify_ms + draft_ms, ticks),
                         "_raw_prefill": prog(40.0, 3)},
            "ops": {"mla.attend:custom-call:bf16[32,256,640]": {
                        "calls": 6 * ticks,
                        "total_s": 6 * ticks * attend_us * 1e-6,
                        "median_s": attend_us * 1e-6},
                    "mla.attend:custom-call:bf16[1,768,128,192]": {
                        "calls": 18, "total_s": 0.01, "median_s": 5e-4}}}


def _record(occupancy=0.75, ctx=1000.0):
    return {"root": ROOT, "peaks": harness.peaks_for("TPU v5 lite"),
            "occupancy": {"occupancy": [occupancy]}, "mean_decode_ctx": ctx,
            "geometry": {"slots": 32, "page_size": 16, "q_heads": 128,
                         "kv_heads": 128, "head_dim": 60, "itemsize": 2}}


def _reader(name):
    return harness.load_module(ROOT, "layer_metrics", name)


def test_the_ticks_readers_read_its_one_program(tmp_path):
    assert _reader("step.verify_ms.open").read(_record(), _trace()) \
        == pytest.approx(15.0)
    # the draft pass is what follows the trunk's head in each run of the
    # program: the first of its two head ops (`[slots, span]` out, at
    # least half the time the head's 295 MB take to read)
    share = _reader("mtp.draft_share_pct.open")
    hlo = lambda name, shape, op: f"%{name} = {shape}{{1,0}} {op}(%a, %b)"
    ops, mods, t = [], [], 10.0
    for _ in range(3):
        start = t
        for name, shape, op, ms in (
                ("fusion.1", "bf16[64,7680]", "fusion", 11.0),
                ("mla.attend.3", "bf16[32,256,640]", "custom-call", 0.6),
                ("iota_reduce_fusion.9", "bf16[32,2]", "fusion", 0.4),
                ("compare_select_fusion.2", "s32[32,2]", "fusion", 0.0003),
                ("fusion.1", "bf16[64,7680]", "fusion", 2.5997),
                ("iota_reduce_fusion.12", "bf16[32,2]", "fusion", 0.4)):
            ops.append((hlo(name, shape, op), t, ms * 1e-3))
            t += ms * 1e-3
        mods.append(("jit__raw_mtp_step(77)", start, t - start))
        t += 0.005
    # a run cut by the trace's edge shows one head: not counted
    mods.append(("jit__raw_mtp_step(77)", t, 0.012))
    ops.append((hlo("iota_reduce_fusion.9", "bf16[32,2]", "fusion"), t + 0.011,
                0.0004))
    planes = [{"name": "/device:TPU:0",
               "lines": {"XLA Ops": ops, "XLA Modules": mods}}]
    found = share.ticks(planes, share.TICK, share.head_test(
        32, 2, 19200, 7680 * 19200 * 2 / 819e9))
    assert len(found) == 3
    assert [round(1e3 * d, 3) for _, d in found] == [3.0] * 3
    assert sum(d for _, d in found) / sum(t for t, _ in found) \
        == pytest.approx(0.2)
    # no trace on disk (the harness clears it after the readers): nothing
    assert share.read(dict(_record(), root=str(tmp_path)), _trace()) is None


def test_the_verify_roofline_counts_the_rows_once():
    record, trace = _record(), _trace()
    got = _reader("mla.verify_roofline").read(record, trace)
    k = harness.load_module(ROOT, "kernels", "mla_verify")
    least = k.least_seconds([1000.0] * 24, 2, 128, 512, 64, 2,
                            record["peaks"])
    assert got == pytest.approx(100 * least / 120e-6)
    # 24 slots x 1000 rows x 256 query rows x 2176 operations: 68 us of
    # the MXU, where the rows' bytes take 34 us
    assert least == pytest.approx(24_000 * 256 * 2176 / 197e12, rel=0.01)
    assert 50 < got < 100
    # every slot occupied at a kernel that takes the least time reads 100
    full = _reader("mla.verify_roofline").read(
        _record(1.0), _trace(attend_us=1e6 * k.least_seconds(
            [1000.0] * 32, 2, 128, 512, 64, 2, record["peaks"])))
    assert full == pytest.approx(100.0)


def test_the_held_banks_roofline_counts_the_banks_touched():
    """64 rows x 8 of 256 experts, 16 held: 24 slots' 48 span tokens
    touch 16 x (1 - (31/32)^48) = 12.5 banks of 3 x 7680 x 2048 bf16
    numbers, 1.18 GB at 819 GB/s = 1.44 ms; the two grouped matmuls
    take 2.0 + 1.0 ms."""
    ragged = lambda ms, n: {"calls": n, "total_s": n * ms * 1e-3,
                            "median_s": ms * 1e-3}
    trace = _trace()
    trace["ops"].update({
        "ragged-dot-none:custom-call:bf16[512,4096]": ragged(2.0, 500),
        "ragged-dot-none:custom-call:bf16[512,7680]": ragged(1.0, 500),
        # a prefill's rows are another extent
        "ragged-dot-none:custom-call:bf16[8192,4096]": ragged(2.7, 15)})
    got = _reader("moe.held_banks_roofline").read(_record(), trace)
    banks = 16 * (1 - (31 / 32) ** 48)
    weights = banks * 3 * 7680 * 2048 * 2
    rows = 48 * 8 * 16 / 256 * (2 * 7680 + 3 * 2048) * 2
    assert got == pytest.approx(100 * (weights + rows) / 819e9 / 3.0e-3)
    assert 45 < got < 50
    assert _reader("moe.held_banks_roofline").read(_record(), _trace()) \
        is None


def test_the_sweeps_verdict():
    from benchmarks import sweep_knee
    line = lambda rate, first, last, failed=0: {
        "rate_per_s": rate, "failed": failed,
        "ttft_ms_first_third": first, "ttft_ms_last_third": last}
    lines = [line(1.5, 97, 102), line(1.75, 129, 145),
             line(1.875, 120, 221), line(2.0, 121, 2828),
             line(1.25, 100, 90, failed=1)]
    assert [sweep_knee.holds(ln) for ln in lines] == [
        True, True, False, False, False]
    assert sweep_knee.verdict(lines) == {
        "holds": [1.5, 1.75], "knee": 1.75, "rate_per_s": 1.4}
    assert sweep_knee.verdict(lines[2:])["rate_per_s"] is None


def test_readers_find_nothing_where_the_program_has_nothing():
    """A parent commit's trace: no such program, no such kernel. The
    readers return None and do not raise."""
    bare = {"programs": {"_raw_decode_step": {"calls": 1, "total_s": 1.0,
                                              "median_s": 1.0}},
            "ops": {"mla.attend:custom-call:bf16[32,64,640]": {
                "calls": 1, "total_s": 1.0, "median_s": 1.0}}}
    for name in ("step.verify_ms.open", "mtp.draft_share_pct.open",
                 "mla.verify_roofline", "moe.held_banks_roofline"):
        assert _reader(name).read(_record(), bare) is None
    assert _reader("mla.verify_roofline").read(
        dict(_record(), occupancy=None), _trace()) is None

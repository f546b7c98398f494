"""The harness end to end at a tiny size on the CPU (Pallas kernels in
interpret mode): a cell added as new files only, the control that has
to fail, and a broken timed path that has to come out not correct.

Run by hand (about five minutes):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
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

TINY_CFG = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
                max_position_embeddings=256, rms_norm_eps=1e-5,
                rope_theta=1e6, initializer_range=0.02,
                tie_word_embeddings=False, dtype="float32")


@pytest.fixture(scope="module", autouse=True)
def _kernels():
    rehearse.interpret_kernels()


def _run(root, workload, seed=3_000_000_001, seconds=3.0, **kw):
    return harness.run_cell(root, workload, seed, seconds, False,
                            time.perf_counter(), require_tpu=False, **kw)


def test_reference_matches_the_program_in_float32():
    import paddle_tpu as paddle
    builder = harness.load_module(ROOT, "models", "llama_like")
    reference = harness.load_module(ROOT, "reference", "llama_like")
    seed = 5_000_000_000
    model, n = builder.build(TINY_CFG, seed)
    ids = np.random.RandomState(0).randint(0, 512, (1, 40)).astype(np.int32)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids))._value)[0]
    want = reference.logits_at(TINY_CFG, seed, ids[0], np.arange(40))
    assert np.abs(got - want).max() < 1e-5 * max(1.0, np.abs(want).max())
    low = reference.logits_at(TINY_CFG, seed, ids[0], np.arange(40),
                              quant="int8")
    assert np.abs(low - want).max() > 100 * np.abs(got - want).max()


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A throw-away configuration, mix, limits file, per-layer metric,
    kernel count and builder go in as NEW files plus one entry each in
    BENCHMARK.json; no file that was there is edited."""
    root = rehearse.tiny_root(str(tmp_path))
    b = os.path.join(root, "benchmarks")
    before = {}
    for d, _, files in os.walk(b):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    cfg = harness.load_json(os.path.join(
        b, "configs", "deepseek-llm-7b-serve.json"))
    cfg.update(builder="throwaway_builder", num_hidden_layers=1)
    mix = harness.load_json(os.path.join(b, "traffic", "chat-open.json"))
    mix.update(rate_per_s=3.0)
    new = {
        "configs/throwaway.json": json.dumps(cfg),
        "traffic/throwaway-mix.json": json.dumps(mix),
        "limits/throwaway-cell.json": json.dumps(
            {"check": "served_tokens", "requests_compared": 2,
             "limits": {"gap_max": 0.04, "gap_mean": 0.0006}}),
        "models/throwaway_builder.py":
            "from benchmarks.models.llama_like import build  # noqa\n",
        "kernels/throwaway_kernel.py":
            "def bytes_per_call(n):\n    return 8 * n\n",
        "layer_metrics/throwaway.metric.py":
            "from benchmarks.lib import harness\n"
            "def read(record, trace):\n"
            "    k = harness.load_module(record['root'], 'kernels',"
            " 'throwaway_kernel')\n"
            "    return float(k.bytes_per_call(record['attempted']))\n",
    }
    for rel, text in new.items():
        with open(os.path.join(b, rel), "w") as f:
            f.write(text)
    path = os.path.join(root, "BENCHMARK.json")
    bench = harness.load_json(path)
    bench["configs"].append({"name": "throwaway", "source": "none",
                             "file": "benchmarks/configs/throwaway.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway-cell",
                               "config": "throwaway",
                               "traffic": "throwaway-mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p95_ms", "tpot_p95_ms"):
            m["workloads"].append("throwaway-cell")
    bench["per_layer"].append({"name": "throwaway.metric", "unit": "B",
                               "better": "lower",
                               "source": "program_counter",
                               "layer": "test", "moves": "ttft_p95_ms",
                               "workloads": ["throwaway-cell"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    line = _run(root, "throwaway-cell")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    traced = harness.run_cell(root, "throwaway-cell", 7, 2.0, True,
                              time.perf_counter(), require_tpu=False)
    assert traced["metrics"]["throwaway.metric"]["value"] == \
        8.0 * traced["attempted"]
    for p, data in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == data, f"{p} was edited"


@pytest.mark.parametrize("workload", ["dsllm7b-chat-open",
                                      "mistral7b-sessions-closed"])
def test_sound_run_is_correct_and_the_8bit_controls_fail(tmp_path, workload):
    """The control kept as a test, at a size a test run can hold: the
    reference in int8 (and in fp8) in the program's place fails the
    limits that the sound program passes."""
    root = rehearse.tiny_root(str(tmp_path))
    line = _run(root, workload, seconds=5.0, control=True)
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["correct"], line["check"]
    assert line["control_fails"] == {"int8": True, "fp8": True}, \
        line["check"]


def test_a_broken_decode_step_comes_out_not_correct(tmp_path, monkeypatch):
    """The rest of a run, with the timed path broken underneath: the
    decode step's token is altered where it is produced."""
    from paddle_tpu.inference import ContinuousBatchingPredictor as P
    sound = P._raw_decode_step

    def broken(self, *args):
        nxt, done, k, v = sound(self, *args)
        return (nxt + 1) % 512, done, k, v

    monkeypatch.setattr(P, "_raw_decode_step", broken)
    root = rehearse.tiny_root(str(tmp_path))
    line = _run(root, "dsllm7b-chat-open")
    assert line["attempted"] > 0 and line["failed"] == 0
    assert not line["correct"]


def test_a_finished_list_with_nothing_in_it_is_not_correct():
    check = harness.load_module(ROOT, "checks", "served_tokens")
    found = {"cfg": dict(TINY_CFG, reference="llama_like"),
             "limits": {"requests_compared": 4,
                        "limits": {"gap_max": 1.0, "gap_mean": 0.05}}}
    rec = check.decide(ROOT, found, 1, {"finished": []})
    assert not rec["correct"] and rec["positions_compared"] == 0


def test_the_sample_spreads_over_the_list_and_holds_the_longest():
    check = harness.load_module(ROOT, "checks", "served_tokens")
    finished = [([i] * (5 + i % 7), [i] * 3) for i in range(65)]
    finished[40] = ([40] * 50, [40] * 9)
    got = check.draw_sample(finished, 2**31 + 9, 9)
    assert got == check.draw_sample(finished, 2**31 + 9, 9)
    assert got != check.draw_sample(finished, 2**31 + 10, 9)
    first = [p[0] for p, _ in got]
    assert first[0] == 40 and len(set(first)) == 9
    rest = [i for i in range(65) if i != 40]
    # one out of each eighth of the rest of the list
    assert [rest.index(i) // 8 for i in sorted(first[1:])] == list(range(8))
    assert check.draw_sample(finished[:3], 1, 16) and \
        len(check.draw_sample(finished[:3], 1, 16)) == 3
    assert len(check.draw_sample(finished[:1], 1, 16)) == 1


def test_a_cell_of_another_kind_brings_its_runner_and_its_check(tmp_path):
    """A cell that serves no model (the train cell will be one): its
    runner, its check and its end-to-end metric are new files and
    entries; the harness is not edited and knows nothing of them."""
    root = rehearse.tiny_root(str(tmp_path))
    b = os.path.join(root, "benchmarks")
    new = {
        "configs/sums.json": json.dumps({"rows": 1000}),
        "traffic/sum-once.json": json.dumps({"runner": "sum_rows"}),
        "limits/sums-cell.json": json.dumps(
            {"check": "exact_sum", "limits": {"error": 0}}),
        "runners/sum_rows.py":
            "import time\n"
            "def run(ctx):\n"
            "    import jax.numpy as jnp\n"
            "    n = ctx['cfg']['rows']\n"
            "    t0 = time.perf_counter()\n"
            "    total = int(jnp.sum(jnp.arange(n)))\n"
            "    return {'attempted': 1, 'failed': 0, 'answer': total,\n"
            "            'memory_peak_bytes': 0, 'metrics': {\n"
            "            'rows_per_s': n / (time.perf_counter() - t0),\n"
            "            'setup_s': t0 - ctx['t_process_start']}}\n",
        "checks/exact_sum.py":
            "def decide(root, found, seed, record, control=False):\n"
            "    n = found['cfg']['rows']\n"
            "    err = abs(record['answer'] - n * (n - 1) // 2)\n"
            "    lim = found['limits']['limits']['error']\n"
            "    return {'correct': err <= lim, 'compared':\n"
            "            {'error': {'value': err, 'limit': lim}}}\n",
    }
    for rel, text in new.items():
        with open(os.path.join(b, rel), "w") as f:
            f.write(text)
    path = os.path.join(root, "BENCHMARK.json")
    bench = harness.load_json(path)
    bench["configs"].append({"name": "sums", "source": "none",
                             "file": "benchmarks/configs/sums.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "sums-cell", "config": "sums",
                               "traffic": "sum-once", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "rows_per_s", "unit": "rows/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["sums-cell"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    line = _run(root, "sums-cell")
    assert line["correct"] and set(line["metrics"]) == {"rows_per_s",
                                                        "setup_s"}
    found = harness.find_cell(root, "sums-cell")
    check = harness.load_module(root, "checks", "exact_sum")
    assert not check.decide(root, found, 1, {"answer": 7})["correct"]

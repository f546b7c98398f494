"""On-device serving benchmark: AOT-style decode throughput/latency.

Covers BASELINE config[4] ("static-graph predictor → XLA AOT serve"):
drives LLMPredictor's jitted static-KV-cache decode loop on a
bench-sized Llama and reports prefill latency + decode tokens/s for
batch 1 (interactive latency) and batch 8 (throughput serving).

    python tools/serve_bench.py            # real chip (or CPU smoke)

Prints one JSON line and writes it to output/serve_bench_r04.json itself
(real chip only; CPU smoke runs write serve_bench_cpu_smoke.json so a
test run can never clobber TPU evidence).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main(argv=None):
    import jax
    on_tpu = jax.default_backend() != "cpu"

    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMPredictor
    from paddle_tpu.models import LlamaConfig

    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=8,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=2048,
                          tensor_parallel=False)
        prompt_len, max_new, iters = 120, 128, 3
    else:  # CPU smoke for CI
        cfg = LlamaConfig.tiny(tensor_parallel=False)
        prompt_len, max_new, iters = 12, 8, 1

    # host-side init; restore the flag on exit — the pytest smoke runs
    # main() in-process
    from paddle_tpu.framework.flags import flag_value
    prev_host_init = flag_value("host_init")
    paddle.set_flags({"host_init": True})
    try:
        return _run(paddle, LLMPredictor, cfg, on_tpu, prompt_len,
                    max_new, iters)
    finally:
        paddle.set_flags({"host_init": prev_host_init})


def _run(paddle, LLMPredictor, cfg, on_tpu, prompt_len, max_new, iters):
    import jax
    from paddle_tpu.models import LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()

    rs = np.random.RandomState(0)
    results = {}
    for batch in (1, 8):
        pred = LLMPredictor(model, max_batch_size=batch, do_sample=False)
        prompts = [list(rs.randint(1, cfg.vocab_size, prompt_len))
                   for _ in range(batch)]
        # warmup/compile both shapes used below
        t0 = time.perf_counter()
        pred.generate(prompts, max_new_tokens=max_new)
        pred.generate(prompts, max_new_tokens=1)
        compile_s = time.perf_counter() - t0
        # prefill-only (max_new=1): one forward over the prompt
        t0 = time.perf_counter()
        for _ in range(iters):
            pred.generate(prompts, max_new_tokens=1)
        t_prefill = (time.perf_counter() - t0) / iters
        # full call: prefill + max_new decode steps
        t0 = time.perf_counter()
        for _ in range(iters):
            out = pred.generate(prompts, max_new_tokens=max_new)
        t_full = (time.perf_counter() - t0) / iters
        new_per_call = sum(len(o) for o in out)
        # pure-decode rate: the extra (max_new-1) steps over prefill
        decode_tps = (max(new_per_call - batch, 1)
                      / max(t_full - t_prefill, 1e-9))
        results[f"b{batch}"] = {
            "decode_tokens_per_s": round(decode_tps, 1),
            "e2e_tokens_per_s": round(new_per_call / t_full, 1),
            "prefill_latency_s": round(t_prefill, 4),
            "latency_s_per_call": round(t_full, 4),
            "compile_s": round(compile_s, 1),
            "new_tokens_per_call": new_per_call,
        }
        print(f"[serve-bench] batch={batch}: {results[f'b{batch}']}",
              file=sys.stderr, flush=True)

    # continuous batching: streaming mixed-length requests through the
    # paged-KV slot scheduler (VERDICT r4 #5 "serve bench holds
    # throughput with streaming mixed-length requests")
    from paddle_tpu.inference import ContinuousBatchingPredictor
    n_req = 16 if on_tpu else 6
    mixed = [list(rs.randint(1, cfg.vocab_size,
                             int(rs.randint(prompt_len // 4,
                                            prompt_len + 1))))
             for _ in range(n_req)]
    cb = ContinuousBatchingPredictor(
        model, max_batch_size=8 if on_tpu else 2,
        page_size=16, max_seq_len=prompt_len + max_new + 16)
    cb.generate(mixed[:2], max_new_tokens=2)   # warm the compile caches
    cb.stats.update({k: 0 for k in cb.stats})  # report ONLY the timed run
    t0 = time.perf_counter()
    out_cb = cb.generate(mixed, max_new_tokens=max_new)
    t_cb = time.perf_counter() - t0
    cb_tokens = sum(len(o) for o in out_cb)
    results["continuous"] = {
        "tokens_per_s": round(cb_tokens / t_cb, 1),
        "requests": n_req, "new_tokens": cb_tokens,
        "decode_steps": cb.stats["decode_steps"],
        "max_in_flight": cb.stats["max_in_flight"],
        "latency_s": round(t_cb, 3),
    }
    print(f"[serve-bench] continuous: {results['continuous']}",
          file=sys.stderr, flush=True)

    line = json.dumps({
        "metric": "llama_serve_decode_tokens_per_sec",
        "value": results["b8"]["decode_tokens_per_s"],
        "unit": "tokens/s",
        "aux": {**results, "backend": jax.default_backend(),
                "prompt_len": prompt_len, "max_new": max_new,
                "dtype": "bfloat16" if on_tpu else "float32"},
    })
    print(line)
    # only a real-chip run may write the round artifact — a CPU smoke
    # (e.g. the pytest run) must never clobber TPU evidence
    if on_tpu:
        import importlib.util
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "bench_mod_sb", os.path.join(repo, "bench.py"))
        bm = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bm)
        name = f"serve_bench_r{bm._current_round():02d}.json"
    else:
        name = "serve_bench_cpu_smoke.json"
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "output")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name), "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""`sweep.py`'s loop for a cell whose predictor may hold no prefix cache
(a model with latent pages: `sweep.py` clears `pred.prefix_cache`
unguarded), with the verdict written down: one process, one set-up, the
mix's lengths at a list of rates, then the knee and 0.8 of it.

    python3 benchmarks/sweep_knee.py --workload <name> --rates 1.5,1.75,2 --seconds 51

A rate HOLDS when no request of its window failed and the backlog did
not grow: the median wait for a first token in the last third of the
window's requests is at most the larger of 1.5 times and 100 ms more
than that of the first third (`serve_open`'s `ttft_thirds`; a burst that
drains passes, a queue that grows does not). The knee is the highest
rate swept that holds, and the cell's rate 0.8 of it.

Each rate's line is `sweep.py`'s, with the slots taken at most, the
host's share of a tick and the share of token gaps that followed a
prefill beside it. Not part of a benchmark run. A `benchmark` issue that
guards `sweep.py`'s line 46 and gives it the verdict can delete this
file.
"""
import time
T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def holds(line):
    first, last = line["ttft_ms_first_third"], line["ttft_ms_last_third"]
    return line["failed"] == 0 and last <= max(1.5 * first, first + 100.0)


def verdict(lines):
    """{knee, holds, rate_per_s} of a sweep's lines; the knee is None
    where no rate swept holds."""
    ok = [ln["rate_per_s"] for ln in lines if holds(ln)]
    knee = max(ok) if ok else None
    return {"holds": ok, "knee": knee,
            "rate_per_s": None if knee is None else round(0.8 * knee, 3)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2200000001)
    args = ap.parse_args(argv)
    from benchmarks.lib import harness, prefill_account, serve, stage_gaps
    found = harness.find_cell(ROOT, args.workload)
    dev = harness.device_record()
    if dev["platform"] != "tpu":
        raise SystemExit("the sweep needs a TPU")
    import paddle_tpu  # noqa: F401
    from paddle_tpu.serving import Router
    cfg, mix = found["cfg"], found["mix"]
    ctx = {"cfg": cfg, "mix": mix, "seed": args.seed,
           "seconds": args.seconds, "trace_dir": None,
           "t_process_start": T0,
           "builder": harness.load_module(ROOT, "models", cfg["builder"])}
    runner = harness.load_module(ROOT, "runners", mix["runner"])
    model, pred, _ = serve.build(ctx)
    serve.warm(pred, mix, args.seed, cfg["vocab_size"])
    print(json.dumps({"setup_s": time.perf_counter() - T0, "device": dev}),
          flush=True)
    lines = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        if pred.prefix_cache is not None:
            pred.prefix_cache.clear(pred.pool)
        ctx["seed"] = args.seed + i
        rec = runner.offer(ctx, Router([pred]), pred,
                           dict(mix, rate_per_s=rate))
        lines.append({
            "rate_per_s": rate,
            "attempted": rec["attempted"], "failed": rec["failed"],
            "ttft_ms": rec["timings"]["ttft_ms"],
            "tpot_ms": rec["timings"]["tpot_ms"],
            "ttft_p95_ms": rec["metrics"]["ttft_p95_ms"],
            "tpot_p95_ms": rec["metrics"]["tpot_p95_ms"],
            "ttft_ms_first_third": rec["ttft_thirds"][0],
            "ttft_ms_last_third": rec["ttft_thirds"][2],
            "tokens_out_per_s": rec["tokens_out"] / args.seconds,
            "slots_taken_max": rec["pred_stats"].get("max_in_flight"),
            "tick_host_ms": stage_gaps.tick_host_ms(rec, 50),
            "stalled_token_pct": prefill_account.stalled_token_pct(rec),
            "compiled_in_window": rec["programs"]["compiled_in_window"],
            "memory_peak_bytes": rec["memory_peak_bytes"]})
        lines[-1]["holds"] = holds(lines[-1])
        print(json.dumps(lines[-1]), flush=True)
    print(json.dumps(verdict(lines)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

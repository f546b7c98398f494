"""Find the knee of an open-loop cell once: one process, one set-up,
the mix's lengths at a list of rates. A rate is sustained when the
backlog does not grow: the window's requests finish and the waits of
its last third are no longer than those of its first third.

    python3 benchmarks/sweep.py --workload <name> --rates 4,6,8,10 --seconds 20

Not part of a benchmark run; the cell's rate is then written into its
traffic file as 0.8 of the knee, with the sweep recorded in PERF.md.
"""
import time
T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2200000001)
    args = ap.parse_args(argv)
    from benchmarks.lib import harness, serve
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
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        pred.prefix_cache.clear(pred.pool)
        ctx["seed"] = args.seed + i
        rec = runner.offer(ctx, Router([pred]), pred,
                           dict(mix, rate_per_s=rate))
        print(json.dumps({
            "rate_per_s": rate, "device": dev,
            "attempted": rec["attempted"], "failed": rec["failed"],
            "ttft_ms": rec["timings"]["ttft_ms"],
            "tpot_ms": rec["timings"]["tpot_ms"],
            "ttft_p95_ms": rec["metrics"]["ttft_p95_ms"],
            "tpot_p95_ms": rec["metrics"]["tpot_p95_ms"],
            "ttft_ms_first_third": rec["ttft_thirds"][0],
            "ttft_ms_last_third": rec["ttft_thirds"][2],
            "tokens_out_per_s": rec["tokens_out"] / args.seconds,
            "compiled_in_window": rec["programs"]["compiled_in_window"],
            "memory_peak_bytes": rec["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

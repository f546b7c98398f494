"""Read the numbers that the check's limits are set from: for each seed
one short run of the cell at its own load, in one process, with the
program's gaps and the int8 control's gaps side by side.

    python3 benchmarks/limits.py --workload <name> --seeds 1,2,3 --seconds 8 \\
        [--controls int8,fp8]

Prints one line a seed and a last line with the largest sound reading
and the smallest control reading of each number compared. Not part of a
benchmark run.
"""
import time
T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def bytes_in_use():
    """What the finished run left on the device (it should be nothing
    but the reference's compiled programs)."""
    import jax
    return int((jax.devices()[0].memory_stats() or {}).get(
        "bytes_in_use", 0))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--controls", default="int8,fp8")
    args = ap.parse_args(argv)
    from benchmarks.lib import harness
    sound, control = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        line = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                                False, time.perf_counter(),
                                control=tuple(args.controls.split(",")))
        chk = line["check"]
        print(json.dumps({"seed": seed, "correct": line["correct"],
                          "attempted": line["attempted"],
                          "failed": line["failed"],
                          "compared": chk["compared"],
                          "control": chk["control"],
                          "positions": chk["positions_compared"],
                          "argmax_share": chk["argmax_share"],
                          "device": line["device"],
                          "bytes_in_use_after": bytes_in_use()}),
              flush=True)
        for k, v in chk["compared"].items():
            sound.setdefault(k, []).append(v["value"])
            for q, c in chk["control"].items():
                control.setdefault((q, k), []).append(c[k]["value"])
    print(json.dumps({"readings": {
        k: dict({"sound_largest": max(sound[k]), "sound": sound[k]},
                **{f"{q}_smallest": min(v) for (q, kk), v in
                   control.items() if kk == k},
                **{q: v for (q, kk), v in control.items() if kk == k})
        for k in sound}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one cell of the benchmark once and print the result line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It needs a TPU with as many chips as the cell asks for and exits
non-zero, printing no result, without one. The last line of standard
output is one JSON object: correct, attempted, failed, metrics, device
(and breakdown with --trace 1).
"""
import time
T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks.lib import harness
    line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                            bool(args.trace), T_PROCESS_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

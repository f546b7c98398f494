"""Randomized parity fuzzing vs torch/numpy oracles (round-5 campaign).

    JAX_PLATFORMS=cpu python tools/fuzz_parity.py [family] [seed] [iters]

Families: ops (reductions/manipulation/losses/pooling/linalg/sorting),
ops2 (conv/interpolate/norm/pad/einsum/activations), vision
(transforms + manipulation long tail), grads (backward vs
torch autograd), rnn_dist (RNN weight-copy + distribution goldens),
cf_fft_linalg (dy2static control flow, fft/stft, decompositions),
index (getitem/setitem), dtype (promotion/scalar rules/bitwise),
einsum_io (einsum advanced forms, save/load + jit.save roundtrips).
Default: every family, seed 0.

This harness found and fixed 10 real parity bugs in round 5 (see
tests/test_functional_extra.py TestRound5FuzzFinds and the
cross_entropy/interpolate/pooling/svd/Categorical commit messages);
each find is frozen as a deterministic regression test — the fuzzer
itself stays non-deterministic exploration tooling, runnable in CI via
tests/test_fuzz_smoke.py.
"""
from __future__ import annotations

import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
FAMILIES = {
    "ops": "fuzz_ops.py",
    "ops2": "fuzz_ops2.py",
    "grads": "fuzz_grads.py",
    "rnn_dist": "fuzz_rnn_dist.py",
    "cf_fft_linalg": "fuzz3.py",
    "index": "fuzz_index.py",
    "vision": "fuzz_vision.py",
    "dtype": "fuzz_dtype.py",
    "einsum_io": "fuzz_einsum_io.py",
}


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    fam = argv[0] if argv and argv[0] in FAMILIES else None
    rest = argv[1:] if fam else argv
    seed = rest[0] if rest else "0"
    iters = rest[1] if len(rest) > 1 else "10"
    names = [fam] if fam else list(FAMILIES)
    rc = 0
    for name in names:
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, FAMILIES[name]),
             seed, iters],
            capture_output=True, text=True, timeout=3600)
        tail = [ln for ln in (p.stdout or "").splitlines() if "done:" in ln]
        ok = tail and tail[0].endswith(" 0 failures")
        print(f"[fuzz {name}] {tail[0] if tail else 'NO OUTPUT'}"
              f"{'' if ok else '  <-- FAILURES'}")
        if not ok:
            print((p.stdout or "")[-3000:])
            print((p.stderr or "")[-1500:])
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())

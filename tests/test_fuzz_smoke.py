"""CI smoke for the parity fuzz harness (tools/fuzz_parity.py): a small
deterministic slice of every family must come back clean. The full
harness runs with bigger budgets out-of-band; every bug it has found is
ALSO frozen as a deterministic regression test elsewhere in the suite."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("family,iters", [
    ("ops", "4"), ("ops2", "3"), ("grads", "3"),
    ("rnn_dist", "3"), ("cf_fft_linalg", "3"), ("index", "8"),
    ("vision", "5"), ("dtype", "8"), ("einsum_io", "2"),
])
def test_fuzz_family_smoke(family, iters):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fuzz_parity.py"),
         family, "0", iters],
        capture_output=True, text=True, timeout=1500, env=env, cwd=REPO)
    assert p.returncode == 0, (p.stdout or "")[-2500:]

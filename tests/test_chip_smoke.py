"""chip_smoke.py's phase functions at a tiny size on the CPU, Pallas in
interpret mode (the first rehearsal of the on-chip-measurement guide,
kept as a test), and the script's refusal to pass without a chip.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from paddle_tpu.framework.flags import get_flags, set_flags  # noqa: E402
from paddle_tpu.models import LlamaConfig  # noqa: E402


@pytest.fixture
def interpret():
    """The real Pallas paths through the interpreter, flags restored."""
    names = ["use_pallas_kernels", "pallas_interpret"]
    old = get_flags(names)
    set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
    try:
        yield
    finally:
        set_flags({k.removeprefix("FLAGS_"): v for k, v in old.items()})


def _tiny(**kw):
    """The smallest config the paged kernels' gates accept: 8 heads of
    128 (H % 8 == 0, D % 128 == 0)."""
    base = dict(hidden_size=1024, num_attention_heads=8,
                num_key_value_heads=8, num_hidden_layers=1,
                intermediate_size=128, vocab_size=128,
                tensor_parallel=False)
    base.update(kw)
    return LlamaConfig.tiny(**base)


def test_serve_phase_record(interpret):
    rec = chip_smoke.serve_phase(
        config=_tiny(), dtype="float32", max_batch_size=4, page_size=8,
        max_seq_len=64, new_tokens=4, require_kernels=False)
    assert rec["ok"], rec["checks"]
    assert rec["requests"] == rec["completed"] == 6
    assert rec["tokens_generated"] == 6 * 4
    # "auto" decodes MHA through the block-table kernel, no metadata
    assert rec["pallas_fallbacks"] == 0
    assert set(rec["decode_kernels_traced"]) == {"paged_attention"}
    # the prefix cache did its three jobs: a full hit, and two partial
    # hits (one through copy-on-write)
    assert rec["prefix_hits"] == 1 and rec["prefix_partial_hits"] == 2
    assert rec["first_step_logits_max_err"] <= rec["first_step_logits_bound"]
    assert rec["token_agreement"] >= chip_smoke.TOKEN_AGREEMENT_MIN
    # interpret mode lowers the interpreter, not Mosaic: the check that
    # main() makes on the chip must be able to fail here
    assert rec["decode_has_tpu_custom_call"] is False
    assert "kernel_in_decode" not in rec["checks"]


def test_train_phase_record(interpret):
    rec = chip_smoke.train_phase(
        config=_tiny(), dtype="float32", batch=2, seq=32, steps=3,
        require_kernels=False)
    assert rec["ok"], rec["checks"]
    arm, ref = rec["kernel_arm"], rec["xla_arm"]
    assert len(arm["losses"]) == 3 and arm["losses"][-1] < arm["losses"][0]
    assert arm["params_with_grad"] == arm["param_tensors"] > 0
    assert arm["kernels"] and not ref["kernels"]
    assert rec["loss_rel_err"] <= rec["loss_rtol"]
    assert rec["grad_norm_rel_err"] <= rec["grad_norm_rtol"]
    assert arm["grad_norms"][0] > 0


def test_two_replicas_sit_on_two_devices():
    """The router gives each replica it builds its own device, weights
    and pool both (they used to share device 0), and both serve."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.serving import Router
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
    router = Router([model, model], policy="least_loaded",
                    max_batch_size=2, page_size=8, max_seq_len=32)
    try:
        handles = [router.submit([3 + i, 4, 5, 6], max_new_tokens=3)
                   for i in range(4)]
        outs = [h.result(timeout=120) for h in handles]
        assert [h.status for h in handles] == ["ok"] * 4
        assert {h.replica for h in handles} == {"replica0", "replica1"}
        assert all(len(o) == 3 for o in outs)
        where = []
        for rep in router.replicas:
            pred = rep.predictor
            pool = {d for a in pred.pool.k + pred.pool.v
                    for d in a.devices()}
            weights = {d for a in pred._p_vals for d in a.devices()}
            assert pool == weights and len(pool) == 1
            where.append(pool.pop())
        assert where == jax.devices()[:2]
    finally:
        router.shutdown()


def test_hybrid_phase_record(interpret):
    """data=2,model=2 ZeRO-3 against the one-device step, the Pallas
    calls partitioned by hand over the mesh (kernels._common)."""
    rec = chip_smoke.hybrid_train_phase(
        config=_tiny(hidden_size=2048, num_attention_heads=16,
                     num_key_value_heads=16),
        dtype="float32", batch=2, seq=32, steps=2)
    assert rec["ok"], rec["checks"]
    fp = rec["hybrid"]["footprint"]["params_bytes"]
    assert fp["per_replica"] < fp["global"]
    assert rec["hybrid"]["topology"] == "data=2,model=2"
    assert rec["loss_rel_err_per_step"][0] <= rec["loss_rtol"]


def test_script_fails_without_a_chip(tmp_path):
    """`python chip_smoke.py` on the CPU: non-zero exit, `"ok": false`
    as the last line of stdout, the device named as JAX reports it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=str(tmp_path))
    assert p.returncode != 0
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "needs a TPU" in last["error"]
    assert '"ok": true' not in p.stdout


def test_no_device_is_an_error_not_a_fallback():
    """The places this PR turned from fallbacks into errors."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.framework.place import CPUPlace, TPUPlace
    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        paddle.set_device("tpu")
    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        TPUPlace(0).jax_device
    assert CPUPlace(0).jax_device.platform == "cpu"
    assert paddle.get_device() == "cpu"
    assert jax.config.jax_compilation_cache_dir in (
        os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        os.path.join(REPO, ".jax_cache"))


def test_launcher_parent_stays_off_jax():
    """A chip belongs to one process: the launcher is a parent of the
    processes that need it, so none of its modules may import jax (the
    package import it triggers only updates jax's configuration)."""
    import glob
    import re
    for path in glob.glob(os.path.join(
            REPO, "paddle_tpu", "distributed", "launch", "*.py")):
        with open(path) as f:
            src = f.read()
        assert not re.search(r"^\s*(import jax|from jax)", src, re.M), path

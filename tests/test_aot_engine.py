"""AOT inference engine (paddle_tpu.inference.aot): dy2static capture →
serialized compiled executables → warm-start serving.

Covers the PR-8 acceptance surface:
- captured-vs-eager output parity on the tiny llama model (both the
  raw captured forward program and end-to-end warm-started generate);
- bucket-miss fallback → live JIT + write-back into the bundle;
- digest-verification failure → artifact rejected, counted in
  aot.invalidations, predictor falls back to live JIT (and self-heals);
- jaxlib-fingerprint mismatch → whole bundle rejected + clean rebuild;
- geometry-override mismatch → invalidation + reset;
- the two-tier XLA persistent-cache wiring (fingerprint fence + the
  0.5s min-compile-time floor is enforced, never lowered);
- tools/aot_report.py prints the manifest without importing jax;
- the shared framework.integrity helpers back both the engine bundle
  and VerifiedCheckpointer;
- launcher --engine_dir → PADDLE_TPU_ENGINE_DIR pass-through;
- flight dumps default to an output/ directory, not the cwd.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference import ContinuousBatchingPredictor, aot

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GEO = dict(max_batch_size=2, page_size=8, max_seq_len=64,
           enable_prefix_cache=False)
BUCKETS = (8, 16)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))


@pytest.fixture(scope="module")
def built_bundle(model, tmp_path_factory):
    """One engine build shared by the module (building compiles real
    programs — do it once); mutating tests copy it."""
    import jax
    prev_cache = jax.config.jax_compilation_cache_dir
    path = str(tmp_path_factory.mktemp("aot") / "engine")
    was = obs.enabled()
    obs.enabled(True)
    try:
        manifest = aot.build_engine(model, path, prompt_buckets=BUCKETS,
                                    batch_sizes=(1, 2), **GEO)
    finally:
        obs.enabled(was)
        jax.config.update("jax_compilation_cache_dir", prev_cache)
    assert manifest["artifacts"]
    return path


def _copy(built_bundle, tmp_path):
    dst = str(tmp_path / "engine")
    shutil.copytree(built_bundle, dst)
    return dst


def _prompts(rng, lens):
    return [rng.randint(2, 256, (n,)).tolist() for n in lens]


def _ctr(reg, name, **labels):
    m = reg.get(name)
    if not m:
        return 0.0
    return sum(s.value for s in m.samples()
               if all(s.labels.get(k) == v for k, v in labels.items()))


class TestBuildAndWarmStart:
    def test_manifest_contents(self, built_bundle):
        m = json.load(open(os.path.join(built_bundle, "manifest.json")))
        fp = m["fingerprint"]
        import jax
        assert fp["jax"] == jax.__version__
        assert fp["platform"] == jax.default_backend()
        assert m["buckets"]["prompt_buckets"] == list(BUCKETS)
        kinds = {rec["kind"] for rec in m["artifacts"].values()}
        assert {"prefill", "decode", "forward"} <= kinds
        for rec in m["artifacts"].values():
            p = os.path.join(built_bundle, rec["file"])
            assert os.path.getsize(p) > 0
            from paddle_tpu.framework import integrity
            assert integrity.sha256_file(p) == rec["sha256"]

    def test_warm_start_zero_compile_and_parity(self, model,
                                                built_bundle):
        """The tier-1 smoke: warm-load end to end — every serving
        program comes from the bundle (zero fallbacks) and greedy
        output is bitwise-identical to the live-JIT predictor."""
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            pred, eng = aot.warm_start(model, built_bundle,
                                       wire_cache=False)
            rng = np.random.RandomState(3)
            prompts = _prompts(rng, [8, 16])
            warm = pred.generate(prompts, max_new_tokens=4)
            assert eng.stats["misses"] == 0
            assert eng.stats["hits"] > 0
            reg = obs.get_registry()
            assert _ctr(reg, "aot.bucket_misses") == 0
            assert _ctr(reg, "aot.bundle_hits") > 0
            # cold-start SLO gauge recorded, labeled warm
            g = reg.get("serve.cold_start_seconds")
            modes = {s.labels.get("mode") for s in g.samples()}
            assert modes == {"warm"}
        finally:
            obs.enabled(was)
        cold = ContinuousBatchingPredictor(model, **GEO).generate(
            prompts, max_new_tokens=4)
        assert warm == cold

    def test_cold_start_gauge_set_once_mode_cold(self, model):
        """A predictor that compiles live records
        serve.cold_start_seconds{mode="cold"} at its first token and
        never again (the warm twin is asserted by
        test_warm_start_zero_compile_and_parity)."""
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            cb = ContinuousBatchingPredictor(model, **GEO)
            rng = np.random.RandomState(5)
            cb.generate(_prompts(rng, [8]), max_new_tokens=2)
            g = obs.get_registry().get("serve.cold_start_seconds")
            first = [(s.labels.get("mode"), s.value)
                     for s in g.samples()]
            cb.generate(_prompts(rng, [16]), max_new_tokens=2)
            again = [(s.labels.get("mode"), s.value)
                     for s in g.samples()]
        finally:
            obs.enabled(was)
        assert [m for m, _ in first] == ["cold"]
        assert first[0][1] > 0
        assert again == first

    def test_captured_forward_parity_vs_eager(self, model,
                                              built_bundle):
        """The dy2static capture surface itself: the serialized
        `forward` program's logits match the eager model's."""
        from paddle_tpu._grad_mode import no_grad
        eng = aot.load_engine(built_bundle, model=model,
                              wire_cache=False)
        fwd = eng.program(("forward", (1, 8)))
        assert fwd is not None
        ids = np.random.RandomState(0).randint(
            2, 256, (1, 8)).astype(np.int32)
        p_vals = [p._value for _, p in model.named_parameters()]
        b_vals = [b._value for _, b in model.named_buffers()]
        got = np.asarray(fwd(p_vals, b_vals, ids))
        with no_grad():
            out = model(paddle.to_tensor(ids))
        want = np.asarray(
            (out[0] if isinstance(out, tuple) else out).numpy())
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_to_static_model_builds_and_serves(self, tmp_path):
        """A model whose forward went through the to_static/dy2static
        front door builds an engine and warm-serves with parity."""
        paddle.seed(1)
        m2 = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
        rng = np.random.RandomState(9)
        prompts = _prompts(rng, [8])
        base = ContinuousBatchingPredictor(m2, **GEO).generate(
            prompts, max_new_tokens=3)
        m2.forward = paddle.jit.to_static(m2.forward)
        path = str(tmp_path / "e")
        aot.build_engine(m2, path, prompt_buckets=(8,),
                         batch_sizes=(1,), capture_forward=False,
                         wire_cache=False, **GEO)
        pred, eng = aot.warm_start(m2, path, wire_cache=False)
        out = pred.generate(prompts, max_new_tokens=3)
        assert eng.stats["misses"] == 0
        assert out == base


class TestFallbackAndInvalidation:
    def test_bucket_miss_falls_back_and_writes_back(self, model,
                                                    built_bundle,
                                                    tmp_path):
        path = _copy(built_bundle, tmp_path)
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            pred, eng = aot.warm_start(model, path, wire_cache=False)
            rng = np.random.RandomState(4)
            out = pred.generate(_prompts(rng, [32]), max_new_tokens=2)
            assert len(out[0]) == 2
            assert eng.stats["misses"] >= 1
            assert eng.stats["write_backs"] >= 1
            assert _ctr(obs.get_registry(), "aot.bucket_misses") >= 1
            # written back: a reload serves the same shape from tier 1
            m = json.load(open(os.path.join(path, "manifest.json")))
            assert any("(1, 32)" in k for k in m["artifacts"])
            eng2 = aot.load_engine(path, model=model, wire_cache=False)
            pred2 = ContinuousBatchingPredictor(model, engine=eng2,
                                                **GEO)
            out2 = pred2.generate(_prompts(rng, [32]),
                                  max_new_tokens=2)
            assert len(out2[0]) == 2
            assert eng2.stats["misses"] == 0
        finally:
            obs.enabled(was)

    def test_corrupt_artifact_rejected_then_self_heals(
            self, model, built_bundle, tmp_path):
        """Digest mismatch: the artifact NEVER executes — it is
        rejected, counted in aot.invalidations, and the predictor
        falls back to a live-JIT build of that program (which then
        repairs the bundle via write-back)."""
        path = _copy(built_bundle, tmp_path)
        m = json.load(open(os.path.join(path, "manifest.json")))
        victim = next(k for k, r in m["artifacts"].items()
                      if r["kind"] == "decode")
        f = os.path.join(path, m["artifacts"][victim]["file"])
        blob = open(f, "rb").read()
        open(f, "wb").write(blob[:-8] + b"deadbeef")
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            pred, eng = aot.warm_start(model, path, wire_cache=False)
            rng = np.random.RandomState(5)
            out = pred.generate(_prompts(rng, [8]), max_new_tokens=3)
            assert len(out[0]) == 3
            reg = obs.get_registry()
            assert _ctr(reg, "aot.invalidations", reason="digest") >= 1
            assert eng.stats["misses"] >= 1      # decode fell back
            # self-healed: the rewritten artifact verifies again
            m2 = json.load(open(os.path.join(path, "manifest.json")))
            from paddle_tpu.framework import integrity
            rec = m2["artifacts"][victim]
            assert integrity.sha256_file(
                os.path.join(path, rec["file"])) == rec["sha256"]
        finally:
            obs.enabled(was)

    def test_fingerprint_mismatch_invalidates_and_rebuilds(
            self, model, built_bundle, tmp_path):
        path = _copy(built_bundle, tmp_path)
        mp = os.path.join(path, "manifest.json")
        m = json.load(open(mp))
        m["fingerprint"]["jaxlib"] = "0.0.1-other"
        json.dump(m, open(mp, "w"))
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            # strict load: rejected outright
            with pytest.raises(aot.BundleInvalid) as ei:
                aot.load_engine(path, model=model, wire_cache=False)
            assert ei.value.reason == "fingerprint"
            # warm_start: counted + clean rebuild, live-JIT serve works
            pred, eng = aot.warm_start(model, path, wire_cache=False)
            reg = obs.get_registry()
            assert _ctr(reg, "aot.invalidations",
                        reason="fingerprint") >= 1
            m2 = json.load(open(mp))
            assert m2["artifacts"] == {}          # stale execs dropped
            assert not eng.warm
            rng = np.random.RandomState(6)
            out = pred.generate(_prompts(rng, [8]), max_new_tokens=2)
            assert len(out[0]) == 2
            # cold-start gauge says cold: nothing came from the bundle
            g = reg.get("serve.cold_start_seconds")
            assert {s.labels.get("mode") for s in g.samples()} \
                == {"cold"}
        finally:
            obs.enabled(was)

    def test_model_hash_mismatch_rejected(self, built_bundle):
        paddle.seed(2)
        other = LlamaForCausalLM(LlamaConfig.tiny(
            num_hidden_layers=1, tensor_parallel=False))
        with pytest.raises(aot.BundleInvalid) as ei:
            aot.load_engine(built_bundle, model=other, wire_cache=False)
        assert ei.value.reason == "model"

    def test_geometry_override_mismatch_resets(self, model,
                                               built_bundle, tmp_path):
        path = _copy(built_bundle, tmp_path)
        was = obs.enabled()
        obs.enabled(True)
        try:
            obs.get_registry().reset()
            pred, eng = aot.warm_start(model, path, wire_cache=False,
                                       page_size=16)   # bundle has 8
            reg = obs.get_registry()
            assert _ctr(reg, "aot.invalidations",
                        reason="geometry") >= 1
            assert json.load(open(os.path.join(
                path, "manifest.json")))["artifacts"] == {}
            assert pred.page == 16
        finally:
            obs.enabled(was)


class TestTier2Cache:
    def test_wire_fences_and_obeys_the_environment(self, tmp_path,
                                                   monkeypatch):
        import jax
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        prev = jax.config.jax_compilation_cache_dir
        cache = str(tmp_path / "xc")
        try:
            got = aot.wire_xla_cache(cache)
            assert jax.config.jax_compilation_cache_dir == got
            fp = json.load(open(os.path.join(cache,
                                             "cache_fingerprint.json")))
            assert fp == aot.runtime_fingerprint()
            # stale fingerprint -> wiped + invalidation counted
            json.dump({"jaxlib": "stale"},
                      open(os.path.join(cache,
                                        "cache_fingerprint.json"), "w"))
            marker = os.path.join(cache, "stale_entry")
            open(marker, "w").write("x")
            was = obs.enabled()
            obs.enabled(True)
            try:
                obs.get_registry().reset()
                aot.wire_xla_cache(cache)
                assert not os.path.exists(marker)
                assert _ctr(obs.get_registry(), "aot.invalidations",
                            tier="xla_cache") >= 1
            finally:
                obs.enabled(was)
            # where the environment places the cache, a bundle does
            # not move it
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
            other = str(tmp_path / "other")
            assert aot.wire_xla_cache(other) == got
            assert jax.config.jax_compilation_cache_dir == got
            assert not os.path.exists(other)
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
            from paddle_tpu.inference.aot.engine import _reset_cache_object
            _reset_cache_object()


class TestToolingAndSatellites:
    def test_aot_report_runs_without_jax(self, built_bundle, tmp_path):
        """tools/aot_report.py must work on a jax-less box: run it with
        jax import poisoned; it must still print the manifest."""
        poison = tmp_path / "poison"
        poison.mkdir()
        (poison / "jax.py").write_text(
            "raise ImportError('jax must not be imported')\n")
        env = dict(os.environ, PYTHONPATH=str(poison))
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "aot_report.py"),
             built_bundle, "--verify"],
            capture_output=True, text=True, env=env, timeout=60)
        assert out.returncode == 0, out.stderr
        assert "prefill" in out.stdout and "decode" in out.stdout
        assert "verify    OK" in out.stdout
        # --json view parses and carries the fingerprint
        out2 = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "aot_report.py"),
             built_bundle, "--json"],
            capture_output=True, text=True, env=env, timeout=60)
        rec = json.loads(out2.stdout)
        assert rec["fingerprint"]["platform"] == "cpu"

    def test_aot_report_flags_corruption(self, built_bundle, tmp_path):
        path = _copy(built_bundle, tmp_path)
        m = json.load(open(os.path.join(path, "manifest.json")))
        f = os.path.join(path,
                         next(iter(m["artifacts"].values()))["file"])
        open(f, "ab").write(b"tail")
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "aot_report.py"),
             path, "--verify"],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 1
        assert "digest mismatch" in out.stderr

    def test_checkpointer_uses_shared_integrity(self):
        from paddle_tpu.framework import integrity
        from paddle_tpu.distributed import checkpoint as ckpt
        assert ckpt._sha256_file is integrity.sha256_file

    def test_integrity_atomic_helpers(self, tmp_path):
        from paddle_tpu.framework import integrity
        p = str(tmp_path / "a" / "blob.bin")
        digest = integrity.atomic_write_bytes(p, b"payload")
        assert integrity.sha256_file(p) == digest
        assert not [n for n in os.listdir(os.path.dirname(p))
                    if n.startswith(".tmp")]
        # sweep only touches THIS pid's temps
        d = str(tmp_path / "a")
        own = os.path.join(d, f".tmp-x-{os.getpid()}")
        foreign = os.path.join(d, ".tmp-x-999999")
        open(own, "w").write("o")
        open(foreign, "w").write("f")
        integrity.sweep_tmp(d)
        assert not os.path.exists(own)
        assert os.path.exists(foreign)

    def test_launcher_engine_dir_passthrough(self, tmp_path):
        from paddle_tpu.distributed.launch.main import (parse_args,
                                                        PodController)
        eng = str(tmp_path / "engine")
        ctx = parse_args(["--nproc_per_node", "1", "--engine_dir", eng,
                          "train.py"])
        assert ctx.engine_dir == eng
        env = PodController(ctx)._rank_env(0, restart_epoch=3)
        assert env["PADDLE_TPU_ENGINE_DIR"] == os.path.abspath(eng)
        # default comes from the caller's environment
        os.environ["PADDLE_TPU_ENGINE_DIR"] = eng
        try:
            ctx2 = parse_args(["train.py"])
            assert ctx2.engine_dir == eng
            assert aot.default_engine_dir() == eng
        finally:
            os.environ.pop("PADDLE_TPU_ENGINE_DIR", None)

    def test_flight_dir_defaults_to_output(self, tmp_path,
                                           monkeypatch):
        from paddle_tpu.observability import tracing
        monkeypatch.chdir(tmp_path)
        monkeypatch.delenv("PADDLE_TPU_FLIGHT_DIR", raising=False)
        prev = tracing._flight_dir
        tracing.set_flight_dir(None)
        try:
            from paddle_tpu.observability import runtime as obs_rt
            if obs_rt.telemetry_path():
                pytest.skip("telemetry sink configured; its dir wins")
            assert tracing.flight_dir() == str(tmp_path / "output")
            was = obs.enabled()
            obs.enabled(True)
            try:
                with tracing.span("t.flight_default"):
                    pass
                dump = tracing.flight_dump(reason="test", force=True)
            finally:
                obs.enabled(was)
            assert dump is not None
            assert os.path.dirname(dump) == str(tmp_path / "output")
            # no stray dump in the cwd itself
            assert not [n for n in os.listdir(tmp_path)
                        if n.startswith("flight_")]
        finally:
            tracing.set_flight_dir(prev)

@pytest.mark.slow
class TestFreshProcess:
    def test_warm_start_in_fresh_process(self, model, built_bundle,
                                         tmp_path):
        """The real restart story: a NEW interpreter warm-starts from
        the bundle and serves with zero fallbacks."""
        sd = {k: np.asarray(v.numpy())
              for k, v in model.state_dict().items()}
        np.savez(str(tmp_path / "w.npz"), **sd)
        script = tmp_path / "warm.py"
        script.write_text(f"""
import os, json, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax; jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {REPO!r})
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference import aot
paddle.seed(0)
model = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
w = np.load({str(tmp_path / 'w.npz')!r})
model.set_state_dict({{k: paddle.to_tensor(w[k]) for k in w.files}})
pred, eng = aot.warm_start(model, {built_bundle!r}, wire_cache=False)
out = pred.generate([list(range(2, 10))], max_new_tokens=3)
print(json.dumps({{"out": out, "stats": eng.stats}}))
""")
        r = subprocess.run([sys.executable, str(script)],
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        rec = json.loads(r.stdout.strip().splitlines()[-1])
        assert rec["stats"]["misses"] == 0
        assert rec["stats"]["hits"] > 0
        want = ContinuousBatchingPredictor(model, **GEO).generate(
            [list(range(2, 10))], max_new_tokens=3)
        assert rec["out"] == want

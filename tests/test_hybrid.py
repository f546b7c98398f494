"""Hybrid-parallel engine tests (ISSUE 13): ZeRO-2/3 parity and
footprints, TP parity, the explicit 1F1B schedule, bucketed-comm
overlap, the topology-fingerprinted AOT bundle, and the narrowed
shard_map-shim skip contract.

Runs on the 8-virtual-CPU-device mesh from conftest.py.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.observability as obs
from paddle_tpu.distributed.mesh import build_mesh, set_mesh
from paddle_tpu.distributed.fleet.dist_step import DistTrainStep
from paddle_tpu.distributed.fleet.hybrid import (
    HybridParallelPlan, HybridTrainStep, parse_mesh_spec,
    overlapped_all_reduce, overlapped_reduce_scatter,
    prefetch_all_gather)


def _mlp(seed=0, d=16, h=64):
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(d, h), nn.Tanh(), nn.Linear(h, d))


_LOSS = lambda o, t: ((o - t) ** 2).mean()


def _tool(name):
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        f"_hybrid_{name}", os.path.join(root, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ===========================================================================
# plan
# ===========================================================================
class TestPlan:
    def test_parse_spec_aliases_and_errors(self):
        assert parse_mesh_spec("data=4,model=2") == {"data": 4,
                                                     "model": 2}
        assert parse_mesh_spec("dp=2, tp=2, pp=2") == {
            "data": 2, "model": 2, "stage": 2}
        with pytest.raises(ValueError, match="unknown mesh axis"):
            parse_mesh_spec("foo=2")
        with pytest.raises(ValueError, match="axis=degree"):
            parse_mesh_spec("data:2")
        with pytest.raises(ValueError, match="duplicate"):
            parse_mesh_spec("dp=2,data=4")

    def test_topology_canonical_and_fingerprint(self):
        p = HybridParallelPlan.from_spec("model=2,data=4", zero_stage=3)
        # canonical order is mesh order (data before model), degree-1
        # axes omitted
        assert p.topology() == "data=4,model=2"
        assert p.world_size() == 8
        fp = p.fingerprint()
        assert fp["topology"] == "data=4,model=2"
        assert fp["zero_stage"] == 3
        p1 = HybridParallelPlan.from_spec("", zero_stage=0)
        assert p1.topology() == "replicated"
        with pytest.raises(ValueError, match="zero_stage"):
            HybridParallelPlan(degrees={}, zero_stage=7)
        with pytest.raises(ValueError, match="schedule"):
            HybridParallelPlan(degrees={}, schedule="zigzag")

    def test_inferred_degree_resolves_before_fingerprint(self):
        """A -1 (inferred) degree must NEVER fingerprint: unresolved
        plans refuse topology()/fingerprint()/world_size(), build_mesh
        adopts the real sizes, and an explicit mesh that contradicts a
        pinned degree is rejected (review finding: two hosts inferring
        different data degrees used to collide on one topology
        string)."""
        p = HybridParallelPlan.from_spec("data=-1,model=2",
                                         zero_stage=3)
        with pytest.raises(ValueError, match="unresolved"):
            p.topology()
        with pytest.raises(ValueError, match="unresolved"):
            p.fingerprint()
        with pytest.raises(ValueError, match="unresolved"):
            p.world_size()
        p.build_mesh()
        assert p.topology() == "data=4,model=2"
        assert p.world_size() == 8
        # pinned degree contradicting an explicit mesh is a caller bug
        p2 = HybridParallelPlan.from_spec("data=4", zero_stage=0)
        other = build_mesh(dp=8)
        with pytest.raises(ValueError, match="does not match"):
            p2.adopt_mesh(other)
        with pytest.raises(ValueError, match="at most one"):
            HybridParallelPlan.from_spec("data=-1,model=-1")
        # 0 / negative degrees are spec-level errors, not a
        # ZeroDivisionError deep inside build_mesh (review finding)
        with pytest.raises(ValueError, match=">= 1"):
            HybridParallelPlan.from_spec("data=0")
        with pytest.raises(ValueError, match=">= 1"):
            HybridParallelPlan.from_spec("data=-2")

    def test_zero_stage_defaults_from_runtime_config(self):
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        rc = RuntimeConfig(zero_stage=2)
        p = HybridParallelPlan.from_spec("data=2", runtime_config=rc)
        assert p.zero_stage == 2
        with pytest.raises(ValueError, match="zero_stage"):
            RuntimeConfig(zero_stage=5)


# ===========================================================================
# ZeRO stages: parity + footprints
# ===========================================================================
class TestZeroStages:
    def _run(self, stage, accum=1, steps=4, micro=None):
        d = 16
        rng = np.random.RandomState(0)
        x = rng.randn(8, d).astype(np.float32)
        y = rng.randn(8, d).astype(np.float32)
        mesh = build_mesh(dp=4)
        set_mesh(mesh)
        try:
            m = _mlp()
            opt = paddle.optimizer.AdamW(1e-2,
                                         parameters=m.parameters())
            st = DistTrainStep(m, opt, _LOSS, sharding_stage=stage,
                               mesh=mesh, grad_accum_steps=accum)
            losses = []
            for _ in range(steps):
                if accum > 1:
                    for k in range(accum):
                        sl = slice(k * 8 // accum, (k + 1) * 8 // accum)
                        l = st(paddle.to_tensor(x[sl]),
                               paddle.to_tensor(y[sl]))
                    losses.append(float(l))
                else:
                    losses.append(float(st(paddle.to_tensor(x),
                                           paddle.to_tensor(y))))
            w = {k: np.array(v.numpy())
                 for k, v in m.state_dict().items()}
            return losses, w, st
        finally:
            set_mesh(None)

    def test_zero_123_loss_parity_vs_stage0(self):
        """Sharding is a layout decision: stages 1 and 3 must walk the
        stage-0 loss curve."""
        l0, w0, _ = self._run(0)
        l1, w1, _ = self._run(1)
        l3, w3, s3 = self._run(3)
        np.testing.assert_allclose(l1, l0, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(l3, l0, rtol=2e-4, atol=2e-5)
        for k in w0:
            np.testing.assert_allclose(w1[k], w0[k], rtol=2e-4,
                                       atol=2e-5)
            np.testing.assert_allclose(w3[k], w0[k], rtol=2e-4,
                                       atol=2e-5)
        # ZeRO-3: params actually sharded — per-replica footprint drops
        # by the data-axis size (the mem.params_bytes{scope} signal)
        fp = s3._params_bytes
        assert fp["per_replica"] <= fp["global"] // 2

    def test_zero2_accum_matches_zero1_full_batch(self):
        """ZeRO-2 with grad_accum_steps=2 over half-batches must land
        on the same params as ZeRO-1 full-batch stepping (accumulated
        grads averaged == full-batch mean grad), with the persistent
        accumulators 'data'-sharded."""
        _, w1, _ = self._run(1, steps=3)
        _, w2, s2 = self._run(2, accum=2, steps=3)
        for k in w1:
            np.testing.assert_allclose(w2[k], w1[k], rtol=2e-4,
                                       atol=2e-5)
        gb = s2._grad_bytes
        assert gb["per_replica"] <= gb["global"] // 2, gb
        # the flat accumulators really carry a 'data' spec
        specs = [str(getattr(g.sharding, "spec", ""))
                 for g in s2._grad_state["fused"]]
        assert any("data" in s for s in specs), specs
        # accum comm accounting: the micro-step view excludes the
        # boundary-only param all-gather (review finding: micro-steps
        # used to charge the apply program's gather every call)
        class FakeObs:
            comm_per_step = None
        obs_ = FakeObs()
        arrs = [np.zeros((2, 16), np.float32)] * 2
        s2._refresh_comm_accounting(obs_, "s", arrs, boundary=False)
        micro_ops = [e[0] for e in obs_.comm_per_step]
        s2._refresh_comm_accounting(obs_, "s", arrs, boundary=True)
        full_ops = [e[0] for e in obs_.comm_per_step]
        assert "all_gather" not in micro_ops
        assert "all_gather" in full_ops

    def test_zero2_requires_no_scaler(self):
        mesh = build_mesh(dp=2)
        set_mesh(mesh)
        try:
            m = _mlp()
            opt = paddle.optimizer.AdamW(1e-2,
                                         parameters=m.parameters())
            scaler = paddle.amp.GradScaler(init_loss_scaling=2.0 ** 10)
            with pytest.raises(NotImplementedError, match="GradScaler"):
                DistTrainStep(m, opt, _LOSS, sharding_stage=2,
                              mesh=mesh, grad_accum_steps=2,
                              scaler=scaler)
        finally:
            set_mesh(None)


# ===========================================================================
# GradBucketer pad_multiple regression (uneven reduce-scatter shards)
# ===========================================================================
class TestPadMultiple:
    @pytest.mark.parametrize("world", [3, 4, 5, 8])
    def test_padded_size_divisible_and_roundtrip(self, world):
        from paddle_tpu.distributed.collective import GradBucketer
        # sizes chosen so no bucket lands on a multiple of `world`
        shapes = [(7,), (13, 3), (1,), (257,)]
        dtypes = [np.float32] * len(shapes)
        b = GradBucketer(shapes, dtypes, bucket_bytes=1 << 10,
                         pad_multiple=world)
        assert b.buckets
        for bk in b.buckets:
            assert bk.padded_size % world == 0, (world, bk.size,
                                                 bk.padded_size)
            assert bk.padded_size >= bk.size
        arrays = [jnp.asarray(np.random.RandomState(i).randn(*s)
                              .astype(np.float32))
                  for i, s in enumerate(shapes)]
        flats = b.flatten(arrays)
        for bk, f in zip(b.buckets, flats):
            assert f.shape == (bk.padded_size,)
            # padding is ZERO: reduce-scatter shards and global-norm
            # clipping both depend on it
            pad = np.asarray(f)[bk.size:]
            assert not pad.any()
        back = b.unflatten(flats)
        for a, r in zip(arrays, back):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


# ===========================================================================
# TP parity
# ===========================================================================
class TestTensorParallel:
    def test_tp_llama_logits_and_loss_parity(self):
        """TP llama on a model=2 mesh == the unsharded model with the
        same seed: logits (eager, constraints active) and the first
        compiled train-step loss must match."""
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       LlamaPretrainingCriterion)
        rng = np.random.RandomState(0)
        ids = rng.randint(1, 256, (2, 16))
        crit = LlamaPretrainingCriterion(LlamaConfig.tiny())
        loss_fn = lambda lg, lb: crit(lg, lb)

        paddle.seed(0)
        ref = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
        ref.eval()
        ref_logits = np.asarray(ref(paddle.to_tensor(ids)).numpy())

        mesh = build_mesh(mp=2)
        set_mesh(mesh)
        try:
            paddle.seed(0)
            tp = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=True))
            tp.eval()
            tp_logits = np.asarray(tp(paddle.to_tensor(ids)).numpy())
            np.testing.assert_allclose(tp_logits, ref_logits,
                                       rtol=2e-4, atol=2e-4)
            tp.train()
            opt = paddle.optimizer.AdamW(1e-3,
                                         parameters=tp.parameters())
            step = DistTrainStep(tp, opt, loss_fn, mesh=mesh)
            l_tp = float(step(paddle.to_tensor(ids),
                              paddle.to_tensor(ids)))
        finally:
            set_mesh(None)
        ref.train()
        ref_loss = float(loss_fn(ref(paddle.to_tensor(ids)),
                                 paddle.to_tensor(ids)))
        np.testing.assert_allclose(l_tp, ref_loss, rtol=2e-4)

    def test_model_axis_comm_scales_with_tokens_per_sig(self):
        """The analytic model-axis entries are per batch signature and
        the per-call refresh swaps them (review finding: the accounting
        used to stick to whichever signature compiled last)."""
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       LlamaPretrainingCriterion)
        crit = LlamaPretrainingCriterion(LlamaConfig.tiny())
        mesh = build_mesh(dp=4, mp=2)
        set_mesh(mesh)
        try:
            paddle.seed(0)
            m = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=True))
            opt = paddle.optimizer.AdamW(1e-3,
                                         parameters=m.parameters())
            step = DistTrainStep(m, opt, lambda lg, lb: crit(lg, lb),
                                 mesh=mesh)
            a16 = [jnp.zeros((4, 16), jnp.int32)] * 2
            a32 = [jnp.zeros((4, 32), jnp.int32)] * 2
            e16 = step._model_axis_comm(a16)
            e32 = step._model_axis_comm(a32)
            assert e16 and e32
            # activation payloads scale with the token count
            assert e32[0][3] == 2 * e16[0][3]

            class FakeObs:
                comm_per_step = None
            obs = FakeObs()
            step._refresh_comm_accounting(obs, "sig16", a16)
            first = obs.comm_per_step
            step._refresh_comm_accounting(obs, "sig32", a32)
            assert obs.comm_per_step != first
            step._refresh_comm_accounting(obs, "sig16", a16)
            assert obs.comm_per_step is first  # cached per signature
        finally:
            set_mesh(None)


# ===========================================================================
# explicit 1F1B
# ===========================================================================
class TestExplicit1F1B:
    def test_schedule_bitwise_output_and_grad_parity(self):
        """The explicit schedule's per-microbatch outputs must be
        BITWISE the naive sequential stage composition (same body, same
        inputs, masked selects only route them), and the in-schedule
        gradients must match jax.grad of the naive mean loss."""
        from paddle_tpu.distributed.fleet.meta_parallel.\
            pipeline_parallel import pipeline_1f1b
        S, M, Bm, d = 4, 6, 2, 8
        rng = np.random.RandomState(0)
        w = jnp.asarray(rng.randn(S, d, d).astype(np.float32) * 0.3)
        bb = jnp.asarray(rng.randn(S, d).astype(np.float32) * 0.1)
        xm = jnp.asarray(rng.randn(M, Bm, d).astype(np.float32))
        wh = jnp.asarray(rng.randn(d, d).astype(np.float32) * 0.2)
        tgt = jnp.asarray(rng.randn(M, Bm, d).astype(np.float32))

        def body(p, x, key):
            return jnp.tanh(x @ p[0] + p[1])

        def head(pv, y, lbl, key):
            return jnp.mean((y @ pv[0] - lbl) ** 2)

        mesh = build_mesh(pp=S)
        losses, out, dx, g_stk, g_post = pipeline_1f1b(
            body, [w, bb], xm, head, tgt, [wh], num_stages=S,
            mesh=mesh)

        def ref(params, post, x):
            total = 0.0
            outs = []
            for m in range(M):
                y = x[m]
                for s in range(S):
                    y = jnp.tanh(y @ params[0][s] + params[1][s])
                outs.append(y)
                total = total + jnp.mean((y @ post[0] - tgt[m]) ** 2)
            return total / M, jnp.stack(outs)

        lval, ref_out = ref([w, bb], [wh], xm)
        # bitwise: each stage's body runs once on identical values
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(ref_out))
        np.testing.assert_allclose(float(jnp.mean(losses)), float(lval),
                                   rtol=1e-6)
        gp, gh = jax.grad(lambda p, q: ref(p, q, xm)[0],
                          argnums=(0, 1))([w, bb], [wh])
        gx = jax.grad(lambda x: ref([w, bb], [wh], x)[0])(xm)
        np.testing.assert_allclose(np.asarray(g_stk[0]),
                                   np.asarray(gp[0]), rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(g_stk[1]),
                                   np.asarray(gp[1]), rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(g_post[0]),
                                   np.asarray(gh[0]), rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(gx),
                                   rtol=1e-4, atol=1e-6)

    def test_train_step_parity_and_bubble_telemetry(self, tmp_path):
        """PipelineTrainStep(schedule_mode='1F1B-explicit') must walk
        the single-device loss curve, and the analytic bubble fraction
        must land in the JSONL sink with the right value."""
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineLayer)
        from paddle_tpu.distributed.fleet.meta_parallel.\
            pipeline_parallel import (PipelineTrainStep,
                                      one_f_one_b_bubble_fraction)
        from paddle_tpu.jit import TrainStep

        class Block(nn.Layer):
            def __init__(self, d):
                super().__init__()
                self.fc1 = nn.Linear(d, 2 * d)
                self.fc2 = nn.Linear(2 * d, d)

            def forward(self, x):
                return x + self.fc2(nn.functional.gelu(self.fc1(x)))

        class Edge(nn.Layer):
            def __init__(self, d):
                super().__init__()
                self.proj = nn.Linear(d, d)

            def forward(self, x):
                return self.proj(x)

        d, B, steps, mb, S = 16, 8, 4, 4, 2

        def make(stages):
            paddle.seed(42)
            return PipelineLayer(
                [Edge(d)] + [Block(d) for _ in range(4)] + [Edge(d)],
                num_stages=stages)

        rng = np.random.RandomState(3)
        x = rng.randn(B, d).astype(np.float32)
        y = rng.randn(B, d).astype(np.float32)

        ref = make(1)
        ropt = paddle.optimizer.AdamW(1e-2, parameters=ref.parameters())
        rstep = TrainStep(ref, ropt, _LOSS)
        ref_losses = [float(rstep(paddle.to_tensor(x),
                                  paddle.to_tensor(y)))
                      for _ in range(steps)]

        path = str(tmp_path / "t.jsonl")
        was = obs.enabled()
        obs.enabled(True)
        mesh = build_mesh(pp=S)
        set_mesh(mesh)
        try:
            pm = make(S)
            po = paddle.optimizer.AdamW(1e-2,
                                        parameters=pm.parameters())
            ps = PipelineTrainStep(pm, po, _LOSS, num_microbatches=mb,
                                   mesh=mesh,
                                   schedule_mode="1F1B-explicit")
            losses = [float(ps(paddle.to_tensor(x),
                               paddle.to_tensor(y)))
                      for _ in range(steps)]
            with obs.JsonlExporter(path) as sink:
                sink.export()
        finally:
            set_mesh(None)
            obs.enabled(was)
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-4,
                                   atol=2e-5)
        want = one_f_one_b_bubble_fraction(S, mb)
        assert want == pytest.approx(2 * (S - 1) / (mb + 2 * (S - 1)))
        recs = [json.loads(l) for l in open(path) if l.strip()]
        bub = [r for r in recs
               if r.get("name") == "train.pp.bubble_fraction"]
        assert bub, "bubble gauge missing from the sink"
        assert bub[-1]["value"] == pytest.approx(want)
        assert bub[-1]["labels"]["schedule"] == "1F1B-explicit"

    def test_explicit_mode_rejections(self):
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineLayer)
        from paddle_tpu.distributed.fleet.meta_parallel.\
            pipeline_parallel import PipelineTrainStep

        class Edge(nn.Layer):
            def __init__(self, d=8):
                super().__init__()
                self.proj = nn.Linear(d, d)

            def forward(self, x):
                return self.proj(x)

        mesh = build_mesh(pp=2)
        set_mesh(mesh)
        try:
            paddle.seed(0)
            m = PipelineLayer([Edge() for _ in range(4)], num_stages=2)
            opt = paddle.optimizer.AdamW(
                1e-2, parameters=m.parameters())
            with pytest.raises(ValueError, match="implies"):
                PipelineTrainStep(m, opt, _LOSS, num_microbatches=2,
                                  mesh=mesh,
                                  schedule_mode="1F1B-explicit",
                                  num_virtual_stages=2)
            scaler = paddle.amp.GradScaler(init_loss_scaling=8.0)
            with pytest.raises(NotImplementedError, match="GradScaler"):
                PipelineTrainStep(m, opt, _LOSS, num_microbatches=2,
                                  mesh=mesh, scaler=scaler,
                                  schedule_mode="1F1B-explicit")
        finally:
            set_mesh(None)


# ===========================================================================
# overlap: per-bucket collectives in manual SPMD regions
# ===========================================================================
class TestOverlap:
    def _spmd_run(self, fn, *arrays):
        """Run fn under full-manual shard_map over 'data' with the
        facade bound (the explicit-collective regime)."""
        from paddle_tpu.framework.jax_compat import shard_map
        from paddle_tpu.distributed import collective as C
        mesh = build_mesh(dp=4)
        from jax.sharding import PartitionSpec as P
        set_mesh(mesh)
        try:
            def wrapped(*xs):
                with C.spmd_region({"data": "data"}):
                    return fn(*xs)
            run = shard_map(wrapped, mesh=mesh,
                            in_specs=tuple(P("data") for _ in arrays),
                            out_specs=P("data"))
            return np.asarray(run(*arrays))
        finally:
            set_mesh(None)

    def test_bucketed_all_reduce_matches_monolithic(self):
        from paddle_tpu.distributed.collective import bucketer_for
        was = obs.enabled()
        obs.enabled(True)
        reg = obs.get_registry()

        def calls():
            return sum(s.value
                       for s in reg.counter("comm.calls").samples()
                       if s.labels.get("op") == "all_reduce"
                       and s.labels.get("axis") == "data")

        rng = np.random.RandomState(0)
        grads = [rng.randn(4, 37).astype(np.float32),
                 rng.randn(4, 64).astype(np.float32),
                 rng.randn(4, 5).astype(np.float32)]
        b = bucketer_for([(37,), (64,), (5,)], [np.float32] * 3,
                         bucket_bytes=64 * 4, pad_multiple=4)
        assert len(b.buckets) >= 2

        def sync2(*gs):
            flats = b.flatten([g[0] for g in gs])
            red, _ = overlapped_all_reduce(flats)
            back = b.unflatten(red)
            return jnp.concatenate([r.ravel() for r in back])[None, :]

        c0 = calls()
        try:
            out = self._spmd_run(sync2, *grads)
        finally:
            obs.enabled(was)
        # parity: sum over the 4 shards
        want = np.concatenate([g.sum(0).ravel() for g in grads])
        np.testing.assert_allclose(out.reshape(4, -1)[0], want,
                                   rtol=1e-5, atol=1e-5)
        # one collective PER BUCKET traced (the overlap structure)
        assert calls() - c0 == len(b.buckets)

    def test_bucketed_reduce_scatter_gather_roundtrip(self):
        from paddle_tpu.distributed.collective import bucketer_for
        rng = np.random.RandomState(1)
        grads = [rng.randn(4, 32).astype(np.float32),
                 rng.randn(4, 17).astype(np.float32)]
        b = bucketer_for([(32,), (17,)], [np.float32] * 2,
                         bucket_bytes=32 * 4, pad_multiple=4)

        def sync(*gs):
            flats = b.flatten([g[0] for g in gs])
            shards = overlapped_reduce_scatter(flats)
            full = prefetch_all_gather(shards)
            return jnp.concatenate([f.ravel() for f in full])[None, :]

        out = self._spmd_run(sync, *grads)
        want = np.concatenate(
            [np.pad(g.sum(0).ravel(),
                    (0, bk.padded_size - bk.size))
             for g, bk in zip(grads, b.buckets)])
        np.testing.assert_allclose(out.reshape(4, -1)[0], want,
                                   rtol=1e-5, atol=1e-5)

    def test_quantized_bucket_sync_error_feedback(self):
        """int8 per-bucket sync: quantization error is bounded and the
        residual buffer carries it to the next call."""
        rng = np.random.RandomState(2)
        g = rng.randn(4, 64).astype(np.float32)

        def sync(gv):
            flats = [gv[0]]
            red, res = overlapped_all_reduce(
                flats, quantized=True,
                residuals=[jnp.zeros_like(flats[0])])
            return jnp.stack([red[0], res[0]])[None]

        out = self._spmd_run(sync, g)
        red, res = out.reshape(4, 2, 64)[0]
        want = g.sum(0)
        scale = np.abs(want).max()
        assert np.abs(red - want).max() <= scale * 0.05
        # residual = what the wire dropped (error feedback, non-zero)
        assert np.abs(res).sum() > 0


# ===========================================================================
# shard_map adapter: partial-manual regions on the installed jax
# ===========================================================================
class TestShardMapShim:
    def test_partial_manual_region_runs(self):
        """Manual over 'stage' only, auto over 'data': the region a
        hybrid pp x dp mesh needs is simply available on jax 0.9.0."""
        from paddle_tpu.framework.jax_compat import shard_map
        from jax.sharding import PartitionSpec as P
        mesh = build_mesh(dp=2, pp=2)
        x = jnp.arange(8.0).reshape(2, 4)
        out = jax.jit(shard_map(
            lambda a: a * (1.0 + jax.lax.axis_index("stage")),
            mesh=mesh, in_specs=(P("stage"),), out_specs=P("stage"),
            axis_names={"stage"}))(x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(x) * np.array([[1.0], [2.0]]))

    def test_pipeline_on_hybrid_mesh_matches_sequential(self):
        """pipeline_spmd on a pp=2 x dp=2 mesh (a partial-manual
        shard_map) computes what the stages compute in sequence."""
        from paddle_tpu.distributed.fleet.meta_parallel.\
            pipeline_parallel import pipeline_spmd
        mesh = build_mesh(dp=2, pp=2)
        rng = np.random.RandomState(0)
        w = jnp.asarray(rng.randn(2, 4, 4), jnp.float32)
        xm = jnp.asarray(rng.randn(2, 4, 4), jnp.float32)
        out = pipeline_spmd(lambda p, x, k: x @ p[0], [w], xm,
                            num_stages=2, mesh=mesh)
        want = np.asarray(xm) @ np.asarray(w[0]) @ np.asarray(w[1])
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5,
                                   atol=1e-5)


# ===========================================================================
# autotune: per-axis comm split + zero_stage proposals
# ===========================================================================
class TestAutotuneHybrid:
    def _write(self, path, records):
        with open(path, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
        return path

    def _sample(self, ts, name, kind, value, **labels):
        return {"kind": kind, "ts": ts, "name": name, "value": value,
                "labels": labels}

    def test_comm_proposals_split_per_axis(self, tmp_path):
        at = _tool("autotune")
        recs = [
            self._sample(1.0, "train.steps", "counter", 20),
            # heavy data-axis grad traffic + model-axis activation
            # all-reduces that must NOT inflate the bucket target
            self._sample(1.0, "comm.bytes", "counter", 20 * (2 << 30),
                         op="reduce_scatter", axis="data"),
            self._sample(1.0, "comm.calls", "counter", 20 * 512,
                         op="reduce_scatter", axis="data"),
            self._sample(1.0, "comm.bytes", "counter", 20 * (1 << 30),
                         op="all_reduce", axis="model"),
            self._sample(1.0, "comm.calls", "counter", 20 * 8,
                         op="all_reduce", axis="model"),
        ]
        p = self._write(tmp_path / "t.jsonl", recs)
        rep = at.analyze([str(p)])
        by = {x["field"]: x for x in rep["proposals"]}
        gb = by["grad_bucket_bytes"]
        assert gb["evidence"]["axis"] == "data"
        # target derives from the DATA axis only (2GiB/8 -> 256MiB,
        # capped at 2^28); with the model axis folded in it would hit
        # the same cap, so pin the per-axis evidence instead
        assert gb["evidence"]["per_axis_bytes_per_step"] == {
            "data": 2 << 30, "model": 1 << 30}
        assert gb["evidence"]["value"] == 2 << 30
        q8 = by["quantized_grad_comm"]
        assert q8["evidence"]["axis"] == "data"
        assert q8["evidence"]["value"] == 2 << 30  # not 3 GiB

    def test_zero_stage_proposed_from_opt_state_pressure(self,
                                                         tmp_path):
        at = _tool("autotune")
        recs = [
            self._sample(1.0, "train.steps", "counter", 10),
            self._sample(1.0, "mem.opt_state_bytes", "gauge", 512 << 20,
                         scope="global"),
            self._sample(1.0, "mem.opt_state_bytes", "gauge", 512 << 20,
                         scope="per_replica"),
        ]
        p = self._write(tmp_path / "t.jsonl", recs)
        rep = at.analyze([str(p)])
        by = {x["field"]: x for x in rep["proposals"]}
        z = by["zero_stage"]
        assert z["proposed"] == 1
        assert z["evidence"]["series"] == "mem.opt_state_bytes"
        assert z["evidence"]["value"] == 512 << 20
        assert rep["runtime_config"]["zero_stage"] == 1

    def test_zero3_proposed_from_param_pressure(self, tmp_path):
        at = _tool("autotune")
        recs = [
            self._sample(1.0, "train.steps", "counter", 10),
            self._sample(1.0, "mem.opt_state_bytes", "gauge", 512 << 20,
                         scope="global"),
            self._sample(1.0, "mem.opt_state_bytes", "gauge", 64 << 20,
                         scope="per_replica"),
            self._sample(1.0, "mem.params_bytes", "gauge", 400 << 20,
                         scope="per_replica"),
        ]
        p = self._write(tmp_path / "t.jsonl", recs)
        rep = at.analyze([str(p)], base={"zero_stage": 1})
        by = {x["field"]: x for x in rep["proposals"]}
        assert by["zero_stage"]["proposed"] == 3
        assert by["zero_stage"]["evidence"]["series"] == \
            "mem.params_bytes"

    def test_sharded_small_footprint_proposes_nothing(self, tmp_path):
        at = _tool("autotune")
        recs = [
            self._sample(1.0, "train.steps", "counter", 10),
            self._sample(1.0, "mem.opt_state_bytes", "gauge", 8 << 20,
                         scope="global"),
            self._sample(1.0, "mem.opt_state_bytes", "gauge", 8 << 20,
                         scope="per_replica"),
        ]
        p = self._write(tmp_path / "t.jsonl", recs)
        rep = at.analyze([str(p)])
        assert not [x for x in rep["proposals"]
                    if x["field"] == "zero_stage"]

    def test_config_defaults_parity(self):
        at = _tool("autotune")
        from paddle_tpu.framework.runtime_config import RuntimeConfig
        assert at.CONFIG_DEFAULTS == RuntimeConfig().to_dict()
        assert "zero_stage" in at.CONFIG_DEFAULTS


# ===========================================================================
# two axes at once: ZeRO-3 over data, TP over model, one llama step
# ===========================================================================
class TestTwoAxisLlama:
    def test_zero3_tp_llama_loss_parity_comm_split_and_footprint(self):
        """`data=4,model=2`, ZeRO-3, a TP llama through HybridTrainStep
        on the 8 virtual devices: the loss curve is the unsharded
        reference's (sharding is a layout decision), `comm.bytes`
        carries BOTH a data-axis and a model-axis share, and the
        per-replica parameter and optimizer-state footprints are under
        the global ones."""
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       LlamaPretrainingCriterion)
        crit = LlamaPretrainingCriterion(LlamaConfig.tiny())
        loss_fn = lambda lg, lb: crit(lg, lb)
        ids = np.random.RandomState(0).randint(1, 256, (8, 32))
        x = lambda: paddle.to_tensor(ids)

        def axis_bytes():
            out = {}
            for s in obs.get_registry().counter("comm.bytes").samples():
                ax = s.labels.get("axis", "?")
                out[ax] = out.get(ax, 0) + s.value
            return out

        paddle.seed(0)
        ref = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
        rstep = TrainStep(ref, paddle.optimizer.AdamW(
            1e-3, parameters=ref.parameters()), loss_fn)
        ref_losses = [float(rstep(x(), x())) for _ in range(2)]

        plan = HybridParallelPlan.from_spec("data=4,model=2",
                                            zero_stage=3, schedule="1F1B")
        mesh = plan.build_mesh()
        was = obs.enabled()
        obs.enabled(True)
        set_mesh(mesh)
        try:
            before = axis_bytes()
            paddle.seed(0)
            model = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=True))
            step = HybridTrainStep(
                model, paddle.optimizer.AdamW(
                    1e-3, parameters=model.parameters()),
                loss_fn, plan=plan, mesh=mesh)
            losses = [float(step(x(), x())) for _ in range(2)]
            fp = step.footprint()
            after = axis_bytes()
        finally:
            set_mesh(None)
            obs.enabled(was)
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-3,
                                   atol=2e-4)
        moved = {k: after[k] - before.get(k, 0) for k in after}
        assert moved.get("data", 0) > 0 and moved.get("model", 0) > 0
        for k in ("params_bytes", "opt_state_bytes"):
            assert fp[k]["per_replica"] < fp[k]["global"], (k, fp[k])


# ===========================================================================
# hybrid engine + AOT (small model)
# ===========================================================================
class TestHybridEngine:
    def test_engine_routes_and_aot_round_trip(self, tmp_path):
        plan = HybridParallelPlan.from_spec("data=4", zero_stage=1)
        mesh = plan.build_mesh()
        rng = np.random.RandomState(0)
        x = rng.randn(8, 16).astype(np.float32)
        y = rng.randn(8, 16).astype(np.float32)
        set_mesh(mesh)
        try:
            m = _mlp()
            opt = paddle.optimizer.AdamW(1e-2,
                                         parameters=m.parameters())
            step = HybridTrainStep(m, opt, _LOSS, plan=plan, mesh=mesh)
            assert isinstance(step.inner, DistTrainStep)
            losses = [float(step(paddle.to_tensor(x),
                                 paddle.to_tensor(y)))
                      for _ in range(2)]
            d = str(tmp_path / "bundle")
            man = step.save_bundle(d, paddle.to_tensor(x),
                                   paddle.to_tensor(y))
            assert man["geometry"]["mesh_topology"] == "data=4"
            assert man["geometry"]["plan"]["zero_stage"] == 1
            # fresh step, warm start — losses continue identically
            m2 = _mlp()
            o2 = paddle.optimizer.AdamW(1e-2,
                                        parameters=m2.parameters())
            s2 = HybridTrainStep(
                m2, o2, _LOSS, mesh=mesh,
                plan=HybridParallelPlan.from_spec("data=4",
                                                  zero_stage=1))
            s2.load_bundle(d, paddle.to_tensor(x), paddle.to_tensor(y))
            warm = [float(s2(paddle.to_tensor(x), paddle.to_tensor(y)))
                    for _ in range(2)]
            np.testing.assert_allclose(warm, losses, rtol=1e-5,
                                       atol=1e-6)
            # cost_analysis on a warm-loaded signature must trace an
            # analysis twin, not crash on the AOT stub's _jitted=None
            # (review finding)
            ca = s2.inner.cost_analysis(paddle.to_tensor(x),
                                        paddle.to_tensor(y))
            assert float(ca.get("flops", 0)) > 0
            # ...and the hot path still serves the AOT executable
            assert getattr(
                s2.inner._compiled[next(iter(s2.inner._compiled))],
                "_jitted", "missing") is None
            # topology mismatch → BundleInvalid("topology")
            from paddle_tpu.inference.aot.bundle import BundleInvalid
            p2 = HybridParallelPlan.from_spec("data=2", zero_stage=1)
            s3 = HybridTrainStep(m2, o2, _LOSS, plan=p2,
                                 mesh=p2.build_mesh())
            with pytest.raises(BundleInvalid) as ei:
                s3.load_bundle(d, paddle.to_tensor(x),
                               paddle.to_tensor(y))
            assert ei.value.reason == "topology"
        finally:
            set_mesh(None)

    def test_guarded_limits_name_workarounds(self, tmp_path):
        """Every new NotImplementedError boundary raises with guidance
        (tests_guards.py pin): accum-under-pp at the engine, tied
        embeddings under 1F1B-explicit, pipeline/accum steps at the
        AOT front door."""
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineLayer)
        from paddle_tpu.distributed.fleet.meta_parallel.\
            pipeline_parallel import PipelineTrainStep
        from paddle_tpu.models import LlamaConfig

        # engine: grad accumulation under pipeline parallelism
        plan = HybridParallelPlan(degrees={"stage": 2},
                                  grad_accum_steps=2)
        mesh = plan.build_mesh()
        set_mesh(mesh)
        try:
            paddle.seed(0)

            class Edge(nn.Layer):
                def __init__(self, d=8):
                    super().__init__()
                    self.proj = nn.Linear(d, d)

                def forward(self, x):
                    return self.proj(x)

            m = PipelineLayer([Edge() for _ in range(2)], num_stages=2)
            opt = paddle.optimizer.AdamW(1e-2,
                                         parameters=m.parameters())
            with pytest.raises(NotImplementedError,
                               match="num_microbatches"):
                HybridTrainStep(m, opt, _LOSS, plan=plan, mesh=mesh)

            # extra model inputs cannot ride the pipeline schedule
            p1 = HybridParallelPlan(degrees={"stage": 2})
            with pytest.raises(NotImplementedError, match="ONE tensor"):
                HybridTrainStep(m, opt, _LOSS, plan=p1, mesh=mesh,
                                n_model_inputs=2)

            # 1F1B-explicit with tied pre/post params
            from paddle_tpu.models import LlamaForCausalLMPipe
            cfg = LlamaConfig.tiny(tensor_parallel=False,
                                   tie_word_embeddings=True)
            paddle.seed(0)
            pipe = LlamaForCausalLMPipe(cfg, num_stages=2)
            popt = paddle.optimizer.AdamW(1e-3,
                                          parameters=pipe.parameters())
            with pytest.raises(NotImplementedError, match="untie"):
                PipelineTrainStep(pipe, popt, _LOSS,
                                  num_microbatches=2, mesh=mesh,
                                  schedule_mode="1F1B-explicit")
        finally:
            set_mesh(None)

        # AOT: ZeRO-2 accum step bundles are not wired
        from paddle_tpu.distributed.fleet.hybrid.aot import (
            save_step_bundle)
        p2 = HybridParallelPlan.from_spec("data=2", zero_stage=2,
                                          grad_accum_steps=2)
        mesh2 = p2.build_mesh()
        set_mesh(mesh2)
        try:
            m2 = _mlp()
            o2 = paddle.optimizer.AdamW(1e-2,
                                        parameters=m2.parameters())
            s2 = HybridTrainStep(m2, o2, _LOSS, plan=p2, mesh=mesh2)
            x = paddle.to_tensor(np.zeros((4, 16), np.float32))
            with pytest.raises(NotImplementedError, match="one-shot"):
                save_step_bundle(s2, str(tmp_path / "b"), x, x)
        finally:
            set_mesh(None)

    def test_pp_plan_routes_to_pipeline(self):
        from paddle_tpu.distributed.fleet.meta_parallel import (
            PipelineLayer)
        from paddle_tpu.distributed.fleet.meta_parallel.\
            pipeline_parallel import PipelineTrainStep

        class Edge(nn.Layer):
            def __init__(self, d=8):
                super().__init__()
                self.proj = nn.Linear(d, d)

            def forward(self, x):
                return self.proj(x)

        plan = HybridParallelPlan(degrees={"stage": 2},
                                  schedule="1F1B-explicit",
                                  num_microbatches=2)
        mesh = plan.build_mesh()
        set_mesh(mesh)
        try:
            paddle.seed(0)
            m = PipelineLayer([Edge() for _ in range(4)], num_stages=2)
            opt = paddle.optimizer.AdamW(1e-2,
                                         parameters=m.parameters())
            step = HybridTrainStep(m, opt, _LOSS, plan=plan, mesh=mesh)
            assert isinstance(step.inner, PipelineTrainStep)
            x = paddle.to_tensor(
                np.random.RandomState(0).randn(4, 8).astype(np.float32))
            l0 = float(step(x, x))
            assert np.isfinite(l0)
        finally:
            set_mesh(None)

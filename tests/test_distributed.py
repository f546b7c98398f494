"""Distributed engine tests on the 8-virtual-device CPU mesh.

The key oracle (SURVEY.md §4, mirroring test/collective/fleet
hybrid_parallel_* suites): N-way parallel loss must match the
single-device loss for k steps on a toy model.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn
import paddle_tpu.nn.functional as F

fleet = dist.fleet


def _fresh_mesh(**kw):
    m = dist.build_mesh(**kw)
    dist.set_mesh(m)
    return m


class MLP(nn.Layer):
    def __init__(self, din=8, dh=16, dout=4, parallel=False):
        super().__init__()
        if parallel:
            self.fc1 = fleet.ColumnParallelLinear(din, dh, gather_output=False)
            self.fc2 = fleet.RowParallelLinear(dh, dout,
                                               input_is_parallel=True)
        else:
            self.fc1 = nn.Linear(din, dh)
            self.fc2 = nn.Linear(dh, dout)

    def forward(self, x):
        return self.fc2(F.relu(self.fc1(x)))


def _train(model, steps, x, y, stage=0, mesh=None, lr=0.1):
    opt = paddle.optimizer.Adam(lr, parameters=model.parameters())
    step = fleet.DistTrainStep(model, opt,
                               lambda out, yy: F.mse_loss(out, yy),
                               sharding_stage=stage, mesh=mesh)
    losses = []
    for _ in range(steps):
        losses.append(float(step(paddle.to_tensor(x), paddle.to_tensor(y))))
    return losses, model


def _data():
    rng = np.random.RandomState(0)
    return (rng.rand(8, 8).astype(np.float32),
            rng.rand(8, 4).astype(np.float32))


def _single_device_reference(steps=4):
    x, y = _data()
    paddle.seed(11)
    m = MLP()
    opt = paddle.optimizer.Adam(0.1, parameters=m.parameters())
    losses = []
    for _ in range(steps):
        loss = F.mse_loss(m(paddle.to_tensor(x)), paddle.to_tensor(y))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    return losses, m


class TestMesh:
    def test_build_infer(self):
        m = dist.build_mesh(dp=-1)
        assert m.shape["data"] == 8
        m2 = dist.build_mesh(dp=2, mp=4)
        assert m2.shape["data"] == 2 and m2.shape["model"] == 4
        with pytest.raises(ValueError):
            dist.build_mesh(dp=3, mp=2)

    def test_env(self):
        assert dist.get_world_size() == 1  # single process
        assert dist.get_rank() == 0
        env = dist.ParallelEnv()
        assert env.world_size == 1


class TestCollectiveEagerFallback:
    def test_all_reduce_identity_outside_spmd(self):
        t = paddle.to_tensor([1.0, 2.0])
        out = dist.all_reduce(t)
        np.testing.assert_allclose(out.numpy(), [1.0, 2.0])

    def test_spmd_region_psum(self):
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        mesh = _fresh_mesh(dp=8)
        g = dist.new_group(axis="data")

        def f(x):
            with dist.spmd_region({"data": "data"}):
                t = paddle.Tensor(x)
                out = dist.all_reduce(t)
                return out._value

        sharded = shard_map(f, mesh=mesh, in_specs=P("data"),
                            out_specs=P("data"))
        x = jnp.arange(8.0)
        out = sharded(x)
        np.testing.assert_allclose(np.asarray(out), [28.0] * 8)


class TestDataParallelParity:
    def test_dp_loss_parity(self):
        ref_losses, _ = _single_device_reference()
        x, y = _data()
        mesh = _fresh_mesh(dp=8)
        paddle.seed(11)
        m = MLP()
        losses, _ = _train(m, 4, x, y, mesh=mesh)
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)


class TestZeroStages:
    @pytest.mark.parametrize("stage", [1, 2, 3])
    def test_sharding_stage_parity(self, stage):
        ref_losses, ref_m = _single_device_reference()
        x, y = _data()
        mesh = _fresh_mesh(dp=8)
        paddle.seed(11)
        m = MLP()
        losses, m = _train(m, 4, x, y, stage=stage, mesh=mesh)
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
        for (n1, p1), (n2, p2) in zip(ref_m.named_parameters(),
                                      m.named_parameters()):
            np.testing.assert_allclose(p1.numpy(), np.asarray(p2._value),
                                       rtol=2e-3, atol=1e-5, err_msg=n1)

    def test_group_sharded_parallel_api(self):
        mesh = _fresh_mesh(dp=8)
        m = MLP()
        opt = paddle.optimizer.Adam(0.1, parameters=m.parameters())
        m2, opt2 = dist.group_sharded_parallel(m, opt, level="p_g_os")
        assert m2._sharding_stage == 3


class TestTensorParallelParity:
    def test_tp_loss_parity(self):
        x, y = _data()
        # reference: plain MLP, single device mesh
        paddle.seed(21)
        ref = MLP()
        # deep-copy: the compiled step donates param buffers, so an alias
        # of the live arrays would be invalidated after the first step
        init_sd = {k: paddle.to_tensor(np.array(v.numpy()))
                   for k, v in ref.state_dict().items()}
        losses_ref, _ = _train(ref, 4, x, y, mesh=dist.build_mesh(dp=1))

        # TP over a 4-way model axis starting from the same weights
        mesh = _fresh_mesh(dp=2, mp=4)
        tp = MLP(parallel=True)
        tp.set_state_dict(init_sd)
        losses_tp, _ = _train(tp, 4, x, y, mesh=mesh)
        np.testing.assert_allclose(losses_tp, losses_ref, rtol=1e-4)

    def test_vocab_parallel_embedding(self):
        mesh = _fresh_mesh(mp=8, dp=1)
        emb = fleet.VocabParallelEmbedding(16, 8)
        out = emb(paddle.to_tensor([[1, 2], [3, 4]]))
        assert out.shape == [2, 2, 8]

    def test_parallel_cross_entropy(self):
        mesh = _fresh_mesh(mp=8, dp=1)
        pce = fleet.ParallelCrossEntropy()
        logits = paddle.randn([4, 16])
        labels = paddle.to_tensor(np.array([1, 5, 9, 15]))
        loss = pce(logits, labels)
        ref = F.cross_entropy(logits, labels, reduction="none")
        np.testing.assert_allclose(loss.numpy(), ref.numpy(), rtol=1e-5)


class TestFleetAPI:
    def test_fleet_init_and_wrappers(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 2,
                                   "pp_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        assert hcg.get_data_parallel_world_size() == 4
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.mesh.shape["data"] == 4

        m = fleet.distributed_model(MLP(parallel=True))
        opt = fleet.distributed_optimizer(
            paddle.optimizer.Adam(0.05, parameters=m.parameters()))
        x, y = _data()
        loss = m.train_batch([paddle.to_tensor(x), paddle.to_tensor(y)],
                             optimizer=opt,
                             loss_fn=lambda out, yy: F.mse_loss(out, yy))
        assert np.isfinite(float(loss))

    def test_recompute_matches_plain(self):
        paddle.seed(5)
        m = MLP()
        x = paddle.to_tensor(np.random.RandomState(2).rand(4, 8).astype(np.float32))
        plain = m(x)
        rec = fleet.recompute(m.forward, x)
        np.testing.assert_allclose(rec.numpy(), plain.numpy(), rtol=1e-6)
        # grads flow through recompute
        rec.sum().backward()
        assert m.fc1.weight.grad is not None


class TestAutoParallel:
    def test_process_mesh_shard_tensor(self):
        mesh = dist.ProcessMesh([[0, 1, 2, 3], [4, 5, 6, 7]],
                                dim_names=["x", "y"])
        t = paddle.ones([8, 4])
        d = dist.shard_tensor(t, mesh, [dist.Shard(0), dist.Replicate()])
        assert d.shape == [8, 4]
        assert d._placements[0] == dist.Shard(0)

    def test_reshard(self):
        mesh = dist.ProcessMesh([0, 1, 2, 3], dim_names=["x"])
        t = paddle.ones([8, 4])
        d = dist.shard_tensor(t, mesh, [dist.Shard(0)])
        r = dist.reshard(d, mesh, [dist.Replicate()])
        np.testing.assert_allclose(r.numpy(), np.ones((8, 4)))

    def test_shard_tensor_computes(self):
        mesh = dist.ProcessMesh(list(range(8)), dim_names=["x"])
        a = dist.shard_tensor(paddle.ones([16, 4]), mesh, [dist.Shard(0)])
        b = dist.shard_tensor(paddle.ones([16, 4]), mesh, [dist.Shard(0)])
        c = a + b
        np.testing.assert_allclose(c.numpy(), np.full((16, 4), 2.0))


class TestDistCheckpoint:
    def test_save_load_state_dict(self, tmp_path):
        m = MLP()
        sd = m.state_dict()
        path = str(tmp_path / "ckpt")
        dist.checkpoint.save_state_dict(sd, path)
        m2 = MLP()
        sd2 = m2.state_dict()
        dist.checkpoint.load_state_dict(sd2, path)
        np.testing.assert_allclose(m2.fc1.weight.numpy(),
                                   m.fc1.weight.numpy())

    def test_save_state_dict_async_is_honored(self, tmp_path):
        """Regression: async_save used to be accepted and silently
        ignored (a fully synchronous save). It now snapshots
        immediately, drains in background, and wait_for_async_saves()
        makes the write durable + re-raises drain failures."""
        m = MLP()
        sd = m.state_dict()
        path = str(tmp_path / "ckpt_async")
        dist.checkpoint.save_state_dict(sd, path, async_save=True)
        assert dist.checkpoint.wait_for_async_saves(timeout_s=60)
        m2 = MLP()
        sd2 = m2.state_dict()
        dist.checkpoint.load_state_dict(sd2, path)
        np.testing.assert_allclose(m2.fc1.weight.numpy(),
                                   m.fc1.weight.numpy())
        # idempotent when nothing is outstanding
        assert dist.checkpoint.wait_for_async_saves()


class TestSequenceParallel:
    """Megatron SP (parity: fleet/utils/sequence_parallel_utils.py):
    activations sharded along the sequence dim between the row/column
    matmuls; training must match the plain-TP and single-device runs."""

    def test_sp_loss_parity(self):
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.distributed.mesh import build_mesh, set_mesh
        from paddle_tpu.distributed.fleet.dist_step import DistTrainStep
        from paddle_tpu.distributed.fleet.meta_parallel.mp_layers import (
            ColumnSequenceParallelLinear, RowSequenceParallelLinear)
        from paddle_tpu.jit import TrainStep

        d, B, S, steps = 16, 4, 8, 4
        rng = np.random.RandomState(13)
        x = rng.randn(B, S, d).astype(np.float32)
        y = rng.randn(B, S, d).astype(np.float32)
        lf = lambda o, t: ((o - t) ** 2).mean()

        class SPBlock(nn.Layer):
            def __init__(self):
                super().__init__()
                self.up = ColumnSequenceParallelLinear(
                    d, 2 * d, gather_output=False)
                self.down = RowSequenceParallelLinear(
                    2 * d, d, input_is_parallel=True)

            def forward(self, x):
                return x + self.down(nn.functional.gelu(self.up(x)))

        # single-device reference (same math, no sharding)
        paddle.seed(31)
        ref = SPBlock()
        ref_opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=ref.parameters())
        ref_step = TrainStep(ref, ref_opt, lf)
        ref_losses = [float(ref_step(paddle.to_tensor(x),
                                     paddle.to_tensor(y)))
                      for _ in range(steps)]

        mesh = build_mesh(dp=1, mp=4)
        set_mesh(mesh)
        try:
            paddle.seed(31)
            m = SPBlock()
            opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=m.parameters())
            step = DistTrainStep(m, opt, lf, mesh=mesh)
            losses = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
                      for _ in range(steps)]
        finally:
            set_mesh(None)
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=2e-5)

    def test_scatter_op_shards_sequence_dim(self):
        import jax
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.distributed.mesh import build_mesh, set_mesh, mesh_scope
        from paddle_tpu.distributed.fleet.meta_parallel.mp_layers import (
            ScatterOp)

        mesh = build_mesh(dp=1, mp=4)
        set_mesh(mesh)
        try:
            with mesh_scope(mesh):
                x = paddle.to_tensor(
                    np.zeros((2, 8, 16), np.float32))
                out = ScatterOp.apply(x)
                sharded = jax.jit(lambda v: v * 1.0)(out._value)
            spec = sharded.sharding.spec
            assert "model" in str(spec), spec
        finally:
            set_mesh(None)


class TestDistBf16MultiPrecision:
    def test_bf16_dist_train_step_finite(self):
        """bf16 params under DistTrainStep (the bench/dryrun hybrid path):
        f32 master weights in the sharded opt state, finite descending
        loss."""
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.distributed.mesh import build_mesh, set_mesh
        from paddle_tpu.distributed.fleet.dist_step import DistTrainStep

        mesh = build_mesh(dp=2, mp=4)
        set_mesh(mesh)
        try:
            paddle.seed(0)
            m = nn.Sequential(nn.Linear(16, 64), nn.GELU(),
                              nn.Linear(64, 16))
            for p in m.parameters():
                p._value = p._value.astype(jnp.bfloat16)
            opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=m.parameters())
            step = DistTrainStep(m, opt, lambda o, t: ((o - t) ** 2).mean(),
                                 mesh=mesh, sharding_stage=3)
            rng = np.random.RandomState(0)
            x = paddle.to_tensor(rng.randn(8, 16).astype(np.float32)
                                 .astype(jnp.bfloat16))
            y = paddle.to_tensor(rng.randn(8, 16).astype(np.float32)
                                 .astype(jnp.bfloat16))
            losses = [float(step(x, y)) for _ in range(6)]
            assert all(np.isfinite(v) for v in losses), losses
            assert losses[-1] < losses[0]
            st = step.opt_state[0]
            assert st["master_weight"].dtype == jnp.float32
            assert st["moment1"].dtype == jnp.float32
        finally:
            set_mesh(None)


class TestDistGradScaler:
    def test_f16_scaler_in_dist_step(self):
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.amp import GradScaler
        from paddle_tpu.distributed.mesh import build_mesh, set_mesh
        from paddle_tpu.distributed.fleet.dist_step import DistTrainStep

        mesh = build_mesh(dp=2, mp=1)
        set_mesh(mesh)
        try:
            paddle.seed(0)
            m = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
            for p in m.parameters():
                p._value = p._value.astype(jnp.float16)
            opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=m.parameters())
            sc = GradScaler(init_loss_scaling=2.0 ** 28,
                            decr_every_n_nan_or_inf=1)
            step = DistTrainStep(m, opt, lambda o, t: ((o - t) ** 2).mean(),
                                 mesh=mesh, scaler=sc)
            rng = np.random.RandomState(0)
            x = paddle.to_tensor(rng.randn(8, 8).astype(np.float16))
            y = paddle.to_tensor(rng.randn(8, 4).astype(np.float16))
            losses = [float(step(x, y)) for _ in range(20)]
            assert sc.get_loss_scaling() < 2.0 ** 28  # overflow decayed it
            assert all(np.isfinite(v) for v in losses)
            assert losses[-1] < losses[0]
        finally:
            set_mesh(None)


class TestObjectCollectivesAndShims:
    def test_single_rank_degenerate(self):
        import paddle_tpu.distributed as dist
        objs = []
        dist.all_gather_object(objs, {"a": 1})
        assert objs == [{"a": 1}]
        lst = [{"x": 2}]
        dist.broadcast_object_list(lst)
        assert lst == [{"x": 2}]
        t = paddle.to_tensor(np.ones(3, "float32"))
        assert dist.wait(t) is t
        out = dist.gather(t)
        assert len(out) == 1
        got = []
        dist.scatter_object_list(got, [1, 2, 3])
        assert got == [1]

    def test_p2p_guidance_and_launch_attr(self):
        import paddle_tpu.distributed as dist
        with pytest.raises(RuntimeError):
            dist.isend(paddle.to_tensor(np.ones(2, "f4")), dst=1)
        with pytest.raises(RuntimeError):
            dist.irecv(paddle.to_tensor(np.ones(2, "f4")), src=0)
        assert hasattr(dist, "launch")
        task = dist.collective._DoneTask()
        assert task.is_completed()
        task.wait()


class TestAutoParallelTail:
    """Round-4 auto-parallel surface: Strategy / to_static / shard_optimizer
    / unshard_dtensor (reference: python/paddle/distributed/auto_parallel)."""

    def test_strategy_config_merge(self):
        st = dist.Strategy({"pipeline": {"enable": True,
                                         "accumulate_steps": 4},
                            "amp": {"dtype": "bfloat16"}})
        assert st.pipeline.enable and st.pipeline.accumulate_steps == 4
        assert st.pipeline.schedule_mode == "1F1B"  # default survives
        assert st.amp.dtype == "bfloat16" and st.amp.enable is False
        assert dist.in_auto_parallel_align_mode() is False

    def test_dist_to_static_train_eval_predict(self):
        paddle.seed(0)
        net = paddle.nn.Sequential(paddle.nn.Linear(8, 8), paddle.nn.ReLU(),
                                   paddle.nn.Linear(8, 4))
        opt = paddle.optimizer.AdamW(1e-2, parameters=net.parameters())
        opt = dist.shard_optimizer(opt)
        dm = dist.to_static(net, None, paddle.nn.MSELoss(), opt,
                            dist.Strategy())
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(4, 8).astype("float32"))
        y = paddle.to_tensor(rng.randn(4, 4).astype("float32"))
        losses = [float(dm(x, y)) for _ in range(4)]
        assert losses[-1] < losses[0]
        dm.eval()
        assert float(dm(x, y)) > 0
        dm.predict()
        assert dm(x).shape == [4, 4]

    def test_dist_to_static_multi_input_and_strategy(self):
        paddle.seed(1)

        class TwoIn(paddle.nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = paddle.nn.Linear(8, 4)

            def forward(self, a, b):
                return self.fc(a) + self.fc(b)

        net = TwoIn()
        opt = paddle.optimizer.AdamW(
            1e-2, parameters=net.parameters())
        st = dist.Strategy({"sharding": {"enable": True, "stage": 2}})
        dm = dist.to_static(net, None, paddle.nn.MSELoss(), opt, st)
        rng = np.random.RandomState(3)
        a = paddle.to_tensor(rng.randn(4, 8).astype("float32"))
        b = paddle.to_tensor(rng.randn(4, 8).astype("float32"))
        y = paddle.to_tensor(rng.randn(4, 4).astype("float32"))
        losses = [float(dm(a, b, y)) for _ in range(3)]
        assert losses[-1] < losses[0]
        assert dm._step._stage == 2  # Strategy applied
        dm.predict()
        assert dm(a, b).shape == [4, 4]

    def test_unshard_dtensor(self):
        mesh = dist.ProcessMesh([8])
        t = dist.shard_tensor(paddle.ones([8, 4]), mesh, [dist.Shard(0)])
        u = dist.unshard_dtensor(t)
        assert u.shape == [8, 4]
        np.testing.assert_allclose(u.numpy(), np.ones((8, 4)))
        # placement annotation is gone
        assert getattr(u, "_process_mesh", None) is None


class TestAutoParallelStaticEngine:
    """round 5: static Engine fit/evaluate/predict (parity model:
    upstream auto_parallel/static/engine.py over toy nets, as in
    test/auto_parallel engine tests). Oracle: Engine.fit loss curve ==
    the eager dynamic loop on the same seed/arch/data."""

    def _dataset(self, n=16):
        from paddle_tpu.io import Dataset

        class DS(Dataset):
            def __init__(self):
                rng = np.random.RandomState(0)
                self.x = rng.rand(n, 8).astype(np.float32)
                self.y = rng.rand(n, 4).astype(np.float32)

            def __getitem__(self, i):
                return self.x[i], self.y[i]

            def __len__(self):
                return len(self.x)
        return DS()

    def test_engine_fit_matches_dynamic(self):
        from paddle_tpu.distributed.auto_parallel import Engine
        _fresh_mesh(dp=2, mp=4)
        ds = self._dataset()

        paddle.seed(21)
        m1 = MLP(parallel=True)
        opt1 = paddle.optimizer.Adam(0.05, parameters=m1.parameters())
        eng = Engine(m1, lambda out, y: F.mse_loss(out, y), opt1)
        hist = eng.fit(ds, batch_size=8, epochs=2, verbose=0)
        assert len(hist["loss"]) == 2
        assert hist["loss"][1] < hist["loss"][0]

        # dynamic-path oracle: same arch/seed/data through DistTrainStep
        paddle.seed(21)
        m2 = MLP(parallel=True)
        opt2 = paddle.optimizer.Adam(0.05, parameters=m2.parameters())
        step = fleet.DistTrainStep(m2, opt2,
                                   lambda out, y: F.mse_loss(out, y),
                                   mesh=dist.build_mesh(dp=2, mp=4))
        ref = []
        for _ in range(2):
            ep = []
            for s in range(2):
                xb = paddle.to_tensor(ds.x[s * 8:(s + 1) * 8])
                yb = paddle.to_tensor(ds.y[s * 8:(s + 1) * 8])
                ep.append(float(step(xb, yb)))
            ref.append(float(np.mean(ep)))
        np.testing.assert_allclose(hist["loss"], ref, rtol=1e-5)

    def test_engine_evaluate_predict_metrics(self):
        from paddle_tpu.distributed.auto_parallel import Engine
        _fresh_mesh(dp=-1)
        ds = self._dataset()
        paddle.seed(5)
        m = MLP()
        eng = Engine(m, lambda out, y: F.mse_loss(out, y),
                     paddle.optimizer.SGD(0.1, parameters=m.parameters()))
        res = eng.evaluate(ds, batch_size=8, verbose=0)
        assert "eval_loss" in res and np.isfinite(res["eval_loss"])
        outs = eng.predict(ds, batch_size=8)
        assert len(outs) == 2 and list(outs[0].shape) == [8, 4]

    def test_engine_metric_accuracy_counts_all_rows(self):
        # advisor repro: Accuracy.compute returns ONE tensor; update must
        # receive it whole (row-splatting counted only sample 0 per batch)
        from paddle_tpu.distributed.auto_parallel import Engine
        from paddle_tpu.io import Dataset
        from paddle_tpu.metric import Accuracy
        _fresh_mesh(dp=-1)

        class DS(Dataset):
            def __init__(self):
                self.x = np.eye(4, dtype=np.float32).repeat(4, 0)
                self.y = np.argmax(self.x, -1).astype(np.int64)[:, None]

            def __getitem__(self, i):
                return self.x[i], self.y[i]

            def __len__(self):
                return 16
        ident = nn.Linear(4, 4)
        with paddle.no_grad():
            ident.weight.set_value(np.eye(4, dtype=np.float32) * 10)
            ident.bias.set_value(np.zeros(4, dtype=np.float32))
        eng = Engine(ident, metrics=[Accuracy()])
        res = eng.evaluate(DS(), batch_size=8, verbose=0)
        np.testing.assert_allclose(res["eval_acc"], 1.0)

    def test_engine_cost_after_fit(self):
        from paddle_tpu.distributed.auto_parallel import Engine
        _fresh_mesh(dp=-1)
        ds = self._dataset()
        paddle.seed(3)
        m = MLP()
        eng = Engine(m, lambda out, y: F.mse_loss(out, y),
                     paddle.optimizer.SGD(0.1, parameters=m.parameters()))
        assert eng.cost() is None
        eng.fit(ds, batch_size=8, epochs=1, verbose=0)
        ca = eng.cost()
        assert ca and ca.get("flops", 0) > 0

    def test_engine_save_load(self, tmp_path):
        from paddle_tpu.distributed.auto_parallel import Engine
        _fresh_mesh(dp=-1)
        ds = self._dataset()
        paddle.seed(7)
        m = MLP()
        opt = paddle.optimizer.Adam(0.05, parameters=m.parameters())
        eng = Engine(m, lambda out, y: F.mse_loss(out, y), opt)
        eng.fit(ds, batch_size=8, epochs=1, verbose=0)
        path = str(tmp_path / "ckpt")
        eng.save(path, training=True)
        w_before = {k: np.array(v.numpy())
                    for k, v in m.state_dict().items()}
        eng.fit(ds, batch_size=8, epochs=1, verbose=0)  # drift weights
        eng.load(path)
        for k, v in m.state_dict().items():
            np.testing.assert_allclose(np.asarray(v.numpy()),
                                       w_before[k], atol=1e-6)

    def test_engine_strategy_sharding_and_namespace(self):
        import paddle_tpu.distributed as d2
        # upstream module path importable
        from paddle_tpu.distributed.auto_parallel.static.engine import (
            Engine as E2)
        assert E2 is d2.auto_parallel.Engine
        _fresh_mesh(dp=-1)
        ds = self._dataset()
        paddle.seed(9)
        m = MLP()
        st = d2.Strategy({"sharding": {"enable": True, "stage": 2}})
        eng = E2(m, lambda out, y: F.mse_loss(out, y),
                 paddle.optimizer.Adam(0.05, parameters=m.parameters()),
                 strategy=st)
        hist = eng.fit(ds, batch_size=8, epochs=1, verbose=0)
        assert np.isfinite(hist["loss"][0])

"""Training fast path (PR 3): fused multi-tensor optimizer, ZeRO-1-style
sharded weight update, bucketed/quantized gradient collectives.

Oracles:
- fused vs per-param numerical parity for SGD/Momentum/Adam/AdamW
  (weight decay, grad clipping, bf16 multi-precision master weights);
- reduce-scatter+all-gather (weight_update_sharding) loss curves match
  the all-reduce path and the single-device reference;
- quantized gradient comm converges within tolerance of fp32 comm;
- dispatch count is O(#dtype buckets), not O(#params), and an LR
  scheduler stepping every iteration does not retrigger compilation.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn.functional as F
import paddle_tpu.observability as obs
from paddle_tpu import nn
from paddle_tpu.tensor import Parameter

fleet = dist.fleet


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    paddle.set_flags({"fused_optimizer": True, "quantized_grad_comm": False})


def _params(shapes=((4, 3), (7,), (2, 2, 2), (5, 5)), dtype=np.float32,
            seed=0):
    rng = np.random.RandomState(seed)
    return [Parameter(jnp.asarray(rng.randn(*s).astype(dtype)))
            for s in shapes]


def _set_grads(ps, step, scale=1.0, dtype=None):
    for i, p in enumerate(ps):
        g = np.random.RandomState(100 * step + i).randn(
            *p._value.shape).astype(np.float32) * scale
        arr = jnp.asarray(g)
        if dtype is not None:
            arr = arr.astype(dtype)
        else:
            arr = arr.astype(p._value.dtype)
        p.grad = paddle.to_tensor(arr)


class TestFusedEagerParity:
    @pytest.mark.parametrize("opt_cls,kw", [
        (paddle.optimizer.SGD, {"weight_decay": 0.01}),
        (paddle.optimizer.Momentum, {"use_nesterov": True,
                                     "weight_decay": 0.02}),
        (paddle.optimizer.Adam, {"weight_decay": 0.01}),
        (paddle.optimizer.AdamW, {"weight_decay": 0.05}),
    ])
    def test_matches_per_param(self, opt_cls, kw):
        def run(fused):
            paddle.set_flags({"fused_optimizer": fused})
            ps = _params()
            opt = opt_cls(learning_rate=0.05, parameters=ps, **kw)
            for s in range(3):
                _set_grads(ps, s)
                opt.step()
            return [np.asarray(p._value) for p in ps]

        for a, b in zip(run(True), run(False)):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)

    def test_adamw_decay_fun_and_clip(self):
        def run(fused):
            paddle.set_flags({"fused_optimizer": fused})
            ps = _params()
            for i, p in enumerate(ps):
                p.name = f"w{i}"
            opt = paddle.optimizer.AdamW(
                learning_rate=0.05, parameters=ps, weight_decay=0.1,
                apply_decay_param_fun=lambda n: n in ("w0", "w2"),
                grad_clip=nn.ClipGradByGlobalNorm(0.5))
            for s in range(3):
                _set_grads(ps, s, scale=3.0)
                opt.step()
            return [np.asarray(p._value) for p in ps]

        for a, b in zip(run(True), run(False)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_multi_precision_master_weights(self):
        def run(fused):
            paddle.set_flags({"fused_optimizer": fused})
            ps = _params(dtype=np.float32)
            for p in ps:
                p._value = p._value.astype(jnp.bfloat16)
            opt = paddle.optimizer.AdamW(learning_rate=0.05, parameters=ps,
                                         weight_decay=0.01)
            for s in range(3):
                _set_grads(ps, s, dtype=jnp.bfloat16)
                opt.step()
            # the f32 masters carry sub-bf16-ulp progress
            mws = [np.asarray(opt._accumulators["master_weight"][id(p)])
                   for p in ps]
            return [np.asarray(p._value, np.float32) for p in ps], mws

        (pf, mf), (pp, mp_) = run(True), run(False)
        for a, b in zip(pf, pp):
            np.testing.assert_allclose(a, b, rtol=1e-2, atol=1e-3)
        for a, b in zip(mf, mp_):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)

    def test_mixed_dtype_buckets(self):
        """f32 + bf16 params in one optimizer: one fused dispatch still
        covers both dtype buckets."""
        paddle.set_flags({"fused_optimizer": True})
        ps = _params(((4, 4), (6,)))
        ps[1]._value = ps[1]._value.astype(jnp.bfloat16)
        opt = paddle.optimizer.Adam(0.05, parameters=ps)
        _set_grads(ps, 0)
        opt.step()
        plan = opt._fused_plan
        assert plan is not None and len(plan.buckets) == 2
        assert plan.n_calls == 1

    def test_state_dict_roundtrip_and_path_switch(self):
        paddle.set_flags({"fused_optimizer": True})
        ps = _params()
        opt = paddle.optimizer.Adam(0.05, parameters=ps)
        for s in range(2):
            _set_grads(ps, s)
            opt.step()
        sd = opt.state_dict()
        assert any(k.endswith("_moment1") for k in sd)

        # restore into a fresh optimizer and continue on the PER-PARAM
        # path: trajectories must agree (flat state -> accumulators ->
        # flat again is lossless)
        ps2 = _params()
        opt2 = paddle.optimizer.Adam(0.05, parameters=ps2)
        opt2.set_state_dict(sd)
        # align param values with the stepped ones (deep copy: both
        # paths donate their param buffers)
        for p2, p in zip(ps2, ps):
            p2._value = jnp.array(p._value)
        paddle.set_flags({"fused_optimizer": False})
        _set_grads(ps2, 2)
        opt2.step()
        paddle.set_flags({"fused_optimizer": True})
        _set_grads(ps, 2)
        opt.step()
        for p, p2 in zip(ps, ps2):
            np.testing.assert_allclose(np.asarray(p._value),
                                       np.asarray(p2._value), rtol=1e-5,
                                       atol=1e-6)

    def test_fallback_for_custom_regularizer(self):
        """A callable per-param regularizer is not elementwise-fusible:
        the step silently takes the per-param path (correctness first)."""
        paddle.set_flags({"fused_optimizer": True})
        ps = _params(((3, 3), (4,)))
        ps[0].regularizer = lambda p, g: g + 0.1 * p * p
        opt = paddle.optimizer.SGD(0.1, parameters=ps)
        _set_grads(ps, 0)
        opt.step()
        assert getattr(opt, "_fused_plan", None) is None


class TestFusedDispatchAndLR:
    def test_dispatch_count_o_buckets(self):
        was = obs.enabled()
        obs.enabled(True)
        try:
            reg = obs.get_registry()
            c = reg.counter("train.opt_dispatches")
            base_f = c.value(path="fused")
            base_p = c.value(path="per_param")
            ps = _params(((8, 8), (8,), (3, 3), (5,), (2, 2)))
            paddle.set_flags({"fused_optimizer": True})
            opt = paddle.optimizer.Adam(0.05, parameters=ps)
            for s in range(4):
                _set_grads(ps, s)
                opt.step()
            assert c.value(path="fused") - base_f == 4  # 1 per step
            paddle.set_flags({"fused_optimizer": False})
            _set_grads(ps, 9)
            opt.step()
            # O(#params) for the fallback
            assert c.value(path="per_param") - base_p == len(ps)
        finally:
            obs.enabled(was)

    def test_lr_scheduler_does_not_retrace(self):
        """lr is an operand of the fused program: a scheduler stepping
        every iteration must not retrigger compilation (satellite:
        optimizer/lr.py contract)."""
        paddle.set_flags({"fused_optimizer": True})
        ps = _params(((6, 6), (6,)))
        sched = paddle.optimizer.lr.CosineAnnealingDecay(0.1, T_max=16)
        opt = paddle.optimizer.Momentum(sched, parameters=ps)
        lrs = []
        for s in range(5):
            _set_grads(ps, s)
            opt.step()
            sched.step()
            lrs.append(sched())
        assert len(set(np.round(lrs, 8))) > 1  # lr really changed
        plan = opt._fused_plan
        assert plan is not None and plan.n_calls == 5
        assert plan.n_traces == 1, "lr change retraced the fused program"

    def test_lr_operand_no_float_sync_for_tensor_lr(self):
        """_lr_operand must pass a device scalar through without float()
        (which would force a host sync per step)."""
        ps = _params(((3, 3),))
        opt = paddle.optimizer.SGD(0.1, parameters=ps)
        opt._learning_rate = paddle.to_tensor(np.float32(0.25))
        v = opt._lr_operand()
        assert v.dtype == jnp.float32 and float(v) == 0.25


class TestEagerUnscaleBatched:
    def test_single_program_and_found_inf(self):
        from paddle_tpu.amp import GradScaler
        ps = _params(((4, 4), (3,)))
        opt = paddle.optimizer.SGD(0.1, parameters=ps)
        sc = GradScaler(init_loss_scaling=8.0)
        _set_grads(ps, 0)
        for p in ps:
            p.grad._value = p.grad._value * 8.0
        before = [np.asarray(p.grad._value) for p in ps]
        sc.unscale_(opt)
        assert sc._found_inf is False
        for p, b in zip(ps, before):
            np.testing.assert_allclose(np.asarray(p.grad._value), b / 8.0,
                                       rtol=1e-6)
        # inf in any grad flips the single flag
        _set_grads(ps, 1)
        ps[1].grad._value = ps[1].grad._value.at[0].set(jnp.inf)
        sc._unscaled = False
        sc.unscale_(opt)
        assert sc._found_inf is True


def _mesh(dp, mp=1):
    m = dist.build_mesh(dp=dp, mp=mp)
    dist.set_mesh(m)
    return m


class MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16)
        self.fc2 = nn.Linear(16, 4)

    def forward(self, x):
        return self.fc2(F.relu(self.fc1(x)))


def _data():
    rng = np.random.RandomState(0)
    return (rng.rand(8, 8).astype(np.float32),
            rng.rand(8, 4).astype(np.float32))


def _eager_reference(steps=4, lr=0.1):
    x, y = _data()
    paddle.set_flags({"fused_optimizer": False})
    try:
        paddle.seed(11)
        m = MLP()
        opt = paddle.optimizer.Adam(lr, parameters=m.parameters())
        losses = []
        for _ in range(steps):
            loss = F.mse_loss(m(paddle.to_tensor(x)), paddle.to_tensor(y))
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        return losses
    finally:
        paddle.set_flags({"fused_optimizer": True})


class TestWeightUpdateSharding:
    def _train(self, mesh, wus, steps=4, quant=False):
        paddle.set_flags({"quantized_grad_comm": quant})
        try:
            x, y = _data()
            paddle.seed(11)
            m = MLP()
            opt = paddle.optimizer.Adam(0.1, parameters=m.parameters())
            step = fleet.DistTrainStep(
                m, opt, lambda o, t: F.mse_loss(o, t), mesh=mesh,
                weight_update_sharding=wus)
            losses = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
                      for _ in range(steps)]
            return losses, step
        finally:
            paddle.set_flags({"quantized_grad_comm": False})

    def test_two_device_data_axis_parity(self):
        """reduce-scatter+all-gather == all-reduce on a 2-way data axis
        (the acceptance mesh), both matching the eager reference."""
        ref = _eager_reference()
        try:
            mesh = _mesh(dp=2, mp=4)
            l_ar, _ = self._train(mesh, wus=False)
            l_ws, _ = self._train(mesh, wus=True)
        finally:
            dist.set_mesh(None)
        np.testing.assert_allclose(l_ar, ref, rtol=1e-4)
        np.testing.assert_allclose(l_ws, ref, rtol=1e-4)

    def test_opt_state_memory_divided_by_data_axis(self):
        """ZeRO-1 signal: the per-replica optimizer-state watermark drops
        by the data-axis size, and the flat buffers really are sharded
        over all devices."""
        was = obs.enabled()
        obs.enabled(True)
        try:
            mesh = _mesh(dp=8)
            _, s_plain = self._train(mesh, wus=False, steps=2)
            _, s_wus = self._train(mesh, wus=True, steps=2)
        finally:
            dist.set_mesh(None)
            obs.enabled(was)
        plain = s_plain._opt_state_bytes
        shard = s_wus._opt_state_bytes
        assert plain["per_replica"] == plain["global"]
        # padding + replicated step scalars leave a little slack
        assert shard["per_replica"] <= shard["global"] // 8 + 64, shard
        # the gauge carries the same numbers
        g = obs.get_registry().gauge("mem.opt_state_bytes", unit="bytes")
        assert g.value(scope="per_replica") == shard["per_replica"]
        # physical check: every flat vector leaf is split over 8 devices
        for st in s_wus._opt_state["fused"]:
            for k, v in st.items():
                if getattr(v, "ndim", 0) == 1:
                    assert len(v.sharding.device_set) == 8, k
                    shard_elems = v.sharding.shard_shape(v.shape)[0]
                    assert shard_elems == v.shape[0] // 8, k

    def test_scaler_with_wus(self):
        """Dynamic loss scaling composes with the sharded fused update:
        overflow skips the whole flat update and the scale decays."""
        from paddle_tpu.amp import GradScaler
        try:
            mesh = _mesh(dp=2, mp=1)
            paddle.seed(0)
            m = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
            for p in m.parameters():
                p._value = p._value.astype(jnp.float16)
            opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=m.parameters())
            sc = GradScaler(init_loss_scaling=2.0 ** 28,
                            decr_every_n_nan_or_inf=1)
            step = fleet.DistTrainStep(
                m, opt, lambda o, t: ((o - t) ** 2).mean(), mesh=mesh,
                scaler=sc, weight_update_sharding=True)
            rng = np.random.RandomState(0)
            x = paddle.to_tensor(rng.randn(8, 8).astype(np.float16))
            y = paddle.to_tensor(rng.randn(8, 4).astype(np.float16))
            # 2^28 needs ~13 overflow halvings before real steps land
            losses = [float(step(x, y)) for _ in range(20)]
            assert sc.get_loss_scaling() < 2.0 ** 28
            assert all(np.isfinite(v) for v in losses)
            assert losses[-1] < losses[0]
        finally:
            dist.set_mesh(None)

    def test_state_dict_after_wus_steps(self):
        try:
            mesh = _mesh(dp=8)
            _, step = self._train(mesh, wus=True, steps=2)
            sd = step._opt.state_dict()
        finally:
            dist.set_mesh(None)
        moment_keys = [k for k in sd if k.endswith("_moment1")]
        assert len(moment_keys) == 4  # 2 layers x (weight, bias)
        for k in moment_keys:
            assert np.isfinite(np.asarray(sd[k]._value)).all()


class TestQuantizedComm:
    def test_wire_quantized_all_reduce_close_to_psum(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed import collective as C
        try:
            mesh = _mesh(dp=8)
            S = 64
            x = jnp.asarray(np.random.RandomState(0)
                            .randn(8, S).astype(np.float32))

            def f(v):
                with C.spmd_region({"data": "data"}):
                    t = paddle.Tensor(v[0])
                    out, res = C.quantized_all_reduce(
                        t, residual=paddle.Tensor(
                            jnp.zeros(S, jnp.float32)))
                    rs = C.quantized_reduce_scatter(paddle.Tensor(v[0]))
                    return out._value[None], res._value[None], \
                        rs._value[None]

            g = shard_map(f, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"))
            out, res, rs = g(x)
        finally:
            dist.set_mesh(None)
        exact = np.sum(np.asarray(x), axis=0)
        scale = np.abs(exact).max() + 1e-9
        assert np.abs(np.asarray(out)[0] - exact).max() / scale < 0.05
        assert np.abs(np.asarray(rs).reshape(-1) - exact).max() \
            / scale < 0.05
        # error feedback: the residual is the local quantization error,
        # bounded by one quantization step
        assert np.isfinite(np.asarray(res)).all()

    def test_comm_bytes_accounting_q8(self):
        """comm.bytes records the int8 WIRE payload (2 phases + scale
        exchanges), not the fp32 logical size — a 4x reduction."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed import collective as C
        was = obs.enabled()
        obs.enabled(True)
        try:
            mesh = _mesh(dp=8)
            reg = obs.get_registry()
            base = reg.counter("comm.bytes").value(op="all_reduce_q8",
                                                   axis="data")

            def f(v):
                with C.spmd_region({"data": "data"}):
                    return C.quantized_all_reduce(
                        paddle.Tensor(v[0]))._value[None]

            shard_map(f, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"))(jnp.ones((8, 64), jnp.float32))
            after = reg.counter("comm.bytes").value(op="all_reduce_q8",
                                                    axis="data")
            # 2 int8 phases of 64 elems + 2 f32 scale exchanges x 8 ranks
            assert after - base == 2 * 64 + 8 * 8
        finally:
            dist.set_mesh(None)
            obs.enabled(was)

    def test_quantized_convergence_smoke(self):
        """50-step convergence: loss curve with int8(error-feedback) grad
        comm stays within tolerance of the fp32-comm curve."""
        x, y = _data()

        def run(quant):
            paddle.set_flags({"quantized_grad_comm": quant})
            try:
                paddle.seed(11)
                m = MLP()
                opt = paddle.optimizer.Adam(0.05,
                                            parameters=m.parameters())
                step = fleet.DistTrainStep(
                    m, opt, lambda o, t: F.mse_loss(o, t), mesh=mesh,
                    weight_update_sharding=True)
                return [float(step(paddle.to_tensor(x),
                                   paddle.to_tensor(y)))
                        for _ in range(50)]
            finally:
                paddle.set_flags({"quantized_grad_comm": False})

        try:
            mesh = _mesh(dp=2, mp=4)
            fp = run(False)
            q8 = run(True)
        finally:
            dist.set_mesh(None)
        assert all(np.isfinite(v) for v in q8)
        assert q8[-1] < q8[0] * 0.2  # it really trains
        # trajectory tolerance: quantization noise, bounded by error
        # feedback — final losses agree within 20% relative (both tiny)
        assert abs(q8[-1] - fp[-1]) <= max(0.2 * abs(fp[0]), 0.05), \
            (fp[-1], q8[-1])


class TestGradBucketer:
    def test_layout_and_roundtrip(self):
        from paddle_tpu.distributed.collective import GradBucketer
        shapes = [(4, 3), (7,), (2, 2), (16,)]
        gb = GradBucketer(shapes, ["float32"] * 4, bucket_bytes=64,
                          pad_multiple=8)
        arrs = [jnp.asarray(np.random.RandomState(i)
                            .randn(*s).astype(np.float32))
                for i, s in enumerate(shapes)]
        flats = gb.flatten(arrs)
        assert all(f.shape[0] % 8 == 0 for f in flats)
        back = gb.unflatten(flats)
        for a, b in zip(arrs, back):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        # dtype grouping: mixed dtypes never share a bucket
        gb2 = GradBucketer([(4,), (4,)], ["float32", "bfloat16"])
        assert len(gb2.buckets) == 2

    def test_stable_layout_cache(self):
        from paddle_tpu.distributed.collective import bucketer_for
        a = bucketer_for([(4, 4)], ["float32"], 1024, 2)
        b = bucketer_for([(4, 4)], ["float32"], 1024, 2)
        assert a is b


class TestMetricsReportTrainingView:
    def test_optimizer_section_renders(self, tmp_path):
        import sys
        sys.path.insert(0, "tools")
        try:
            import metrics_report
        finally:
            sys.path.pop(0)
        lines = [
            {"name": "train.opt_update_seconds", "kind": "histogram",
             "labels": {"path": "fused"}, "value": 0.002, "count": 5,
             "p50": 0.002, "p99": 0.003},
            {"name": "train.opt_dispatches", "kind": "counter",
             "labels": {"path": "fused"}, "value": 12},
            {"name": "mem.opt_state_bytes", "kind": "gauge",
             "labels": {"scope": "per_replica"}, "value": 1024},
            {"name": "mem.opt_state_bytes", "kind": "gauge",
             "labels": {"scope": "global"}, "value": 8192},
            {"name": "comm.bytes", "kind": "counter",
             "labels": {"op": "reduce_scatter", "axis": "data"},
             "value": 4096},
            {"name": "comm.calls", "kind": "counter",
             "labels": {"op": "reduce_scatter", "axis": "data"},
             "value": 2},
        ]
        last = metrics_report.parse(json.dumps(r) for r in lines)
        text = metrics_report.render(last)
        assert "optimizer" in text
        assert "fused" in text
        assert "opt_state" in text
        assert "reduce_scatter" in text

"""Pipeline parallelism tests (parity model: the reference's
test_pipeline_parallel loss-parity methodology — pipelined training must
match the single-device run on identical data/init).

Runs on the 8-virtual-CPU-device mesh from conftest.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed.mesh import build_mesh, set_mesh, mesh_scope
from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel import (
    pipeline_spmd, PipelineTrainStep, _auto_split)
from paddle_tpu.distributed.fleet.meta_parallel import PipelineLayer
from paddle_tpu.jit import TrainStep


class Block(nn.Layer):
    def __init__(self, d):
        super().__init__()
        self.fc1 = nn.Linear(d, 2 * d)
        self.fc2 = nn.Linear(2 * d, d)

    def forward(self, x):
        return x + self.fc2(nn.functional.gelu(self.fc1(x)))


class Embed(nn.Layer):
    def __init__(self, d):
        super().__init__()
        self.proj = nn.Linear(d, d)

    def forward(self, x):
        return self.proj(x)


class Head(nn.Layer):
    def __init__(self, d):
        super().__init__()
        self.out = nn.Linear(d, d)

    def forward(self, x):
        return self.out(x)


def _make_pipe_model(d=16, blocks=4, stages=1):
    paddle.seed(42)
    return PipelineLayer(
        [Embed(d)] + [Block(d) for _ in range(blocks)] + [Head(d)],
        num_stages=stages)


def test_auto_split():
    m = _make_pipe_model(stages=2)
    layers = list(m.run_function)
    n_pre, n_post = _auto_split(layers, 2)
    assert (n_pre, n_post) == (1, 1)
    n_pre, n_post = _auto_split(layers, 4)
    assert (n_pre, n_post) == (1, 1)


def test_pipeline_spmd_matches_sequential():
    """The scanned shard_map schedule must equal running the S stage
    functions in order on each microbatch."""
    S, M, Bm, d = 4, 3, 2, 8
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(S, d, d).astype(np.float32) * 0.3)
    b = jnp.asarray(rng.randn(S, d).astype(np.float32) * 0.1)
    xm = jnp.asarray(rng.randn(M, Bm, d).astype(np.float32))

    def body(p, x, key):
        return jnp.tanh(x @ p[0] + p[1])

    mesh = build_mesh(pp=4)
    out = pipeline_spmd(body, [w, b], xm, num_stages=S, mesh=mesh,
                        use_remat=False)

    ref = xm
    for s in range(S):
        ref = jnp.tanh(ref @ w[s] + b[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_spmd_grad_matches_sequential():
    S, M, Bm, d = 2, 4, 2, 8
    rng = np.random.RandomState(1)
    w = jnp.asarray(rng.randn(S, d, d).astype(np.float32) * 0.3)
    xm = jnp.asarray(rng.randn(M, Bm, d).astype(np.float32))
    mesh = build_mesh(pp=2)

    def body(p, x, key):
        return jnp.tanh(x @ p[0])

    def loss_pipe(w):
        return jnp.sum(pipeline_spmd(body, [w], xm, num_stages=S,
                                     mesh=mesh, use_remat=True) ** 2)

    def loss_seq(w):
        y = xm
        for s in range(S):
            y = jnp.tanh(y @ w[s])
        return jnp.sum(y ** 2)

    gp = jax.grad(loss_pipe)(w)
    gs = jax.grad(loss_seq)(w)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gs),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("pp,mb", [(2, 2), (2, 4), (4, 2)])
def test_pipeline_train_loss_parity(pp, mb):
    """pp-stage pipelined training == single-device training, same init."""
    d, B, steps = 16, 8, 5
    rng = np.random.RandomState(3)
    x = rng.randn(B, d).astype(np.float32)
    y = rng.randn(B, d).astype(np.float32)
    loss_fn = lambda o, t: ((o - t) ** 2).mean()

    # single-device reference
    ref_model = _make_pipe_model(d=d)
    ref_opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=ref_model.parameters())
    ref_step = TrainStep(ref_model, ref_opt, loss_fn)
    ref_losses = [float(ref_step(paddle.to_tensor(x), paddle.to_tensor(y)))
                  for _ in range(steps)]

    # pipelined
    mesh = build_mesh(pp=pp)
    set_mesh(mesh)
    try:
        pipe_model = _make_pipe_model(d=d, stages=pp)
        pipe_opt = paddle.optimizer.AdamW(
            learning_rate=1e-2, parameters=pipe_model.parameters())
        pstep = PipelineTrainStep(pipe_model, pipe_opt, loss_fn,
                                  num_microbatches=mb, mesh=mesh)
        pipe_losses = [float(pstep(paddle.to_tensor(x), paddle.to_tensor(y)))
                       for _ in range(steps)]
    finally:
        set_mesh(None)

    np.testing.assert_allclose(pipe_losses, ref_losses, rtol=2e-4, atol=2e-5)
    # trained weights propagate back into the layer tensors via the
    # deferred sync triggered by state_dict (checkpoint path)
    pipe_model.state_dict()
    w_pipe = np.asarray(pipe_model.run_function[1].fc1.weight.numpy())
    w_ref = np.asarray(ref_model.run_function[1].fc1.weight.numpy())
    np.testing.assert_allclose(w_pipe, w_ref, rtol=2e-3, atol=2e-4)
    # optimizer accumulators observe the compiled step's state too
    sd = pipe_opt.state_dict()
    assert any("moment1" in k for k in sd), list(sd)[:4]
    ref_sd = ref_opt.state_dict()
    ref_m1 = [v for k, v in ref_sd.items() if "moment1" in k]
    pipe_m1 = [v for k, v in sd.items() if "moment1" in k]
    assert len(pipe_m1) == len(ref_m1)


@pytest.mark.parametrize("zero", [1, 3])
def test_pipeline_zero_sharding_loss_parity(zero):
    """pp=2 x dp=2 with ZeRO opt-state (stage 1) / param (stage 3)
    sharding over 'data' == plain single-device training: sharding is a
    layout decision, GSPMD's all-gather-at-use must not change math."""
    d, B, steps = 16, 8, 4
    rng = np.random.RandomState(7)
    x = rng.randn(B, d).astype(np.float32)
    y = rng.randn(B, d).astype(np.float32)
    loss_fn = lambda o, t: ((o - t) ** 2).mean()

    ref_model = _make_pipe_model(d=d)
    ref_opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=ref_model.parameters())
    ref_step = TrainStep(ref_model, ref_opt, loss_fn)
    ref_losses = [float(ref_step(paddle.to_tensor(x), paddle.to_tensor(y)))
                  for _ in range(steps)]

    mesh = build_mesh(dp=2, pp=2)
    set_mesh(mesh)
    try:
        pipe_model = _make_pipe_model(d=d, stages=2)
        pipe_opt = paddle.optimizer.AdamW(
            learning_rate=1e-2, parameters=pipe_model.parameters())
        pstep = PipelineTrainStep(pipe_model, pipe_opt, loss_fn,
                                  num_microbatches=2, mesh=mesh,
                                  zero_stage=zero)
        # params/opt-state actually sharded over 'data' when requested
        specs = [sh.spec for sh in pstep._stacked_zsh]
        assert any("data" in tuple(s) for s in specs), specs
        if zero >= 3:
            pspecs = [sh.spec for sh in pstep._stacked_sh]
            assert any("data" in tuple(s) for s in pspecs), pspecs
        losses = [float(pstep(paddle.to_tensor(x), paddle.to_tensor(y)))
                  for _ in range(steps)]
    finally:
        set_mesh(None)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=2e-5)


def test_pipeline_remat_activation_memory():
    """MEASURE the activation-memory claim of the remat schedule
    (pipeline_parallel.py module docstring): with per-tick
    rematerialization a stage holds only boundary activations of its
    in-flight microbatches, so the backward's temp memory must be
    substantially below the no-remat schedule, and the gap must WIDEN
    with more microbatches. Uses XLA's compile-time memory analysis
    (deterministic, works on the CPU mesh; same analysis the TPU bench
    reports on real HBM)."""
    S, L, d, Bm = 4, 4, 128, 2
    rng = np.random.RandomState(0)
    Ws = jnp.asarray(rng.randn(S, L, d, d).astype(np.float32) * 0.05)

    def body(p, x, key):
        for i in range(L):
            x = jnp.tanh(x @ p[0][i])
        return x

    def temp_bytes(pp, M, remat):
        mesh = build_mesh(pp=pp)
        set_mesh(mesh)
        try:
            x = jnp.asarray(rng.randn(M, Bm, d).astype(np.float32))
            W = Ws[:pp]

            def loss(params):
                out = pipeline_spmd(body, params, x, num_stages=pp,
                                    mesh=mesh, use_remat=remat)
                return jnp.sum(out ** 2)

            with mesh_scope(mesh):
                c = jax.jit(jax.grad(loss)).lower([W]).compile()
            return c.memory_analysis().temp_size_in_bytes
        finally:
            set_mesh(None)

    rows = []
    for pp in (1, 4):
        for M in (8, 16):
            on = temp_bytes(pp, M, True)
            off = temp_bytes(pp, M, False)
            rows.append((pp, M, on, off))
    print("\npp  M   temp(remat)  temp(no-remat)  ratio")
    for pp, M, on, off in rows:
        print(f"{pp:2d} {M:3d}  {on/1e3:9.1f}KB  {off/1e3:11.1f}KB  "
              f"{on/off:.2f}")
    # the claim concerns the scanned schedule (pp > 1); the pp=1
    # fallback unrolls microbatches and XLA schedules them equivalently
    for pp, M, on, off in rows:
        if pp > 1:
            assert on < 0.75 * off, (pp, M, on, off)
    # the remat saving must grow with microbatch count: no-remat stores
    # per-tick activations of the whole schedule, remat only boundaries
    (_, _, on8, off8), (_, _, on16, off16) = rows[2], rows[3]
    assert (off16 - on16) > (off8 - on8), rows


def test_pipeline_with_grad_scaler_parity():
    """GradScaler composed with pp: scale/unscale/skip-on-overflow runs
    inside the compiled pipeline step. With finite grads the math must
    equal the scaler-less run exactly."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet

    d, B, steps = 16, 8, 4
    rng = np.random.RandomState(9)
    x = rng.randn(B, d).astype(np.float32)
    y = rng.randn(B, d).astype(np.float32)
    loss_fn = lambda o, t: ((o - t) ** 2).mean()

    def run(with_scaler):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 2,
                                   "mp_degree": 1}
        strategy.pipeline_configs["accumulate_steps"] = 2
        fleet.init(is_collective=True, strategy=strategy)
        try:
            model = fleet.distributed_model(_make_pipe_model(d=d, stages=2))
            opt = paddle.optimizer.AdamW(1e-2,
                                         parameters=model.parameters())
            scaler = (paddle.amp.GradScaler(init_loss_scaling=2.0 ** 10)
                      if with_scaler else None)
            out = []
            for _ in range(steps):
                out.append(float(model.train_batch(
                    [paddle.to_tensor(x), paddle.to_tensor(y)],
                    optimizer=opt, scaler=scaler, loss_fn=loss_fn)))
            return out
        finally:
            set_mesh(None)

    plain = run(False)
    scaled = run(True)
    np.testing.assert_allclose(scaled, plain, rtol=1e-5, atol=1e-6)


def test_pipeline_times_context_parallel_loss_parity():
    """pp=2 x cp=2 x dp=2: the pipeline runs with sequence-sharded
    activations (manual over {'stage','context'}) and ring attention
    executes its local kernel inside the stage body. Must match the
    single-device model exactly (regression: the nested-shard_map path
    used to produce silently wrong ring gradients)."""
    from paddle_tpu.kernels.ring_attention import ring_flash_attention

    d, H, B, T, steps = 16, 2, 8, 8, 4

    class AttnBlock(nn.Layer):
        def __init__(self):
            super().__init__()
            self.qkv = nn.Linear(d, 3 * d)
            self.o = nn.Linear(d, d)

        def forward(self, x):
            Bs, Ts, _ = x.shape
            qkv = self.qkv(x).reshape([Bs, Ts, 3, H, d // H])
            att = ring_flash_attention(qkv[:, :, 0], qkv[:, :, 1],
                                       qkv[:, :, 2], is_causal=True)
            return x + self.o(att.reshape([Bs, Ts, d]))

    class SeqEmbed(nn.Layer):
        def __init__(self):
            super().__init__()
            self.proj = nn.Linear(d, d)

        def forward(self, x):
            return self.proj(x)

    def make(stages):
        paddle.seed(11)
        return PipelineLayer([SeqEmbed()] + [AttnBlock() for _ in range(2)],
                             num_stages=stages)

    rng = np.random.RandomState(5)
    x = rng.randn(B, T, d).astype(np.float32)
    y = rng.randn(B, T, d).astype(np.float32)
    loss_fn = lambda o, t: ((o - t) ** 2).mean()

    ref = make(1)
    ref_opt = paddle.optimizer.AdamW(1e-2, parameters=ref.parameters())
    rstep = TrainStep(ref, ref_opt, loss_fn)
    ref_losses = [float(rstep(paddle.to_tensor(x), paddle.to_tensor(y)))
                  for _ in range(steps)]

    mesh = build_mesh(dp=2, pp=2, cp=2)
    set_mesh(mesh)
    try:
        pipe = make(2)
        popt = paddle.optimizer.AdamW(1e-2, parameters=pipe.parameters())
        pstep = PipelineTrainStep(pipe, popt, loss_fn,
                                  num_microbatches=2, mesh=mesh)
        losses = [float(pstep(paddle.to_tensor(x), paddle.to_tensor(y)))
                  for _ in range(steps)]
    finally:
        set_mesh(None)
    np.testing.assert_allclose(losses, ref_losses, rtol=5e-4, atol=5e-5)


def test_pipeline_times_tensor_parallel():
    """pp=2 × mp=2 hybrid: TP-tagged params inside the staged body."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.meta_parallel.mp_layers import (
        ColumnParallelLinear, RowParallelLinear)

    d, B, steps = 16, 8, 4

    class TPBlock(nn.Layer):
        def __init__(self):
            super().__init__()
            self.up = ColumnParallelLinear(d, 2 * d, gather_output=False)
            self.down = RowParallelLinear(2 * d, d, input_is_parallel=True)

        def forward(self, x):
            return x + self.down(nn.functional.gelu(self.up(x)))

    def make(stages):
        paddle.seed(7)
        return PipelineLayer([Embed(d)] + [TPBlock() for _ in range(4)]
                             + [Head(d)], num_stages=stages)

    rng = np.random.RandomState(5)
    x = rng.randn(B, d).astype(np.float32)
    y = rng.randn(B, d).astype(np.float32)
    loss_fn = lambda o, t: ((o - t) ** 2).mean()

    ref_model = make(1)
    ref_opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=ref_model.parameters())
    ref_step = TrainStep(ref_model, ref_opt, loss_fn)
    ref_losses = [float(ref_step(paddle.to_tensor(x), paddle.to_tensor(y)))
                  for _ in range(steps)]

    strat = fleet.DistributedStrategy()
    strat.hybrid_configs = {"dp_degree": 1, "pp_degree": 2, "mp_degree": 2}
    strat.pipeline_configs["accumulate_steps"] = 2
    fleet.init(is_collective=True, strategy=strat)
    try:
        model = make(2)
        dm = fleet.distributed_model(model)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=model.parameters())
        losses = [float(dm.train_batch(
            [paddle.to_tensor(x), paddle.to_tensor(y)],
            optimizer=opt, loss_fn=loss_fn)) for _ in range(steps)]
    finally:
        set_mesh(None)

    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=2e-5)


def test_pipeline_opt_state_seeding_resume():
    """Rebuilding a PipelineTrainStep from a model+optimizer whose
    accumulators hold trained state (checkpoint-resume shape) must
    continue the loss curve exactly — moments seed the compiled step."""
    d, B = 16, 8
    rng = np.random.RandomState(11)
    x = rng.randn(B, d).astype(np.float32)
    y = rng.randn(B, d).astype(np.float32)
    loss_fn = lambda o, t: ((o - t) ** 2).mean()

    mesh = build_mesh(pp=2)
    set_mesh(mesh)
    try:
        model = _make_pipe_model(d=d, stages=2)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=model.parameters())
        step = PipelineTrainStep(model, opt, loss_fn, num_microbatches=2,
                                 mesh=mesh)
        for _ in range(3):
            step(paddle.to_tensor(x), paddle.to_tensor(y))
        cont = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
                for _ in range(2)]
    finally:
        set_mesh(None)

    # fresh run to the same 3-step point, then rebuild the step
    mesh = build_mesh(pp=2)
    set_mesh(mesh)
    try:
        model2 = _make_pipe_model(d=d, stages=2)
        opt2 = paddle.optimizer.AdamW(learning_rate=1e-2,
                                      parameters=model2.parameters())
        s1 = PipelineTrainStep(model2, opt2, loss_fn, num_microbatches=2,
                               mesh=mesh)
        for _ in range(3):
            s1(paddle.to_tensor(x), paddle.to_tensor(y))
        # flush into layer tensors + accumulators (checkpoint), rebuild
        model2.state_dict(); opt2.state_dict()
        s2 = PipelineTrainStep(model2, opt2, loss_fn, num_microbatches=2,
                               mesh=mesh)
        resumed = [float(s2(paddle.to_tensor(x), paddle.to_tensor(y)))
                   for _ in range(2)]
    finally:
        set_mesh(None)

    np.testing.assert_allclose(resumed, cont, rtol=1e-5, atol=1e-6)


def test_pipeline_set_state_dict_invalidates():
    """Loading a checkpoint AFTER the compiled step exists must be picked
    up by the next step (regression: stale device-side stacked params)."""
    d, B = 16, 8
    rng = np.random.RandomState(21)
    x = rng.randn(B, d).astype(np.float32)
    y = rng.randn(B, d).astype(np.float32)
    loss_fn = lambda o, t: ((o - t) ** 2).mean()

    mesh = build_mesh(pp=2)
    set_mesh(mesh)
    try:
        model = _make_pipe_model(d=d, stages=2)
        snapshot = {k: np.array(v.numpy())
                    for k, v in model.state_dict().items()}
        opt = paddle.optimizer.AdamW(learning_rate=5e-2,
                                     parameters=model.parameters())
        step = PipelineTrainStep(model, opt, loss_fn, num_microbatches=2,
                                 mesh=mesh)
        l0 = float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
        for _ in range(3):
            step(paddle.to_tensor(x), paddle.to_tensor(y))
        # roll back to the initial weights — next step must see them
        model.set_state_dict({k: paddle.to_tensor(v)
                              for k, v in snapshot.items()})
        l_re = float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
    finally:
        set_mesh(None)
    # first loss from the same initial weights (opt moments differ, but
    # the LOSS is computed before the update, so it must match exactly)
    np.testing.assert_allclose(l_re, l0, rtol=1e-5)


@pytest.mark.parametrize("pp,virtual,mb", [(2, 2, 4), (2, 2, 2),
                                           (2, 2, 3), (4, 2, 4)])
def test_interleaved_virtual_stages_loss_parity(pp, virtual, mb):
    """Interleaved schedule (V chunks per device, reference parity:
    PipelineParallelWithInterleave) must train bit-close to the
    single-device reference, including M not divisible by S (wave
    injection skips)."""
    blocks = pp * virtual  # one layer per chunk
    d, B, steps = 16, 12, 4
    rng = np.random.RandomState(3)
    x = rng.randn(B, d).astype(np.float32)
    y = rng.randn(B, d).astype(np.float32)
    loss_fn = lambda o, t: ((o - t) ** 2).mean()

    ref_model = _make_pipe_model(d=d, blocks=blocks)
    ref_opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=ref_model.parameters())
    ref_step = TrainStep(ref_model, ref_opt, loss_fn)
    ref_losses = [float(ref_step(paddle.to_tensor(x), paddle.to_tensor(y)))
                  for _ in range(steps)]

    mesh = build_mesh(pp=pp)
    set_mesh(mesh)
    try:
        pipe_model = _make_pipe_model(d=d, blocks=blocks, stages=pp)
        pipe_opt = paddle.optimizer.AdamW(
            learning_rate=1e-2, parameters=pipe_model.parameters())
        pstep = PipelineTrainStep(pipe_model, pipe_opt, loss_fn,
                                  num_microbatches=mb, mesh=mesh,
                                  num_virtual_stages=virtual)
        pipe_losses = [float(pstep(paddle.to_tensor(x),
                                   paddle.to_tensor(y)))
                       for _ in range(steps)]
    finally:
        set_mesh(None)
    np.testing.assert_allclose(pipe_losses, ref_losses, rtol=2e-4,
                               atol=2e-5)
    # sync-back: chunk weights restored to per-layer tensors in ring order
    pipe_model.state_dict()
    w_pipe = np.asarray(pipe_model.run_function[2].fc1.weight.numpy())
    assert np.isfinite(w_pipe).all()


@pytest.mark.parametrize("tie", [False, True])
def test_llama_pipe_parity_with_monolithic(tie):
    """LlamaForCausalLMPipe (ecosystem parity: PaddleNLP
    LlamaForCausalLMPipe) = same math as the monolithic model: copy the
    pipe's weights into LlamaForCausalLM and the first-step loss must
    match the pipelined train_batch loss. tie=True exercises the shared
    embedding/lm-head parameter across the first and last stages (the
    SharedLayerDesc role)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.mesh import set_mesh
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaForCausalLMPipe,
                                   LlamaPretrainingCriterion)

    cfg = LlamaConfig.tiny(tensor_parallel=False, tie_word_embeddings=tie)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 2,
                               "mp_degree": 1}
    strategy.pipeline_configs["accumulate_steps"] = 2
    fleet.init(is_collective=True, strategy=strategy)
    try:
        paddle.seed(0)
        pipe = fleet.distributed_model(
            LlamaForCausalLMPipe(cfg, num_stages=2))
        opt = paddle.optimizer.AdamW(1e-3, parameters=pipe.parameters())
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(rng.randint(1, cfg.vocab_size, (4, 32)))
        crit = LlamaPretrainingCriterion(cfg)
        psd = {k: np.array(v.numpy())
               for k, v in pipe.state_dict().items()}
        l0 = float(pipe.train_batch([ids, ids], optimizer=opt,
                                    loss_fn=lambda lg, lb: crit(lg, lb)))
        l1 = float(pipe.train_batch([ids, ids]))
        assert np.isfinite(l0) and l1 < l0

        # remap pipe keys -> monolithic keys
        L = cfg.num_hidden_layers
        mono = LlamaForCausalLM(cfg)
        remap = {}
        for k, v in psd.items():
            parts = k.split(".")
            idx = int(parts[1])
            rest = ".".join(parts[2:])
            if idx == 0:
                remap["llama." + rest] = v  # embed_tokens.*
            elif idx == L + 1:
                if rest.startswith("norm."):
                    remap["llama." + rest] = v
                else:
                    remap[rest] = v         # lm_head.*
            else:
                remap[f"llama.layers.{idx - 1}." + rest.replace(
                    "layer.", "", 1)] = v
        mono.set_state_dict({k: paddle.to_tensor(v)
                             for k, v in remap.items()})
        mono.eval()
        logits = mono(ids)
        logits = logits[0] if isinstance(logits, tuple) else logits
        ref = float(crit(logits, ids))
        np.testing.assert_allclose(l0, ref, rtol=2e-5)
    finally:
        set_mesh(None)

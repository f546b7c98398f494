"""chip_smoke.py: the serve loop and the train step on the chip, once.

Drives the two hot paths through the entry points a user calls, at the
published widths of Llama-2-7B (hidden 4096, 32 heads of 128, vocab
32000, bf16) with the depth cut to what one 16 GB chip holds:

- serve: ``serving.Router`` over one model, which builds a
  ``ContinuousBatchingPredictor`` on a ``PagedKVPool`` and decodes
  through the paged-attention Pallas kernels. The same requests then go
  through the plain XLA attention path of the same model (kernels off,
  fixed block tables, no prefix cache), and the two are compared.
- train: ``fleet.HybridTrainStep`` on a one-device plan, AdamW with f32
  master weights, a repeated batch of 2048-token sequences. A second
  build of the step with ``FLAGS_use_pallas_kernels`` off takes the same
  weights and batch, and step-one loss and gradient norm are compared.

With ``--chips 4`` it runs instead the three paths that exist only
across chips (tensor-parallel serving, router replicas each on its own
chip, the ``data=2,model=2`` ZeRO-3 train step), each against its
one-device twin, and no one-chip phase.

It measures nothing: no rate, no utilisation. Every line it prints is
one JSON object; the last is ``{"ok": ..., "device": {...}}``. It exits
non-zero when ``jax.devices()[0].platform`` is not ``"tpu"``, when a
phase raises, or when a check fails. One process, no children.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

# Comparison thresholds. bf16 carries 8 bits of mantissa and the logits
# themselves are bf16, so two correct paths that round in different
# places (the flash kernel keeps an f32 accumulator across KV blocks,
# XLA casts the probabilities to bf16 before P.V) differ by whole ulps
# of the largest logit after a stack of layers: 4 ulps were seen at
# depth 8 on the chip. The bound is 16 ulps of the largest reference
# logit; a wrong mask or a missed page moves logits by their own
# spread, an order of magnitude more.
LOGITS_TOL_BF16 = 16 * 2.0 ** -8    # times max(1, max|reference logit|)
LOGITS_TOL_F32 = 2e-4
# Greedy tokens are compared while the two arms still share a history
# (after a first disagreement the continuations are different
# sequences). Random weights give near-flat logits, so a bf16 ulp can
# flip an argmax; a broken kernel agrees on about 1/vocab of the tokens.
TOKEN_AGREEMENT_MIN = 0.80
# Step-one loss and global gradient norm, kernel path against XLA path.
LOSS_RTOL_BF16 = 2e-2
GRAD_NORM_RTOL_BF16 = 5e-2
LOSS_RTOL_F32 = 1e-4
GRAD_NORM_RTOL_F32 = 1e-3

# Decoder layers kept (widths are never cut). One layer is 202 M
# parameters, embedding plus head 262 M. Serving holds bf16 weights and
# the KV pool; training holds 16 bytes a parameter (bf16 weights and
# gradients, f32 master and two moments) beside the activations of a
# 2048-token batch.
SERVE_DEPTH = 8
TRAIN_DEPTH = 2
REPLICA_DEPTH = 2

SERVE_KERNELS = ("_paged_kernel",)
TRAIN_KERNELS = ("_fwd_kernel", "_bwd_dkdv_kernel", "_bwd_dq_kernel",
                 "_rms_kernel")


def device_record():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _mem(device=None):
    import jax
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


def _all_in_use(mems):
    """Every device holds something. A backend that reports no memory
    statistics (the CPU) cannot fail this; a TPU must report them."""
    import jax
    if not any(mems):
        return jax.devices()[0].platform != "tpu"
    return all(m.get("bytes_in_use", 0) > 0 for m in mems)


def _release():
    """Drop what the finished phase left on the device."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


def _tols(dtype):
    if dtype == "bfloat16":
        return LOGITS_TOL_BF16, LOSS_RTOL_BF16, GRAD_NORM_RTOL_BF16
    return LOGITS_TOL_F32, LOSS_RTOL_F32, GRAD_NORM_RTOL_F32


def _kernel_flags(on):
    from paddle_tpu.framework.flags import set_flags
    set_flags({"use_pallas_kernels": bool(on)})


def _fallbacks():
    from paddle_tpu.observability import metrics
    return int(sum(s.value for s in
                   metrics.counter("kernels.pallas_fallbacks").series()))


def _decode_kernels():
    """{kernel: count} of `kernels.paged_decode`: which kernel the
    decode programs traced so far attend through."""
    from paddle_tpu.observability import metrics
    return {s.labels["kernel"]: int(s.value) for s in
            metrics.counter("kernels.paged_decode").samples() if s.value}


def _decode_kernels_since(before):
    return {k: n - before.get(k, 0) for k, n in _decode_kernels().items()
            if n > before.get(k, 0)}


def _kernels_in(text, names):
    return {n: f'kernel_name = "{n}"' in text for n in names}


def build_model(config, seed, dtype):
    """Llama at `config`, weights drawn from `seed` by the package's own
    initialisers (no download), cast to `dtype`."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    paddle.seed(seed)
    model = LlamaForCausalLM(config)
    if dtype == "bfloat16":
        model.bfloat16()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    return model, n_params


def llama_config(depth, tensor_parallel=False):
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig.llama2_7b(num_hidden_layers=depth,
                                 tensor_parallel=tensor_parallel)


def _shape_record(config, dtype):
    """The widths and the depth a phase ran at, for its record."""
    return {"depth": config.num_hidden_layers,
            "hidden": config.hidden_size,
            "heads": config.num_attention_heads,
            "vocab": config.vocab_size, "dtype": dtype}


def make_requests(seed, vocab, page):
    """Two waves of prompts. Wave one: three lengths in three prompt
    buckets (32, 64, 128). Wave two, sent once wave one has finished so
    that the prefix cache holds it: a prompt that extends wave one's
    partial trailing page (copy-on-write, then a suffix prefill), one
    that shares three whole pages of another and then diverges (suffix
    prefill over cached pages), and an exact repeat (no forward pass)."""
    rng = np.random.RandomState(seed)

    def toks(n):
        return rng.randint(2, vocab, (n,)).tolist()

    wave1 = [toks(page + 4), toks(3 * page + 2), toks(6 * page + 4)]
    wave2 = [wave1[0] + toks(7), wave1[2][:3 * page] + toks(10),
             list(wave1[1])]
    return wave1, wave2


def _matched_agreement(got, want):
    """Tokens compared while both arms share a history, and how many of
    those agree: per request, positions up to and including the first
    disagreement."""
    compared = agreed = 0
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            compared += 1
            if a != b:
                break
            agreed += 1
    return agreed, compared


def _first_step_logits(model, prompts, pad_to):
    """Last-prompt-position logits of one causal forward over the
    right-padded prompts, through whichever attention path the flags
    select at trace time."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.jit.bridge import functionalize
    ids = np.zeros((len(prompts), pad_to), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
    last = jnp.asarray([len(p) - 1 for p in prompts], jnp.int32)
    pure, p_vals, b_vals, _, _ = functionalize(model, training=False)

    def fwd(p, b, x):
        logits, _, _ = pure(p, b, jax.random.key(0), x)
        lg = logits._value
        return jnp.take_along_axis(lg, last[:, None, None], axis=1)[:, 0]

    out = jax.jit(fwd)(p_vals, b_vals, jnp.asarray(ids))
    return np.asarray(out.astype(jnp.float32))


def serve_phase(*, config, seed=0, dtype="bfloat16", max_batch_size=8,
                page_size=16, max_seq_len=1024, new_tokens=32,
                require_kernels=True, timeout_s=900.0):
    """Router -> ContinuousBatchingPredictor -> PagedKVPool -> paged
    kernels with the predictor's defaults, then the same requests on
    the plain XLA path; returns the phase record and raises on a failed
    check."""
    from paddle_tpu.inference import ContinuousBatchingPredictor
    from paddle_tpu.serving import Router

    logits_tol = _tols(dtype)[0]
    rec = {"phase": "serve", **_shape_record(config, dtype)}
    t0 = time.perf_counter()
    model, n_params = build_model(config, seed, dtype)
    itemsize = 2 if dtype == "bfloat16" else 4
    rec["params"] = n_params
    rec["weight_bytes"] = n_params * itemsize
    rec["build_seconds"] = round(time.perf_counter() - t0, 2)
    wave1, wave2 = make_requests(seed, config.vocab_size, page_size)
    prompts = wave1 + wave2
    geometry = dict(max_batch_size=max_batch_size, page_size=page_size,
                    max_seq_len=max_seq_len)

    # -- kernel arm: the path a user gets ------------------------------
    _kernel_flags(True)
    fb0, dk0 = _fallbacks(), _decode_kernels()
    t0 = time.perf_counter()
    router = Router([model], **geometry)
    pred = router.replicas[0].predictor
    try:
        handles = [router.submit(p, max_new_tokens=new_tokens)
                   for p in wave1]
        got = [h.result(timeout=timeout_s) for h in handles]
        handles2 = [router.submit(p, max_new_tokens=new_tokens)
                    for p in wave2]
        got += [h.result(timeout=timeout_s) for h in handles2]
        statuses = [h.status for h in handles + handles2]
    finally:
        router.shutdown()
    rec["serve_seconds_incl_compile"] = round(time.perf_counter() - t0, 2)
    rec["requests"] = len(prompts)
    rec["completed"] = sum(s == "ok" for s in statuses)
    rec["statuses"] = statuses
    rec["tokens_generated"] = sum(len(g) for g in got)
    rec["decode_kernels_traced"] = _decode_kernels_since(dk0)
    rec["pallas_fallbacks"] = _fallbacks() - fb0
    rec["kv_pool_bytes"] = int(sum(a.nbytes for a in pred.pool.k + pred.pool.v))
    stats = dict(pred.stats)
    rec["prefix_hits"] = stats["prefix_hits"]
    rec["prefix_partial_hits"] = stats["prefix_partial_hits"]
    rec["pages_reused"] = stats["pages_reused"]
    rec["decode_steps"] = stats["decode_steps"]
    text = pred.lower_decode_step().as_text()
    rec["decode_has_tpu_custom_call"] = "tpu_custom_call" in text
    rec["decode_kernels"] = _kernels_in(text, SERVE_KERNELS)
    del text
    kernel_logits = _first_step_logits(model, wave1, 8 * page_size)
    rec["mem_after_kernel_arm"] = _mem()
    del router, pred, handles, handles2
    _release()

    # -- oracle arm: plain XLA attention, fixed tables, no prefix cache -
    _kernel_flags(False)
    try:
        t0 = time.perf_counter()
        oracle = ContinuousBatchingPredictor(
            model, enable_prefix_cache=False, **geometry)
        want = oracle.generate(prompts, max_new_tokens=new_tokens)
        rec["oracle_seconds_incl_compile"] = round(
            time.perf_counter() - t0, 2)
        rec["oracle_has_tpu_custom_call"] = "tpu_custom_call" in \
            oracle.lower_decode_step().as_text()
        oracle_logits = _first_step_logits(model, wave1, 8 * page_size)
    finally:
        _kernel_flags(True)
    del oracle

    err = float(np.max(np.abs(kernel_logits - oracle_logits)))
    bound = logits_tol * max(1.0, float(np.max(np.abs(oracle_logits))))
    rec["first_step_logits_max_err"] = err
    rec["first_step_logits_mean_err"] = float(
        np.mean(np.abs(kernel_logits - oracle_logits)))
    rec["first_step_logits_bound"] = bound
    rec["logits_finite"] = bool(np.isfinite(kernel_logits).all())
    agreed, compared = _matched_agreement(got, want)
    rec["tokens_compared"] = compared
    rec["tokens_agreed"] = agreed
    rec["token_agreement"] = agreed / max(compared, 1)
    rec["token_agreement_min"] = TOKEN_AGREEMENT_MIN
    rec["first_token_agreement"] = sum(
        g[:1] == w[:1] for g, w in zip(got, want)) / len(got)
    rec["mem_at_end"] = _mem()

    checks = {
        "all_completed": rec["completed"] == rec["requests"],
        "all_tokens": all(len(g) == new_tokens for g in got),
        "tokens_in_vocab": all(0 <= t < config.vocab_size
                               for g in got for t in g),
        "prefix_cache_exercised": rec["prefix_hits"] >= 1
        and rec["prefix_partial_hits"] >= 1,
        "logits_finite": rec["logits_finite"],
        "logits_within_tol": err <= bound,
        "token_agreement": rec["token_agreement"] >= TOKEN_AGREEMENT_MIN,
        "oracle_is_plain_xla": not rec["oracle_has_tpu_custom_call"],
    }
    if require_kernels:
        checks["block_table_kernel"] = \
            set(rec["decode_kernels_traced"]) == {"paged_attention"}
        checks["no_fallbacks"] = rec["pallas_fallbacks"] == 0
        checks["kernel_in_decode"] = rec["decode_has_tpu_custom_call"] \
            and all(rec["decode_kernels"].values())
    rec["checks"] = checks
    rec["ok"] = all(checks.values())
    del model
    _release()
    return rec


def _eager_rms_vjp(hidden, dtype, seed):
    """fused_rms_norm under an eager jax.vjp, outside any jit: the call
    the eager tape makes for every op, and where the one recorded chip
    attempt on the previous installation died. Returns the largest
    deviation of (out, dx, dw) from the XLA formulas."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.norm import fused_rms_norm
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(64, hidden), dtype)
    w = jnp.asarray(1.0 + 0.1 * rng.randn(hidden), dtype)

    def both():
        out, pull = jax.vjp(lambda a, g: fused_rms_norm(a, g, 1e-5), x, w)
        return (out,) + pull(jnp.ones_like(out))

    _kernel_flags(True)
    got = both()
    _kernel_flags(False)
    try:
        want = both()
    finally:
        _kernel_flags(True)
    return max(float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                     - r.astype(jnp.float32))
                             / (1.0 + jnp.abs(r.astype(jnp.float32)))))
               for g, r in zip(got, want))


def _train_arm(config, seed, dtype, x, y, steps, kernels_on, init=None,
               spec="data=1", zero_stage=0):
    """One build of the step: model from `seed` (or `init`, a list of
    host arrays), AdamW, HybridTrainStep on `spec`; returns the arm's
    record, the initial weights and the step object."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet import (HybridParallelPlan,
                                              HybridTrainStep)
    from paddle_tpu.distributed.mesh import mesh_scope
    from paddle_tpu.models import LlamaPretrainingCriterion
    from paddle_tpu.observability import metrics

    _kernel_flags(kernels_on)
    plan = HybridParallelPlan.from_spec(spec, zero_stage=zero_stage)
    mesh = plan.build_mesh()
    # TP-tagged layers read the process mesh when they are built; the
    # step carries its own afterwards
    with mesh_scope(mesh):
        model, n_params = build_model(config, seed, dtype)
        params = list(model.parameters())
        if init is None:
            init = [np.asarray(p._value) for p in params]
        else:
            for p, a in zip(params, init):
                p._value = jnp.asarray(a)
        crit = LlamaPretrainingCriterion(config)
        opt = paddle.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.1,
                                     parameters=model.parameters())
        step = HybridTrainStep(model, opt, lambda lg, lb: crit(lg, lb),
                               plan=plan, mesh=mesh)
    arm = {"kernels": bool(kernels_on), "params": n_params,
           "topology": plan.topology(), "zero_stage": zero_stage}
    text = step.lower(x, y).as_text()
    arm["has_tpu_custom_call"] = "tpu_custom_call" in text
    arm["kernels_in_step"] = _kernels_in(text, TRAIN_KERNELS)
    del text
    gnorm = metrics.gauge("train.grad_norm")
    losses, gnorms = [], []
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, y)
        losses.append(float(loss))
        jax.effects_barrier()
        gnorms.append(float(gnorm.value()))
        if len(losses) == 1:
            arm["first_step_seconds_incl_compile"] = round(
                time.perf_counter() - t0, 2)
    arm["losses"] = losses
    arm["grad_norms"] = gnorms
    moved = step.inner.params_with_grad()
    arm["params_with_grad"] = sum(moved)
    arm["param_tensors"] = len(moved)
    arm["footprint"] = step.footprint()
    arm["mem"] = _mem()
    return arm, init, step


def train_phase(*, config, seed=0, dtype="bfloat16", batch=2, seq=2048,
                steps=4, require_kernels=True):
    """HybridTrainStep on one device, kernels on, then the XLA build on
    the same weights and batch; returns the phase record."""
    import paddle_tpu as paddle
    _, loss_rtol, gnorm_rtol = _tols(dtype)
    rec = {"phase": "train", **_shape_record(config, dtype),
           "batch": batch, "seq": seq, "steps": steps}
    rng = np.random.RandomState(seed + 1)
    ids = rng.randint(0, config.vocab_size, (batch, seq)).astype(np.int32)
    x = paddle.to_tensor(ids)
    y = paddle.to_tensor(ids)

    rec["eager_rms_vjp_max_rel_err"] = _eager_rms_vjp(
        config.hidden_size, dtype, seed)
    arm, init, step = _train_arm(config, seed, dtype, x, y, steps, True)
    rec["params"] = arm["params"]
    # bf16 weights + f32 master + two f32 moments + bf16 gradients
    rec["state_bytes_from_shapes"] = arm["params"] * (2 + 4 + 4 + 4 + 2) \
        if dtype == "bfloat16" else arm["params"] * (4 + 4 + 4 + 4)
    rec["kernel_arm"] = arm
    del step
    _release()
    try:
        ref, _, step = _train_arm(config, seed, dtype, x, y, 1, False,
                                  init=init)
    finally:
        _kernel_flags(True)
    rec["xla_arm"] = ref
    del step, init
    _release()

    l0, r0 = arm["losses"][0], ref["losses"][0]
    g0, rg0 = arm["grad_norms"][0], ref["grad_norms"][0]
    rec["loss_rel_err"] = abs(l0 - r0) / max(abs(r0), 1e-12)
    rec["grad_norm_rel_err"] = abs(g0 - rg0) / max(abs(rg0), 1e-12)
    rec["loss_rtol"] = loss_rtol
    rec["grad_norm_rtol"] = gnorm_rtol
    checks = {
        "eager_rms_vjp": rec["eager_rms_vjp_max_rel_err"] <= _tols(dtype)[0],
        "loss_finite": bool(np.isfinite(arm["losses"]).all()),
        "loss_falling": arm["losses"][-1] < arm["losses"][0],
        "grad_norm_finite": bool(np.isfinite(arm["grad_norms"]).all())
        and g0 > 0,
        "all_params_receive_grad":
            arm["params_with_grad"] == arm["param_tensors"],
        "loss_matches_xla": rec["loss_rel_err"] <= loss_rtol,
        "grad_norm_matches_xla": rec["grad_norm_rel_err"] <= gnorm_rtol,
        "xla_arm_is_plain_xla": not ref["has_tpu_custom_call"],
    }
    if require_kernels:
        checks["kernels_in_step"] = all(arm["kernels_in_step"].values())
    rec["checks"] = checks
    rec["ok"] = all(checks.values())
    return rec


# ---------------------------------------------------------------- 4 chips --

def tp_serve_phase(*, config, seed=0, dtype="bfloat16", tp=4,
                   max_batch_size=8, page_size=16, max_seq_len=1024,
                   new_tokens=32, require_kernels=True):
    """ContinuousBatchingPredictor(tp_degree=tp) against tp_degree=1 in
    the same process: same requests, tokens compared, and the compiled
    decode step must carry collectives over the model axis."""
    import jax
    from paddle_tpu.inference import ContinuousBatchingPredictor
    rec = {"phase": "tp_serve", "tp": tp, **_shape_record(config, dtype)}
    model, n_params = build_model(config, seed, dtype)
    rec["params"] = n_params
    wave1, wave2 = make_requests(seed, config.vocab_size, page_size)
    prompts = wave1 + wave2
    geometry = dict(max_batch_size=max_batch_size, page_size=page_size,
                    max_seq_len=max_seq_len)
    _kernel_flags(True)
    fb0, dk0 = _fallbacks(), _decode_kernels()
    one = ContinuousBatchingPredictor(model, tp_degree=1, **geometry)
    want = one.generate(prompts, max_new_tokens=new_tokens)
    del one
    _release()
    t0 = time.perf_counter()
    cb = ContinuousBatchingPredictor(model, tp_degree=tp, **geometry)
    got = cb.generate(prompts, max_new_tokens=new_tokens)
    rec["tp_seconds_incl_compile"] = round(time.perf_counter() - t0, 2)
    rec["decode_kernels_traced"] = _decode_kernels_since(dk0)
    rec["pallas_fallbacks"] = _fallbacks() - fb0
    rec["tp_devices"] = [d.id for d in cb.tp_devices]
    rec["kv_shards"] = tp if cb.pool.kv_sharding is not None else 1
    compiled = cb.lower_decode_step().compile()
    text = compiled.as_text()
    rec["decode_collectives"] = {
        op: text.count(f" {op}(") + text.count(f" {op}-start(")
        for op in ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute", "all-to-all")}
    rec["decode_has_tpu_custom_call"] = "tpu_custom_call" in text
    ma = compiled.memory_analysis()
    rec["decode_bytes_per_device"] = {
        "arguments": int(ma.argument_size_in_bytes),
        "temporaries": int(ma.temp_size_in_bytes)}
    del text, compiled
    rec["mem_per_device"] = [_mem(d) for d in jax.devices()[:tp]]
    agreed, compared = _matched_agreement(got, want)
    rec["tokens_compared"] = compared
    rec["tokens_agreed"] = agreed
    rec["token_agreement"] = agreed / max(compared, 1)
    checks = {
        "all_tokens": all(len(g) == new_tokens for g in got),
        "token_agreement": rec["token_agreement"] >= TOKEN_AGREEMENT_MIN,
        "model_axis_collectives":
            sum(rec["decode_collectives"].values()) > 0,
        "kv_sharded": rec["kv_shards"] == tp,
        "every_device_holds_state": _all_in_use(rec["mem_per_device"]),
    }
    if require_kernels:
        checks["block_table_kernel"] = \
            set(rec["decode_kernels_traced"]) == {"paged_attention"}
        checks["no_fallbacks"] = rec["pallas_fallbacks"] == 0
        checks["kernel_in_decode"] = rec["decode_has_tpu_custom_call"]
    rec["checks"] = checks
    rec["ok"] = all(checks.values())
    del cb, model
    _release()
    return rec


def replicas_phase(*, config, seed=0, dtype="bfloat16", replicas=4,
                   max_batch_size=4, page_size=16, max_seq_len=512,
                   new_tokens=16, timeout_s=900.0):
    """Router over `replicas` one-chip replicas of one model: each must
    sit on its own device with its own weights and pool there, and the
    pool must answer like a single predictor."""
    import jax
    from paddle_tpu.inference import ContinuousBatchingPredictor
    from paddle_tpu.serving import Router
    rec = {"phase": "replicas", "replicas": replicas,
           **_shape_record(config, dtype)}
    model, n_params = build_model(config, seed, dtype)
    rec["params"] = n_params
    rng = np.random.RandomState(seed + 2)
    prompts = [rng.randint(2, config.vocab_size, (n,)).tolist()
               for n in (12, 20, 28, 36, 44, 52, 60, 24)]
    geometry = dict(max_batch_size=max_batch_size, page_size=page_size,
                    max_seq_len=max_seq_len)
    _kernel_flags(True)
    want = ContinuousBatchingPredictor(model, **geometry).generate(
        prompts, max_new_tokens=new_tokens)
    _release()
    router = Router([model] * replicas, policy="least_loaded", **geometry)
    try:
        handles = [router.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        got = [h.result(timeout=timeout_s) for h in handles]
        statuses = [h.status for h in handles]
        served_by = sorted({h.replica for h in handles})
        placement = {}
        for rep in router.replicas:
            devs = sorted({d.id for a in rep.predictor.pool.k
                           for d in a.devices()})
            wdevs = sorted({d.id for a in rep.predictor._p_vals[:1]
                            for d in a.devices()})
            placement[rep.name] = {"pool": devs, "weights": wdevs}
    finally:
        router.shutdown()
    rec["statuses"] = statuses
    rec["served_by"] = served_by
    rec["placement"] = placement
    rec["mem_per_device"] = [_mem(d) for d in jax.devices()[:replicas]]
    agreed, compared = _matched_agreement(got, want)
    rec["tokens_compared"] = compared
    rec["tokens_agreed"] = agreed
    rec["token_agreement"] = agreed / max(compared, 1)
    pool_devs = [tuple(v["pool"]) for v in placement.values()]
    checks = {
        "all_completed": all(s == "ok" for s in statuses),
        "distinct_devices": len(set(pool_devs)) == replicas
        and all(len(d) == 1 for d in pool_devs),
        "weights_with_pool": all(v["pool"] == v["weights"]
                                 for v in placement.values()),
        "memory_in_use_everywhere": _all_in_use(rec["mem_per_device"]),
        "every_replica_served": len(served_by) == replicas,
        "token_agreement": rec["token_agreement"] >= TOKEN_AGREEMENT_MIN,
    }
    rec["checks"] = checks
    rec["ok"] = all(checks.values())
    del router, model
    _release()
    return rec


def hybrid_train_phase(*, config, seed=0, dtype="bfloat16", batch=2,
                       seq=2048, steps=3, spec="data=2,model=2",
                       zero_stage=3):
    """HybridTrainStep on `spec` with ZeRO-3 against the one-device
    step on the same batch and seed."""
    import dataclasses
    import paddle_tpu as paddle
    _, loss_rtol, _ = _tols(dtype)
    rec = {"phase": "hybrid_train", "spec": spec, "zero_stage": zero_stage,
           **_shape_record(config, dtype),
           "batch": batch, "seq": seq, "steps": steps}
    rng = np.random.RandomState(seed + 1)
    ids = rng.randint(0, config.vocab_size, (batch, seq)).astype(np.int32)
    x = paddle.to_tensor(ids)
    y = paddle.to_tensor(ids)
    one, init, step = _train_arm(config, seed, dtype, x, y, steps, True)
    rec["one_device"] = one
    del step
    _release()
    tp_config = dataclasses.replace(config, tensor_parallel=True)
    arm, _, step = _train_arm(tp_config, seed, dtype, x, y, steps, True,
                              init=init, spec=spec, zero_stage=zero_stage)
    rec["hybrid"] = arm
    import jax
    rec["mem_per_device"] = [_mem(d) for d in jax.devices()]
    del step, init
    _release()
    fp = arm["footprint"].get("params_bytes", {})
    rel = [abs(a - b) / max(abs(b), 1e-12)
           for a, b in zip(arm["losses"], one["losses"])]
    rec["loss_rel_err_per_step"] = rel
    rec["loss_rtol"] = loss_rtol
    checks = {
        "loss_finite": bool(np.isfinite(arm["losses"]).all()),
        "loss_falling": arm["losses"][-1] < arm["losses"][0],
        "loss_matches_one_device": rel[0] <= loss_rtol,
        "params_sharded": 0 < fp.get("per_replica", 0) < fp.get("global", 0),
        "memory_in_use_everywhere": _all_in_use(rec["mem_per_device"]),
    }
    rec["checks"] = checks
    rec["ok"] = all(checks.values())
    return rec


# -------------------------------------------------------------------- main --

def native_record():
    """Whether the native runtime library was built for this run, and
    from what."""
    from paddle_tpu import _native
    return {"phase": "native", **_native.build_record()}


def run(chips, seed, emit):
    """Every phase in order; returns (all ok, device record)."""
    import jax
    import paddle_tpu  # noqa: F401  (places the compile cache by its rule)
    dev = device_record()
    emit({"phase": "start", "device": dev, "jax": jax.__version__,
          "chips_asked": chips, "seed": seed,
          "cache_dir": jax.config.jax_compilation_cache_dir})
    if dev["platform"] != "tpu":
        raise RuntimeError(
            f"chip_smoke.py needs a TPU; JAX reports platform "
            f"{dev['platform']!r} ({dev['kind']})")
    if dev["count"] < chips:
        raise RuntimeError(
            f"--chips {chips} needs {chips} devices, JAX reports "
            f"{dev['count']}")
    if chips == 1:
        phases = [
            lambda: serve_phase(config=llama_config(SERVE_DEPTH), seed=seed),
            lambda: train_phase(config=llama_config(TRAIN_DEPTH), seed=seed),
        ]
    else:
        phases = [
            lambda: tp_serve_phase(config=llama_config(SERVE_DEPTH),
                                   seed=seed, tp=chips),
            lambda: replicas_phase(config=llama_config(REPLICA_DEPTH),
                                   seed=seed, replicas=chips),
            lambda: hybrid_train_phase(config=llama_config(TRAIN_DEPTH),
                                       seed=seed),
        ]
    ok = True
    for phase in phases:
        t0 = time.perf_counter()
        rec = phase()
        rec["phase_seconds"] = round(time.perf_counter() - t0, 2)
        emit(rec)
        ok = ok and rec["ok"]
    emit(native_record())
    return ok, dev


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the cross-chip paths and no one-chip "
                         "phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "output")
    os.makedirs(out_dir, exist_ok=True)
    records = []

    def emit(record):
        records.append(record)
        print(json.dumps(record), flush=True)

    def finish(final):
        with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
            json.dump({"final": final, "records": records}, f, indent=1)
        print(json.dumps(final), flush=True)

    try:
        ok, dev = run(args.chips, args.seed, emit)
    except BaseException as e:    # reported as the last line, then re-raised
        try:
            dev = device_record()
        except Exception as de:   # not even a device to name
            dev = {"error": f"{type(de).__name__}: {de}"}
        finish({"ok": False, "device": dev,
                "error": f"{type(e).__name__}: {e}"})
        raise
    finish({"ok": bool(ok), "device": dev})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

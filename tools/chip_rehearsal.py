"""Compile what chip_smoke.py will run, for a TPU that is described and
not attached (the third rehearsal of the on-chip-measurement guide).

Run it here, on the CPU, before a chip call:

    JAX_PLATFORMS=cpu python tools/chip_rehearsal.py serve train
    JAX_PLATFORMS=cpu python tools/chip_rehearsal.py tp hybrid   # 4 chips

Each program is lowered with ``jax.ShapeDtypeStruct`` arguments placed
on a described ``v5e:2x2`` and handed to the installed TPU compiler,
which raises what the chip's compiler would raise (a kernel over the
VMEM limit, a program over HBM, a kernel it cannot partition) and
reports ``memory_analysis()``: the first honest answer to "what depth
fits". Nothing runs, so this says nothing about results or times.

Code that asks ``jax.default_backend()`` sees the CPU here and would
lower no kernel at all. This script therefore patches the gate
(``kernels._common.use_pallas`` and the ``_use_pallas`` name each kernel
module bound at import) so that it follows ``FLAGS_use_pallas_kernels``
alone, builds its predictors as chip_smoke.py does, and donates the
pools as the chip path does. It does not set ``FLAGS_pallas_interpret``
(that would lower the interpreter), and the package gains no option for
any of this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,  # noqa: E402
                          SingleDeviceSharding)

TOPOLOGY = "v5e:2x2"
HBM_BYTES = 16 * 1024 ** 3


def describe_topology():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name=TOPOLOGY)


def force_kernels():
    """Make every kernel gate follow FLAGS_use_pallas_kernels alone, as
    it does on the chip."""
    from paddle_tpu.framework.flags import flag_value
    from paddle_tpu.kernels import (_common, attention, norm,
                                    paged_attention)

    def wanted():
        return bool(flag_value("use_pallas_kernels"))

    # the predictor asks `_common` itself whether its span programs
    # carry ragged metadata
    _common.use_pallas = wanted
    for mod in (attention, norm, paged_attention):
        mod._use_pallas = wanted


def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


def onto(tree, sharding_of):
    """ShapeDtypeStructs for `tree`'s arrays, each placed by
    `sharding_of(leaf)`."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding_of(a)), tree)


def report(name, lowered, n_devices=1):
    t0 = time.perf_counter()
    try:
        compiled = lowered.compile()
    except Exception as e:  # the compiler's refusal IS the finding
        rec = {"program": name, "compiled": False,
               "error": f"{type(e).__name__}: {str(e)[:600]}"}
        print(json.dumps(rec), flush=True)
        return rec
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    per_device = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                     - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    rec = {
        "program": name, "compiled": True,
        "compile_seconds": round(time.perf_counter() - t0, 1),
        "tpu_custom_calls": text.count('custom_call_target="tpu_custom_call"'),
        "f64_in_program": " f64[" in text,
        "collectives": {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                        for op in ("all-reduce", "all-gather",
                                   "reduce-scatter", "collective-permute")},
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "live_bytes_per_device": per_device,
        "fits_16GiB": per_device < HBM_BYTES,
        "devices": n_devices,
    }
    print(json.dumps(rec), flush=True)
    return rec


# ------------------------------------------------------------------ serve --

def serve_programs(depth, tp=1, chunk=0):
    """Every program the smoke's serve phase compiles, at its shapes:
    the three prefill buckets, the two suffix prefills, the decode step
    and the copy-on-write page copy; with `chunk`, the mixed
    prefill+decode step at that span bucket and nothing else (chunked
    prefill is off by default and not on the smoke's path)."""
    import chip_smoke as cs
    from paddle_tpu.inference import ContinuousBatchingPredictor
    from paddle_tpu.kernels.paged_attention import RaggedMetaBuilder
    topo = describe_topology()
    model, _ = cs.build_model(cs.llama_config(depth), 0, "bfloat16")
    pred = ContinuousBatchingPredictor(
        model, max_batch_size=8, page_size=16, max_seq_len=1024,
        tp_degree=tp, prefill_chunk_tokens=chunk)
    pred._ensure_ready()
    mesh = None
    if tp > 1:
        mesh = Mesh(np.array(topo.devices[:tp]).reshape(
            pred._tp_mesh.devices.shape), pred._tp_mesh.axis_names)

        def place(a):
            return NamedSharding(mesh, a.sharding.spec)
        scalar = NamedSharding(mesh, PartitionSpec())
    else:
        one = SingleDeviceSharding(topo.devices[0])

        def place(a):
            return one
        scalar = one
    p = onto(pred._p_vals, place)
    b = onto(pred._b_vals, place)
    kl = onto(pred.pool.k, place)
    vl = onto(pred.pool.v, place)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=scalar)

    B, pps, page = pred.B, pred.pages_per_seq, pred.page
    dn = (2, 3)
    recs = []
    from paddle_tpu.kernels._common import kernel_partition_scope
    with pred._trace_lock, kernel_partition_scope(mesh):
        if chunk:
            meta = tuple(i32(B * pps) for _ in RaggedMetaBuilder.FIELDS) \
                if pred.span_ragged else ()
            return [report(
                f"mixed[B={B}, span {chunk}] tp={tp}",
                jax.jit(pred._raw_mixed_step, donate_argnums=dn).lower(
                    p, b, kl, vl, i32(B, pps), i32(B), i32(B, chunk),
                    i32(B), i32(B), *meta), tp)]
        for bucket in (32, 64, 128):
            recs.append(report(
                f"prefill[1x{bucket}] tp={tp}",
                jax.jit(pred._raw_prefill, donate_argnums=dn).lower(
                    p, b, kl, vl, i32(1, bucket), i32(1, bucket), i32(1),
                    i32(1, -(-bucket // page))), tp))
        for sb, wpb in ((8, 2), (16, 4)):
            recs.append(report(
                f"suffix_prefill[{sb}|{wpb} pages] tp={tp}",
                jax.jit(pred._raw_suffix_prefill, donate_argnums=dn).lower(
                    p, b, kl, vl, i32(1, sb), i32(1, sb), i32(), i32(),
                    i32(wpb), i32(pps)), tp))
        recs.append(report(
            f"decode[B={B}, {pps} pages/seq] tp={tp}",
            jax.jit(pred._raw_decode_step, donate_argnums=dn).lower(
                p, b, kl, vl, i32(B, pps), i32(B), i32(B)), tp))

    def cow(kl_, vl_, s, d):
        return ([k.at[d].set(k[s]) for k in kl_],
                [v.at[d].set(v[s]) for v in vl_])
    recs.append(report(
        f"page_copy tp={tp}",
        jax.jit(cow, donate_argnums=(0, 1)).lower(kl, vl, i32(), i32()),
        tp))
    return recs


# ------------------------------------------------------------------ train --

def train_program(depth, spec="data=1", zero_stage=0, batch=2, seq=2048,
                  kernels=True):
    """The whole HybridTrainStep program (fwd + bwd + AdamW update) as
    the smoke builds it, re-targeted from the CPU mesh it was built on
    to the described chips. `kernels=False` is the smoke's XLA arm."""
    import dataclasses
    import chip_smoke as cs
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet import (HybridParallelPlan,
                                              HybridTrainStep)
    from paddle_tpu.distributed.fleet.dist_step import _step_scope
    from paddle_tpu.distributed.mesh import set_mesh
    from paddle_tpu.models import LlamaPretrainingCriterion
    topo = describe_topology()
    cs._kernel_flags(kernels)
    plan = HybridParallelPlan.from_spec(spec, zero_stage=zero_stage)
    cpu_mesh = plan.build_mesh()
    set_mesh(cpu_mesh)
    config = cs.llama_config(depth)
    if plan.mp > 1:
        config = dataclasses.replace(config, tensor_parallel=True)
    model, n_params = cs.build_model(config, 0, "bfloat16")
    crit = LlamaPretrainingCriterion(config)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.1,
                                 parameters=model.parameters())
    step = HybridTrainStep(model, opt, lambda lg, lb: crit(lg, lb),
                           plan=plan, mesh=cpu_mesh)
    inner = step.inner
    n = cpu_mesh.devices.size
    mesh = Mesh(np.array(topo.devices[:n]).reshape(cpu_mesh.devices.shape),
                cpu_mesh.axis_names)

    def move(sh):
        return NamedSharding(mesh, sh.spec)

    is_sh = lambda x: isinstance(x, NamedSharding)  # noqa: E731
    state_structs = onto(inner._opt_state, lambda a: move(a.sharding))
    p_structs = onto([t._value for t in inner._p], lambda a: move(a.sharding))
    b_structs = onto([t._value for t in inner._b], lambda a: move(a.sharding))
    # re-target the step's own shardings, then let it build its program
    inner._mesh = mesh
    inner._p_sh = [move(s) for s in inner._p_sh]
    inner._b_sh = [move(s) for s in inner._b_sh]
    inner._s_sh = jax.tree_util.tree_map(move, inner._s_sh, is_leaf=is_sh)
    ids = jnp.zeros((batch, seq), jnp.int32)
    batch_sh = inner._batch_shardings([ids, ids])
    run = inner._build(batch_sh)
    repl = NamedSharding(mesh, PartitionSpec())
    arrays = [jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=s)
              for s in batch_sh]
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=repl)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=repl)
    with _step_scope(mesh):
        lowered = run._jitted.lower(p_structs, b_structs, state_structs,
                                    key, lr, arrays, ())
    cs._kernel_flags(True)
    rec = report(f"train_step[{spec}, zero={zero_stage}, depth={depth}, "
                 f"{batch}x{seq}, kernels={kernels}]", lowered, n)
    rec["params"] = n_params
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", nargs="+",
                    choices=("serve", "mixed", "train", "tp", "hybrid"))
    ap.add_argument("--serve-depth", type=int, default=None)
    ap.add_argument("--train-depth", type=int, default=None)
    args = ap.parse_args(argv)
    import chip_smoke as cs
    no_persistent_cache()
    force_kernels()
    serve_depth = args.serve_depth or cs.SERVE_DEPTH
    train_depth = args.train_depth or cs.TRAIN_DEPTH
    recs = []
    if "serve" in args.what:
        recs += serve_programs(serve_depth)
    if "mixed" in args.what:
        recs += serve_programs(serve_depth, chunk=64)
    if "train" in args.what:
        recs.append(train_program(train_depth))
        recs.append(train_program(train_depth, kernels=False))
    if "tp" in args.what:
        recs += serve_programs(serve_depth, tp=4)
    if "hybrid" in args.what:
        recs.append(train_program(train_depth, spec="data=2,model=2",
                                  zero_stage=3))
    bad = [r["program"] for r in recs
           if not r["compiled"] or not r.get("fits_16GiB", True)]
    print(json.dumps({"programs": len(recs), "refused_or_too_big": bad}))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

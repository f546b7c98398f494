"""Rehearse the benchmark's cells without a chip.

    python benchmarks/rehearse.py tiny [workload ...]      # end to end, CPU
    python benchmarks/rehearse.py compile [workload ...]   # real size, v5e

`tiny` copies the benchmark into a temporary root, shrinks every
configuration, mix and limits file with the patches in `tests/data/tiny/`, and runs
each cell through the same harness and runners on the CPU with the
Pallas kernels in interpret mode. Its numbers are CPU numbers and are
printed as a rehearsal's, never as a device metric's.

`compile` lowers each cell's serve programs at their real shapes for a
described `v5e:2x2` and prints the compiler's `memory_analysis()` a
device, so that a memory plan is checked before chip time is spent.
Nothing runs. `run.py` itself has neither mode.
"""
import time
T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
TINY = os.path.join(ROOT, "benchmarks", "tests", "data", "tiny")


def tiny_root(dst, patches=TINY):
    """A copy of the benchmark under `dst` with the tiny patches merged
    into its configurations and mixes."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    for kind in ("configs", "traffic", "limits"):
        src = os.path.join(patches, kind)
        for name in sorted(os.listdir(src)):
            path = os.path.join(dst, "benchmarks", kind, name)
            with open(path) as f:
                data = json.load(f)
            with open(os.path.join(src, name)) as f:
                data.update(json.load(f))
            with open(path, "w") as f:
                json.dump(data, f, indent=1)
    return dst


def interpret_kernels():
    from paddle_tpu.framework.flags import set_flags
    set_flags({"use_pallas_kernels": True, "pallas_interpret": True})


def run_tiny(workloads, seed, seconds, trace):
    from benchmarks.lib import harness
    interpret_kernels()
    lines = {}
    with tempfile.TemporaryDirectory() as tmp:
        root = tiny_root(tmp)
        for w in workloads:
            line = harness.run_cell(root, w, seed, seconds, trace, T0,
                                    require_tpu=False)
            print(json.dumps({"rehearsal_on_cpu": w, "line": line}),
                  flush=True)
            lines[w] = line
    return lines


def compile_cell(workload):
    """Lower the cell's decode step and its largest prefill programs at
    real size for one described v5e chip."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from benchmarks.lib import harness
    import paddle_tpu  # noqa: F401
    from paddle_tpu.framework.flags import flag_value
    from paddle_tpu.inference import ContinuousBatchingPredictor
    from paddle_tpu.kernels import attention, norm, paged_attention
    from paddle_tpu.kernels.paged_attention import RaggedMetaBuilder
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # code that asks the backend sees the CPU here: make the kernel
    # gates follow the flag alone, as they do on the chip
    for mod in (attention, norm, paged_attention):
        mod._use_pallas = lambda: bool(flag_value("use_pallas_kernels"))
    found = harness.find_cell(ROOT, workload)
    cfg, mix = found["cfg"], found["mix"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    builder = harness.load_module(ROOT, "models", cfg["builder"])
    model, n_params = builder.build(cfg, 0, abstract=True)
    ragged = (cfg["num_attention_heads"] == cfg["num_key_value_heads"])
    pred = ContinuousBatchingPredictor(model, use_ragged=ragged,
                                       kv_dtype=cfg["dtype"], **cfg["serve"])
    pred._ensure_ready()

    def sds(a):
        return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype, sharding=one)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    p = [sds(a) for a in pred._p_vals]
    b = [sds(a) for a in pred._b_vals]
    kl = [sds(a) for a in pred.pool.k]
    vl = [sds(a) for a in pred.pool.v]
    B, pps, page = pred.B, pred.pages_per_seq, pred.page
    meta = tuple(i32(B * pps) for _ in RaggedMetaBuilder.FIELDS) \
        if pred.use_ragged else ()
    weights_pool = sum(a.size * a.dtype.itemsize for a in p + kl + vl)
    print(json.dumps({"workload": workload, "params": n_params,
                      "use_ragged": pred.use_ragged,
                      "weights_and_pool_bytes": int(weights_pool)}),
          flush=True)
    jobs = [("decode", pred._raw_decode_step,
             (i32(B, pps), i32(B), i32(B)) + meta)]
    warm = mix.get("warm", {})
    for n, bucket in warm.get("prefill", []):
        if n == max(x[0] for x in warm["prefill"]):
            jobs.append((f"prefill[{n}x{bucket}]", pred._raw_prefill,
                         (i32(n, bucket), i32(n, bucket), i32(n),
                          i32(n, -(-bucket // page)))))
    for sb, wpb in warm.get("suffix", []):
        if wpb == max(x[1] for x in warm["suffix"]):
            jobs.append((f"suffix[{sb}|{wpb} pages]",
                         pred._raw_suffix_prefill,
                         (i32(1, sb), i32(1, sb), i32(), i32(), i32(wpb),
                          i32(pps))))
    ok = True
    for name, fn, args in jobs:
        t0 = time.perf_counter()
        rec = {"workload": workload, "program": name}
        try:
            with pred._trace_lock, pred._kernel_scope():
                compiled = jax.jit(fn, donate_argnums=(2, 3)).lower(
                    p, b, kl, vl, *args).compile()
            ma = compiled.memory_analysis()
            live = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                       - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
            rec.update(compiled=True, temp_bytes=int(ma.temp_size_in_bytes),
                       live_bytes=live, fits_16GiB=live < 16 * 1024 ** 3,
                       tpu_custom_calls=compiled.as_text().count(
                           'custom_call_target="tpu_custom_call"'),
                       compile_seconds=round(time.perf_counter() - t0, 1))
            ok = ok and rec["fits_16GiB"]
        except Exception as e:   # the compiler's refusal is the finding
            rec.update(compiled=False,
                       error=f"{type(e).__name__}: {str(e)[:500]}")
            ok = False
        print(json.dumps(rec), flush=True)
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("tiny", "compile"))
    ap.add_argument("workloads", nargs="*")
    ap.add_argument("--seed", type=int, default=3000000001)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    workloads = args.workloads or names
    if args.mode == "tiny":
        lines = run_tiny(workloads, args.seed, args.seconds,
                         bool(args.trace))
        return 0 if all(line["correct"] for line in lines.values()) else 1
    return 0 if all([compile_cell(w) for w in workloads]) else 1


if __name__ == "__main__":
    sys.exit(main())

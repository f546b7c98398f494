"""The harness: finds a cell's files by the names in BENCHMARK.json,
runs the cell once, decides `correct`, reduces the trace, and builds
the contract's result line. `run.py` is its command line; `limits.py`
and `rehearse.py` call `run_cell` in-process.

Files found by name, under the benchmark root's `benchmarks/`:
  configs/<config>.json         sizes, source, `builder`, `reference`
  traffic/<traffic>.json        parameters of the mix and its `runner`
  limits/<workload>.json        the cell's `check` and its limits
  checks/<check>.py             decide(root, found, seed, record, control)
  models/<builder>.py           build(cfg, seed) -> (model, n_params)
  reference/<reference>.py      logits_at(cfg, seed, ids, rows, quant)
  runners/<runner>.py           run(ctx) -> record
  layer_metrics/<metric>.py     read(record, trace) -> number or None
  kernels/<kernel>.py           bytes and operations of one kernel call
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(root, kind, name):
    """The module `benchmarks/<kind>/<name>.py` under `root`."""
    path = os.path.join(root, "benchmarks", kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}".replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(root, workload):
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    mix = load_json(os.path.join(root, "benchmarks", "traffic",
                                 cell["traffic"] + ".json"))
    limits = load_json(os.path.join(root, "benchmarks", "limits",
                                    workload + ".json"))

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {"bench": bench, "cell": cell, "cfg": cfg, "mix": mix,
            "limits": limits,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def device_record():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peaks_for(kind, root=REPO):
    table = load_json(os.path.join(root, "benchmarks",
                                   "peaks.json"))["by_device_kind"]
    if kind not in table:
        raise SystemExit(f"device_kind {kind!r} is not in "
                         f"benchmarks/peaks.json: no peak, no roofline")
    return table[kind]


def memory_peak_bytes():
    """Peak bytes in use on the fullest device (0 where the backend
    keeps no statistics, which is the CPU)."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def say(tag, payload):
    """An earlier line: one JSON object, never the last line."""
    print(json.dumps({tag: payload}), flush=True)


def brief(rec, limit=4000):
    """The record's entries that print in under `limit` characters:
    samples and token lists stay out of the log."""
    return {k: v for k, v in rec.items()
            if len(json.dumps(v, default=str)) <= limit}


def run_cell(root, workload, seed, seconds, trace, t_process_start,
             require_tpu=True, control=False):
    """Run one cell once; returns the result line as a dict."""
    found = find_cell(root, workload)
    cell, cfg, mix = found["cell"], found["cfg"], found["mix"]
    import jax
    dev = device_record()
    if require_tpu:
        if dev["platform"] != "tpu":
            raise SystemExit(f"the benchmark needs a TPU; JAX reports "
                             f"{dev['platform']!r} ({dev['kind']})")
        if dev["count"] < cell["chips"]:
            raise SystemExit(f"{workload} needs {cell['chips']} chips, "
                             f"JAX reports {dev['count']}")
        peaks = peaks_for(dev["kind"], root)
    else:
        peaks = None
    import paddle_tpu  # noqa: F401  (places the compile cache in the checkout)
    say("start", {"workload": workload, "seed": seed, "seconds": seconds,
                  "trace": trace, "device": dev,
                  "cache_dir": jax.config.jax_compilation_cache_dir})
    out_dir = os.path.join(root, "benchmarks", "out", workload)
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(out_dir, "trace") if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {"root": root, "workload": workload, "cell": cell, "cfg": cfg,
           "mix": mix, "seed": int(seed), "seconds": float(seconds),
           "trace_dir": trace_dir, "t_process_start": t_process_start,
           "builder": load_module(root, "models", cfg["builder"])
           if "builder" in cfg else None,
           "say": say}
    runner = load_module(root, "runners", mix["runner"])
    rec = runner.run(ctx)          # the program's state is freed on return
    say("run", brief(rec))

    checker = load_module(root, "checks", found["limits"]["check"])
    t0 = time.perf_counter()
    check = checker.decide(root, found, int(seed), rec, control=control)
    check["seconds"] = time.perf_counter() - t0
    say("check", check)

    device = dict(dev, memory_peak_bytes=rec["memory_peak_bytes"])
    line = {"correct": check["correct"], "attempted": rec["attempted"],
            "failed": rec["failed"], "metrics": {}, "device": device}
    if not trace:
        for m in found["end_to_end"]:
            if rec["metrics"].get(m["name"]) is not None:
                line["metrics"][m["name"]] = {
                    "value": rec["metrics"][m["name"]], "unit": m["unit"]}
    else:
        from benchmarks.lib import trace_reduce
        reduced = trace_reduce.reduce_dir(trace_dir, head_seconds=0.05)
        with open(os.path.join(out_dir, "planes_head.json"), "w") as f:
            json.dump(reduced.pop("head"), f)
        say("trace", {k: reduced.get(k) for k in
                      ("planes", "busy_s", "window_s", "names_trace",
                       "programs")})
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = reduced["breakdown"]
        rec["peaks"], rec["root"] = peaks, root
        for m in found["per_layer"]:
            reader = load_module(root, "layer_metrics", m["name"])
            value = reader.read(rec, reduced)
            if value is not None:
                line["metrics"][m["name"]] = {"value": float(value),
                                              "unit": m["unit"]}
        with open(os.path.join(out_dir, "trace_summary.json"), "w") as f:
            json.dump(reduced, f)
        shutil.rmtree(trace_dir, ignore_errors=True)
    if control:
        line["control_fails"] = check.get("control_fails")
        line["check"] = check
    return line

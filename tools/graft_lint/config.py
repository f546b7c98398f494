"""Project-specific configuration for the graft-lint passes.

This file IS the repo's tribal knowledge, machine-readable: which
functions are hot paths, which locks are non-reentrant, where the
telemetry catalogs live. New subsystems extend these tables instead of
re-teaching every reviewer (docs/STATIC_ANALYSIS.md explains each).
"""
import fnmatch

# --------------------------------------------------------------- GL102 --
# Registered hot-path functions: (relpath glob, function name glob).
# Inside these, explicit host transfers (np.asarray / .numpy() /
# .item() / block_until_ready / device_get) are findings unless the
# site carries a `# graft-lint: ok[GL102] <why>` sanction — the decode
# loop's single designed sync point is sanctioned, a stray second one
# is a bug. (Functions jitted with jax.jit are checked everywhere,
# with a stricter rule set, regardless of this table.)
HOT_PATH_FUNCTIONS = (
    # the continuous-batching serve loop (generation decode fast path)
    ("paddle_tpu/inference/__init__.py", "ContinuousBatchingPredictor._serve"),
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor._dispatch_step"),
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor._resolve_step"),
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor._batch_prefill"),
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor._suffix_prefill"),
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor._jit_call"),
    # mixed prefill+decode step: chunk scheduling + dispatch run once
    # per tick while a long prompt ingests — a stray host sync there
    # stalls the interleaved decode slots too
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor._dispatch_mixed_step"),
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor._chunk_bucket"),
    # speculative decoding: draft/dispatch/verify-resolve run once per
    # multi-token tick — a stray sync there forfeits the whole point
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor._dispatch_spec_step"),
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor._resolve_spec_step"),
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor._await_step"),
    # tensor-parallel dispatch plumbing: the analytic model-axis
    # all-reduce accounting runs once per dispatched tick, and the
    # weight re-shard check runs per generate — a host transfer in
    # either stalls every GSPMD program in flight
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor._tp_account"),
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor._tp_shard_all"),
    # host-side prompt-lookup drafter: pure-python list matching, runs
    # per spec tick per slot
    ("paddle_tpu/generation/sampling.py", "propose_ngram_drafts"),
    # serving front end: router / scheduler / streaming are host-side
    # by design — ANY device sync there stalls every tenant
    ("paddle_tpu/serving/*.py", "*"),
    # paged KV bookkeeping runs once per decode tick; the disaggregated
    # span export/import (PagedKVPool.export_span / import_span) is
    # covered by the PagedKVPool.* row — its host gather/scatter is the
    # DESIGNED transport sync and carries explicit sanctions
    ("paddle_tpu/generation/kv_cache.py", "RaggedMetaBuilder.*"),
    ("paddle_tpu/generation/kv_cache.py", "PagedKVPool.*"),
    # prefill→decode handoff endpoints on the predictor: run on the
    # replica worker thread between serve-loop ticks — any sync beyond
    # the span payload itself stalls that replica's decode clock
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor.export_page_span"),
    ("paddle_tpu/inference/__init__.py",
     "ContinuousBatchingPredictor.import_page_span"),
    # eager (dygraph) generation decode loop + seq2seq beam decode
    ("paddle_tpu/generation/__init__.py",
     "GenerationMixin._generate_eager_batch"),
    ("paddle_tpu/nn/decode.py", "dynamic_decode"),
    # eager fused-optimizer step (one dispatch per step, no syncs)
    ("paddle_tpu/optimizer/fused.py", "FusedPlan.run"),
    ("paddle_tpu/optimizer/fused.py", "try_fused_step"),
    # hybrid-parallel per-step entry (loss sync is deferred by design)
    ("paddle_tpu/distributed/fleet/dist_step.py", "DistTrainStep.__call__"),
    # ZeRO-2 micro-step entry: runs once per accumulation micro-batch
    ("paddle_tpu/distributed/fleet/dist_step.py",
     "DistTrainStep._call_accum"),
    # hybrid engine front door: one dispatch per step, zero host syncs
    ("paddle_tpu/distributed/fleet/hybrid/engine.py",
     "HybridTrainStep.__call__"),
    # explicit 1F1B tick loop: traced per schedule tick — a host sync
    # here would serialize the whole pipeline clock
    ("paddle_tpu/distributed/fleet/meta_parallel/pipeline_parallel.py",
     "pipeline_1f1b.staged.tick_1f1b"),
    # TP layer forwards: traced inside every hybrid step; implicit
    # tracer bools / host transfers here poison every compile
    ("paddle_tpu/distributed/fleet/meta_parallel/mp_layers.py",
     "*.forward"),
    # fleet aggregator tail loop: runs at heartbeat cadence inside the
    # launcher babysit loop — must stay file-I/O-only (no device work,
    # no blocking syncs); a host sync here stalls hang/straggler
    # detection for the whole pod
    ("paddle_tpu/observability/fleet.py", "FleetAggregator.*"),
    ("paddle_tpu/observability/fleet.py", "RankFileTailer.*"),
)


def is_hot_path(relpath: str, qualname: str) -> bool:
    for pat, fn in HOT_PATH_FUNCTIONS:
        if fnmatch.fnmatch(relpath, pat) and fnmatch.fnmatch(qualname, fn):
            return True
    return False


# --------------------------------------------------------------- GL104 --
# Known non-reentrant-lock-acquiring callables (the PR-5 deadlock
# registry). Bare function names match any call; method names also
# require the receiver hint regex to match the receiver expression
# (None = any receiver). All of these take a plain threading.Lock a
# signal handler interrupting the lock holder can never acquire.
LOCKY_FUNCTIONS = {
    # observability.tracing: flight ring + registry snapshot + sink
    "flight_dump": None,
    # observability.metrics: MetricRegistry._lock via create-or-get
    "counter": None,
    "gauge": None,
    "histogram": None,
}
LOCKY_METHODS = {
    # FlightRecorder ring lock
    "dump": r"(flight|recorder)",
    # JsonlExporter / process sink locks
    "export": None,
    "write_record": None,
    "flush": r"(exporter|sink|jsonl)",
    "close": r"(exporter|sink|jsonl)",
    # MetricRegistry + series locks
    "collect": r"(registry|_reg)",
    "snapshot": r"(registry|_reg)",
    "inc": r"(^_m_|counter|gauge|metric)",
    "observe": r"(^_m_|hist|metric)",
    "set": r"(^_m_|gauge)",
}
# receiver/name regex for "this expression is a lock object"
LOCK_NAME_RE = r"(?i)(^|[._])lock$"


# --------------------------------------------------------------- GL106 --
# Knobs migrated into the typed RuntimeConfig
# (paddle_tpu/framework/runtime_config.py). Reading one via the bare
# FLAGS registry (flag_value / get_flags) anywhere else bypasses the
# config object — the bundle-baked value and the running value then
# silently diverge, which is exactly the drift aot.config_drift exists
# to surface. Only RUNTIME_CONFIG_HOME (the from_flags() bridge) may
# read them directly.
RUNTIME_CONFIG_HOME = "paddle_tpu/framework/runtime_config.py"
RUNTIME_CONFIG_KNOBS = frozenset({
    "serve_prefill_chunk_tokens",
    "serve_decode_watchdog_s",
    "serve_spec_draft_tokens",
    "serve_spec_ngram_max",
    "serve_sampling",
    "serve_tp_degree",
    "serve_role",
    "grad_bucket_bytes",
    "quantized_grad_comm",
})

# --------------------------------------------------------------- GL107 --
# Control surfaces: modules whose functions actuate the fleet/serving
# plane. Inside them, every call to a CONTROL_ACTIONS name must be
# reachable only through a decision path that also emits a
# {"kind": "control"} audit record (a CONTROL_AUDIT_EMITTERS call in
# the same function, or in every in-module caller, transitively).
CONTROL_SURFACES = (
    "paddle_tpu/distributed/launch/*.py",
    "paddle_tpu/serving/controller.py",
)
# Side-effecting actuator verbs (terminal callee names): process kills,
# fleet-membership changes, pool scaling, tier weight/shed levers.
CONTROL_ACTIONS = frozenset({
    "kill_rank",
    "retire_rank",
    "add_replica",
    "drain_replica",
    "revive",
    "set_tier_weight",
    "set_shed_tiers",
})
# Sanctioned audit paths: the raw record sink, the SLO controller's
# record helper, the mitigation controller's decision entry point
# (which records internally), and the launcher's control.jsonl sink.
CONTROL_AUDIT_EMITTERS = frozenset({
    "export_record",
    "_record",
    "offer",
    "_emit_control",
})

# --------------------------------------------------------------- GL108 --
# Cross-boundary trace-propagation surfaces: the files where a request
# crosses a thread/queue/process boundary (router dispatch into the
# serve loop, prefill→decode page-span handoff, replica adoption).
# Inside them, boundary-record constructors must carry the request's
# TraceContext and parent-less root spans may only be minted at the
# configured admission sites (docs/OBSERVABILITY.md "Request tracing").
TRACE_BOUNDARIES = (
    "paddle_tpu/serving/router.py",
    "paddle_tpu/serving/streaming.py",
    "paddle_tpu/inference/__init__.py",
)
# Boundary-crossing record constructors -> the field that carries the
# context. A construction without the keyword (and without a
# `<record>.trace = ...` attach in the same function) drops the trace.
TRACE_CARRIERS = {
    "ServeRequest": "trace",
    "KVPageSpan": "trace",
}
# Functions (qualname globs) allowed to mint a parent-less root span
# inside a boundary file: router admission (THE per-request root) and
# the serve loop's pool-local serve.generate umbrella.
TRACE_MINT_SITES = (
    "RequestHandle.__init__",
    "ContinuousBatchingPredictor._serve",
)

# Standalone tool entry points linted by the default CLI run alongside
# paddle_tpu/ (the autotune replay engine and the other telemetry
# readers ship code too — the closing-the-loop pipeline is only as
# trustworthy as its tools).
TOOL_ENTRY_POINTS = ("tools/autotune.py", "tools/trace_report.py",
                     "tools/metrics_report.py", "tools/fleet_report.py",
                     "tools/aot_report.py", "tools/trace_replay.py")

# --------------------------------------------------------------- GL105 --
# Where telemetry is emitted (scanned for counter/gauge/histogram/span/
# start_span/traced/define_flag call sites) — independent of the CLI
# paths, so a run over one sub-package still audits the whole catalog.
EMISSION_ROOTS = ("paddle_tpu",)
# The catalogs every metric/span name must appear in (and vice versa).
CATALOG_DOCS = ("docs/OBSERVABILITY.md", "docs/ROBUSTNESS.md")
# Flags may be documented in any of these.
FLAG_DOC_ROOTS = ("docs", "README.md")
# Only names under these domains are catalog-checked; quickstart
# examples (myapp.*) and module paths in backticks stay out of scope.
CATALOG_PREFIXES = ("train", "serve", "serving", "comm", "mem", "pp",
                    "robustness", "aot", "ckpt", "dist", "launch",
                    "bench", "router", "kernels", "autotune", "fleet",
                    "slo", "jit")

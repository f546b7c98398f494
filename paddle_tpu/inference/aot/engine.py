"""AOT inference engine: zero-compile warm-start serving.

Two cache tiers sit between a process restart and the first token:

- **Tier 1 — the engine bundle** (bundle.py): serialized, digest-
  verified XLA executables for every calibrated shape bucket. A hit
  dispatches straight into ``Compiled.__call__`` — no trace, no
  compile, no HLO anywhere on the path (``aot.bundle_hits``).
- **Tier 2 — the XLA persistent compilation cache**: a bucket MISS
  still traces and calls the compiler, but the backend compile is
  served from disk across restarts. Where the environment places the
  cache (``JAX_COMPILATION_CACHE_DIR``) it stays there; otherwise
  ``wire_xla_cache`` points it at ``<bundle>/xla_cache`` so a deployed
  bundle carries its own.

Both tiers are fenced by invalidation-on-mismatch: a bundle whose
jaxlib/platform fingerprint or model hash disagrees with the current
runtime is REJECTED (counted in ``aot.invalidations``) and the caller
falls back to a clean live-JIT build; the tier-2 directory carries its
own fingerprint file and is wiped on mismatch.

Telemetry: ``aot.load`` / ``aot.compile_fallback`` spans,
``aot.{bundle_hits,bucket_misses,invalidations}`` counters, and the
``serve.cold_start_seconds`` gauge recorded by the predictor at its
first token (docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import contextlib
import logging
import os
import threading
from typing import Dict, Optional

from ...observability import metrics as _obsm
from ...observability import tracing as _obstr
from ...framework import integrity as _integrity
from .bundle import (EngineBundle, BundleInvalid, runtime_fingerprint,
                     model_fingerprint, sig_key)

__all__ = ["InferenceEngine", "load_engine", "warm_start",
           "wire_xla_cache", "default_engine_dir"]

_logger = logging.getLogger("paddle_tpu.aot")

# predictor ctor kwargs that are baked INTO the compiled executables
# (shapes, paged-pool layout, eos/pad semantics): differing values at
# warm_start invalidate the bundle. Everything else (name, prefix
# cache, queue/shed/watchdog knobs) is runtime-only and never does.
COMPILED_GEOMETRY_KEYS = frozenset({
    "max_batch_size", "page_size", "max_seq_len", "num_pages",
    "pad_token_id", "eos_token_id", "kv_dtype",
    # chunked prefill: the mixed-step programs' span buckets derive
    # from it, so a different threshold means different executables
    "prefill_chunk_tokens",
    # speculative decoding + on-device sampling are program VARIANTS:
    # the verify span width is spec_draft_tokens + 1 and
    # sampling_enabled switches decode to the batched-operand sampling
    # program (spec_ngram_max is host-side drafting policy — runtime-
    # only, never invalidates)
    "spec_draft_tokens", "sampling_enabled",
    # tensor-parallel degree: the GSPMD partitioning (weights over the
    # 'model' axis, KV pages over heads) is compiled into every
    # executable — checked FIRST at warm start as the serve-path
    # `topology` invalidation (mirror of hybrid/aot.py's train-step
    # topology gate)
    "tp_degree",
    # disaggregated serve role: a per-role bundle carries a per-role
    # PROGRAM SET (a prefill bundle calibrates max_new=1 and never
    # compiles multi-token decode; a decode bundle drops the chunked
    # mixed programs), so role rides the fingerprint next to topology
    # and gets its own warm-start gate / `role` invalidation reason
    "role",
})


def _serve_topology(tp) -> str:
    """Canonical serve-bundle topology string for a TP degree — the
    same rendering HybridParallelPlan.topology() produces for a pure
    'model' mesh, so serve and train-step bundles fingerprint their
    partitioning in one vocabulary."""
    tp = int(tp or 1)
    return f"model={tp}" if tp > 1 else "replicated"


def default_engine_dir() -> Optional[str]:
    """Engine path handed down by the environment — the elastic
    launcher exports ``PADDLE_TPU_ENGINE_DIR`` per rank (from its
    ``--engine_dir`` flag) so every restart epoch warm-starts from the
    same bundle instead of recompiling the world."""
    return os.environ.get("PADDLE_TPU_ENGINE_DIR") or None


def _invalidate(reason: str, detail: str = "", tier: str = "bundle"):
    _obsm.counter("aot.invalidations").inc(reason=reason, tier=tier)
    _logger.warning("aot %s invalidated (%s)%s", tier, reason,
                    f": {detail}" if detail else "")


def _reset_cache_object():
    """jax initializes its persistent-cache object ONCE per process;
    a later ``jax_compilation_cache_dir`` update is silently ignored
    unless the cache object is reset (still so on jax 0.9.0). Every dir
    change in this module goes through here or it does nothing."""
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


@contextlib.contextmanager
def _no_persistent_cache():
    """Disable the XLA persistent compilation cache for the duration.

    Engine artifacts MUST come from a real backend compile: an
    executable that was deserialized from a persistent-cache hit
    RE-serializes into a blob that cannot be loaded again (seen on
    jaxlib 0.4.37 as "Symbols not found"; on jaxlib 0.9.0, CPU, as
    "Function ... not found" at the first call) — writing one into the
    bundle would poison every future warm start of that signature.
    Process-global toggle: a concurrent compile on another thread
    merely skips the cache for its one compile (correctness
    unaffected)."""
    import jax
    if not jax.config.jax_enable_compilation_cache:
        yield
        return
    jax.config.update("jax_enable_compilation_cache", False)
    _reset_cache_object()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        _reset_cache_object()


def wire_xla_cache(cache_dir: str) -> str:
    """Point the XLA persistent compilation cache (tier 2) at
    `cache_dir`, fenced by a runtime-fingerprint file: a directory
    written by a different jaxlib/platform is wiped (counted in
    ``aot.invalidations{tier="xla_cache"}``) instead of risking a
    stale-executable hit. Where ``JAX_COMPILATION_CACHE_DIR`` is set the
    environment has placed the cache and a bundle does not move it:
    the directory in force is returned untouched."""
    import jax
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    cache_dir = os.path.abspath(cache_dir)
    fp_path = os.path.join(cache_dir, "cache_fingerprint.json")
    cur = runtime_fingerprint()
    if os.path.isdir(cache_dir):
        prev = _integrity.read_json(fp_path)
        if prev != cur:
            _invalidate("fingerprint", f"{prev} -> {cur}",
                        tier="xla_cache")
            import shutil
            shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(cache_dir, exist_ok=True)
    if not os.path.exists(fp_path):
        _integrity.atomic_write_json(fp_path, cur)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    _reset_cache_object()   # dir updates are no-ops without this
    return cache_dir


class InferenceEngine:
    """Signature → compiled-executable table consulted by
    ``ContinuousBatchingPredictor._jit_call``.

    - ``get(sig)``: tier-1 lookup. Bundle artifacts load lazily (digest
      verified); a verified hit serves with zero compilation.
    - ``compile_fallback(sig, fn, args, lock)``: the bucket-miss path —
      trace + compile live (tier 2 underneath makes the backend compile
      a disk read across restarts) and WRITE the new executable back
      into the bundle so the next process hits tier 1.
    - ``recording=True`` (the builder's mode): same machinery, but
      misses are expected calibration work — they count/span as
      ``aot.build`` events instead of ``aot.bucket_misses``.
    """

    def __init__(self, bundle: Optional[EngineBundle] = None,
                 write_back: bool = True, recording: bool = False):
        self.bundle = bundle
        self.write_back = bool(write_back)
        self.recording = bool(recording)
        self._lock = threading.Lock()
        # keyed by the sig TUPLE (hashable) — the per-decode-tick hot
        # path is one dict lookup; repr-based manifest keys are only
        # built when the bundle is consulted
        self._table: Dict[tuple, object] = {}   # sig -> callable
        self._origin: Dict[tuple, str] = {}     # sig -> bundle|fallback
        self._dead: set = set()                 # sigs that failed to load
        self.stats = {"hits": 0, "misses": 0, "loads": 0,
                      "write_backs": 0}
        self._m_hit = _obsm.counter("aot.bundle_hits")
        self._m_miss = _obsm.counter("aot.bucket_misses")
        # warm-ness is a property of what the bundle held at START —
        # this session's own write-backs must not relabel a cold start
        # as warm (the predictor stamps serve.cold_start_seconds with
        # this)
        self.warm = bool(bundle is not None and bundle.exists()
                         and bundle.artifacts())

    def get(self, sig):
        hit = self._table.get(sig)
        if hit is None and sig not in self._dead \
                and self.bundle is not None:
            with self._lock:
                hit = self._table.get(sig)
                if hit is None and sig not in self._dead:
                    hit = self._load(sig)
        if hit is not None and self._origin.get(sig) == "bundle":
            # aot.bundle_hits counts dispatches served by DESERIALIZED
            # bundle executables (tier 1) only — a live-compiled
            # fallback re-dispatching from the in-memory table must
            # not read as "warm" in telemetry
            self.stats["hits"] += 1
            kind = sig[0] if isinstance(sig, tuple) and sig else "?"
            self._m_hit.inc(kind=str(kind))
        return hit

    def _load(self, sig):
        try:
            loaded = self.bundle.load_artifact(sig_key(sig))
        except BundleInvalid as e:
            # one corrupt artifact poisons only itself; load-time
            # validate() already gated the bundle-level fingerprints
            _invalidate(e.reason, e.detail)
            self._dead.add(sig)
            return None
        if loaded is None:
            return None
        self.stats["loads"] += 1
        self._table[sig] = loaded
        self._origin[sig] = "bundle"
        return loaded

    # ------------------------------------------------------------ tier 2 --
    def compile_fallback(self, sig, fn, args, trace_lock=None):
        """Bucket miss: compile live (AOT-style, so the Compiled object
        is in hand for write-back), execute, remember, persist."""
        key = sig_key(sig)
        kind = str(sig[0]) if isinstance(sig, tuple) and sig else "?"
        self.stats["misses"] += 1
        if self.recording:
            sp = _obstr.start_span("aot.build_program", parent=None,
                                   kind=kind, sig=key[:160])
        else:
            self._m_miss.inc(kind=kind)
            sp = _obstr.start_span("aot.compile_fallback", parent=None,
                                   kind=kind, sig=key[:160])
        try:
            lock = trace_lock if trace_lock is not None \
                else threading.Lock()
            with lock:
                # tier-1 artifacts must come from a REAL compile, not
                # a persistent-cache hit (see _no_persistent_cache);
                # bundle.add_artifact round-trip-verifies as a second
                # fence (docs/DEPLOYMENT.md)
                with _no_persistent_cache():
                    compiled = fn.lower(*args).compile()
            with self._lock:
                self._table[sig] = compiled
                self._origin[sig] = "fallback"
            if self.write_back and self.bundle is not None:
                try:
                    rec = self.bundle.add_artifact(sig, compiled)
                    self.stats["write_backs"] += 1
                    sp.event("write_back", file=rec["file"],
                             bytes=rec["bytes"])
                except Exception as e:  # persistence is best-effort;
                    sp.event("write_back_failed",   # serving never dies
                             error=f"{type(e).__name__}: {e}"[:160])
            sp.end(status="ok")
        except BaseException as e:
            sp.end(status=f"error:{type(e).__name__}")
            raise
        return compiled(*args)

    def program(self, sig):
        """Direct access to a compiled program (e.g. the builder's
        captured ``forward`` parity surface) without hit accounting."""
        got = self._table.get(sig)
        if got is None and self.bundle is not None \
                and sig not in self._dead:
            with self._lock:
                got = self._table.get(sig) or self._load(sig)
        return got


# ---------------------------------------------------------------------------
# load / warm-start
# ---------------------------------------------------------------------------
def load_engine(path: str, model=None, write_back: bool = True,
                wire_cache: bool = True) -> InferenceEngine:
    """Open a bundle for serving. Validates the runtime fingerprint and
    (when `model` is given) the model hash BEFORE anything loads; a
    mismatch raises :class:`BundleInvalid` after counting it in
    ``aot.invalidations`` — a corrupted or mismatched bundle never
    serves. Artifact digests verify lazily at first use."""
    bundle = EngineBundle(path)
    with _obstr.span("aot.load", parent=None, path=path) as sp:
        try:
            m = bundle.validate(
                model_fingerprint(model) if model is not None else None)
        except BundleInvalid as e:
            _invalidate(e.reason, e.detail)
            sp.event("invalidated", reason=e.reason)
            raise
        if wire_cache:
            wire_xla_cache(bundle.xla_cache_dir)
        eng = InferenceEngine(bundle, write_back=write_back)
        sp.set_label(artifacts=len(m.get("artifacts", {})))
    return eng


def warm_start(model, path: Optional[str] = None, strict: bool = False,
               wire_cache: bool = True, runtime_config=None,
               **cb_kwargs):
    """Build a ``ContinuousBatchingPredictor`` warm-started from the
    engine bundle at `path` (default: ``$PADDLE_TPU_ENGINE_DIR``).

    Geometry comes from the bundle manifest (the executables were
    compiled against it); explicit ``cb_kwargs`` override it, but an
    override that CHANGES the compiled-in geometry (batch/page/seq/eos/
    pad) invalidates the bundle — mixed-geometry artifacts would be
    silently wrong — and triggers a clean reset. The manifest's
    ``runtime_config`` participates the same way on its COMPILED
    fields (``runtime_config.COMPILED_FIELDS``: geometry, bucket
    table, chunk threshold): passing a ``runtime_config`` that
    disagrees there — or passing one against a legacy bundle that
    recorded no config at all — invalidates (reason
    ``runtime_config``); a tuned config deploys by REBUILDING the
    bundle (``tools/autotune.py`` → ``EngineBuilder``), never by
    silently serving mismatched artifacts. Runtime-only fields
    (queue/shed/watchdog/WFS/grad-comm) may differ freely — the
    explicit config serves, the shared bundle survives. Without an
    explicit config the bundle's own baked config drives the
    predictor.

    Config-vs-observed drift: whichever config ends up serving is
    compared against the ambient FLAGS-derived config on the migrated
    knobs, and every disagreement is counted in
    ``aot.config_drift{key}`` — the operator signal that this host's
    flags no longer match what the deploy artifact encodes.

    On ANY invalidation (corrupt manifest, fingerprint or model-hash
    mismatch, geometry change, runtime-config change) the bundle is
    rejected, counted in ``aot.invalidations``, re-created empty, and
    the predictor starts as a clean live-JIT build whose compiles
    write back into the fresh bundle — the engine self-heals instead
    of serving stale programs. With ``strict=True`` the invalidation
    raises instead.

    Returns ``(predictor, engine)``.
    """
    from .. import ContinuousBatchingPredictor
    from ...framework.runtime_config import (RuntimeConfig,
                                             MIGRATED_FLAG_KNOBS,
                                             COMPILED_FIELDS)
    path = path or default_engine_dir()
    if not path:
        raise ValueError("warm_start needs an engine path (argument or "
                         "PADDLE_TPU_ENGINE_DIR)")
    mh = model_fingerprint(model)
    geometry: Dict = {}
    eff_rc: Optional[RuntimeConfig] = runtime_config
    engine: Optional[InferenceEngine] = None
    try:
        engine = load_engine(path, model=model, wire_cache=wire_cache)
        geometry = dict(engine.bundle.manifest().get("geometry", {}))
        # topology FIRST (mirror of hybrid/aot.py's train-step gate):
        # the GSPMD partitioning is compiled into every executable, so
        # a bundle built for one device topology must never serve
        # another — the mismatch gets its own `topology` reason rather
        # than drowning in the generic geometry diff
        want_tp = cb_kwargs.get("tp_degree")
        if want_tp is None and runtime_config is not None:
            want_tp = runtime_config.tp_degree
        if want_tp is not None:
            got_topo = geometry.get(
                "mesh_topology",
                _serve_topology(geometry.get("tp_degree", 1)))
            want_topo = _serve_topology(want_tp)
            if got_topo != want_topo:
                raise BundleInvalid(
                    "topology",
                    f"bundle partitioned for {got_topo!r}, requested "
                    f"{want_topo!r} — per-topology bundles: rebuild "
                    f"(or point at the bundle built) for this mesh")
        # role SECOND (per-role bundles, docs/DEPLOYMENT.md): a
        # disaggregated fleet builds one bundle per (role, topology) —
        # the calibrated PROGRAM SET differs (a prefill bundle never
        # compiled multi-token decode), so serving a decode fleet from
        # a prefill bundle must invalidate by name, not limp through
        # bucket misses
        want_role = cb_kwargs.get("role")
        if want_role is None and runtime_config is not None:
            want_role = runtime_config.serve_role
        if want_role is not None:
            got_role = geometry.get("role", "unified")
            if got_role != want_role:
                raise BundleInvalid(
                    "role",
                    f"bundle built for role {got_role!r}, requested "
                    f"{want_role!r} — per-role bundles: rebuild (or "
                    f"point at the bundle built) for this role")
        # only COMPILED-IN geometry invalidates (these are baked into
        # the executables' shapes/semantics); runtime knobs — name,
        # enable_prefix_cache, max_queue, shed_policy, watchdog — are
        # free to differ per replica/deployment without destroying the
        # shared bundle
        changed = {k: v for k, v in cb_kwargs.items()
                   if k in COMPILED_GEOMETRY_KEYS and k in geometry
                   and geometry[k] != v}
        if changed:
            raise BundleInvalid(
                "geometry", f"overrides change compiled-in geometry: "
                            f"{sorted(changed)}")
        m = engine.bundle.manifest()
        bundle_rc_d = m.get("runtime_config")
        if bundle_rc_d is not None:
            try:
                bundle_rc = RuntimeConfig.from_dict(bundle_rc_d)
            except ValueError as e:
                # hand-edited or newer-schema config: reject and
                # self-heal like any other corrupt manifest field
                raise BundleInvalid("runtime_config",
                                    f"unreadable baked config: {e}")
            if runtime_config is not None:
                # invalidate only on COMPILED disagreement: a tuned
                # bucket table / pool layout means different
                # executables, but runtime-only knobs (queue, shed,
                # watchdog, WFS quantum, grad comm) are free to differ
                # per replica — destroying the shared bundle for a
                # max_queue tweak would cost a full recompile for
                # nothing. A requested "auto" value (num_pages=None,
                # prompt_buckets=()) expresses no opinion and accepts
                # whatever the builder resolved and baked.
                rq = runtime_config.to_dict()
                changed = sorted(
                    k for k in set(bundle_rc.diff(runtime_config))
                    & COMPILED_FIELDS
                    if not (k in ("num_pages", "prompt_buckets")
                            and rq[k] in (None, [])))
                if changed:
                    raise BundleInvalid(
                        "runtime_config",
                        f"bundle config "
                        f"{str(m.get('runtime_config_hash'))[:12]}... "
                        f"vs requested "
                        f"{runtime_config.config_hash()[:12]}... "
                        f"(compiled fields: {changed})")
                # adopt the builder-resolved values for the auto
                # fields: the predictor must bucket/pool exactly as
                # the artifacts were compiled
                fills = {}
                if runtime_config.num_pages is None:
                    fills["num_pages"] = bundle_rc.num_pages
                if not runtime_config.prompt_buckets:
                    fills["prompt_buckets"] = bundle_rc.prompt_buckets
                if fills:
                    eff_rc = runtime_config.replace(**fills)
            if eff_rc is None:
                eff_rc = bundle_rc   # the baked config serves
        elif runtime_config is not None:
            # a legacy bundle (no recorded config) cannot vouch that
            # its artifacts match the requested config — serving the
            # old geometry while telemetry reports the tuned knobs
            # would be exactly the silent split this field prevents
            raise BundleInvalid(
                "runtime_config",
                "bundle predates runtime_config; rebuild to deploy an "
                "explicit config")
    except BundleInvalid as e:
        if strict:
            raise
        if e.reason in ("geometry", "runtime_config", "topology",
                        "role"):
            _invalidate(e.reason, e.detail)  # load_engine counted others
        geometry = {}
        bundle = EngineBundle.create(
            path, mh, {**cb_kwargs}, buckets={},
            runtime_config=(runtime_config.to_dict()
                            if runtime_config is not None else None))
        if wire_cache:
            wire_xla_cache(bundle.xla_cache_dir)
        engine = InferenceEngine(bundle, write_back=True)
        eff_rc = runtime_config
    if eff_rc is not None:
        # drift telemetry: the serving config vs what this host's
        # FLAGS would have produced, on the knobs flags can express —
        # a deploy whose artifact disagrees with the fleet's flag
        # state should light a dashboard, not be discovered in a
        # perf regression
        ambient = RuntimeConfig.from_flags()
        drift = eff_rc.diff(ambient)
        for field in sorted(set(drift) & set(MIGRATED_FLAG_KNOBS.values())):
            _obsm.counter("aot.config_drift").inc(key=field)
    kw = {**geometry, **cb_kwargs}
    # manifest-only fingerprint field, not a predictor kwarg
    kw.pop("mesh_topology", None)
    predictor = ContinuousBatchingPredictor(model, engine=engine,
                                            runtime_config=eff_rc, **kw)
    if not geometry:
        # reset path: persist the EFFECTIVE geometry (ctor defaults
        # resolved) so the next warm_start reconstructs an identical
        # predictor for the write-back artifacts
        try:
            engine.bundle.set_geometry({
                "max_batch_size": predictor.B,
                "page_size": predictor.page,
                "max_seq_len": predictor.max_seq_len,
                "num_pages": predictor.capacity,
                "pad_token_id": predictor.pad_token_id,
                "eos_token_id": predictor.eos_token_id,
                "tp_degree": predictor.tp,
                "mesh_topology": predictor.tp_topology,
                "role": getattr(predictor, "role", "unified"),
                **{k: v for k, v in cb_kwargs.items()
                   if isinstance(v, (int, float, str, bool,
                                     type(None)))}})
        except BundleInvalid:
            pass
    return predictor, engine

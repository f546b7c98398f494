"""Engine builder: dy2static capture → AOT compile → serialized bundle.

The builder is the AnalysisPredictor-analog's offline half (PAPER.md
§0/§1: dynamic-to-static capture feeding a static-graph executor): it
captures the model through the existing ``jit``/dy2static front door,
lowers and AOT-compiles the serving programs for an explicit set of
shape buckets (``jit(...).lower(...).compile()``), serializes each
executable, and packages everything into a versioned on-disk bundle
(bundle.py) that the loader (engine.py) warm-starts from with zero
tracing or compilation on the hot path.

What gets captured, per the bucket table:

- **prefill** — one program per (batch-bucket, prompt-bucket): the
  predictor's device-resident admission program (forward + on-device
  argmax + paged K/V scatter).
- **decode** — THE decode step (geometry-constant signature): paged
  cache write + paged attention + argmax + eos, one program for every
  step of every request.
- **mixed** — with chunked prefill in the geometry
  (``prefill_chunk_tokens``): the mixed prefill+decode step, one
  program per chunk bucket ``{page_size * 2^k <= chunk_max}`` — long
  prompts then ingest chunk-by-chunk at warm start with zero
  compilation, exactly like decode.
- **forward** — the plain captured model forward (logits) per bucket:
  the dy2static capture surface itself, used for captured-vs-eager
  parity checks and Predictor-style batch scoring. The model's
  ``forward`` may be a ``to_static``-wrapped StaticFunction — capture
  goes through ``jit.bridge.functionalize``, so the dy2static AST
  transforms (data-dependent if/while → lax.cond/while_loop) are in
  effect during tracing.
- **custom programs** — ``add_program(name, fn, *args)`` AOT-compiles
  any extra jittable function into the bundle (e.g. an eager Trainer
  step for train-then-serve restarts).

Calibration is exact-by-construction: the builder drives a real
``ContinuousBatchingPredictor`` (with the engine in recording mode)
over synthetic prompts shaped to each bucket, so the signatures in the
bundle are literally the signatures the serve loop will dispatch.
"""
from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

import numpy as np

from ...observability import metrics as _obsm
from ...observability import tracing as _obstr
from .bundle import EngineBundle, model_fingerprint
from .engine import InferenceEngine, wire_xla_cache

__all__ = ["EngineBuilder", "build_engine"]


class EngineBuilder:
    """Collects capture targets, then :meth:`build` writes the bundle.

    `prompt_buckets` are prompt-length buckets (powers of two ≥ 8 —
    the predictor's admission bucketing); `batch_sizes` the admission
    batch sizes to pre-compile per bucket (each ≤ ``max_batch_size``).
    """

    def __init__(self, model, prompt_buckets: Optional[Sequence[int]] = None,
                 batch_sizes: Optional[Sequence[int]] = None,
                 max_new_tokens: int = 2, capture_forward: bool = True,
                 runtime_config=None, **cb_kwargs):
        from ...framework.runtime_config import RuntimeConfig
        self.model = model
        # runtime_config is the tuned-knob payload (tools/autotune.py
        # output): it supplies geometry + bucket-table defaults here
        # and is recorded — with its hash — in the bundle manifest so
        # the tuning proposal ships as part of the versioned artifact.
        # The default is the PURE-DEFAULT config, NOT from_flags():
        # the builder has always pinned chunked prefill explicitly (a
        # build-host flag must not silently reshape calibration).
        self._rc = runtime_config if runtime_config is not None \
            else RuntimeConfig()
        if prompt_buckets is None:
            prompt_buckets = self._rc.prompt_buckets or (8, 16)
        self.prompt_buckets = sorted(set(int(b) for b in prompt_buckets))
        self.cb_kwargs = dict(cb_kwargs)
        self.max_new_tokens = int(max_new_tokens)
        # a prefill-role bundle (disaggregated fleets) serves exactly
        # one token per request — TTFT, then the KV span hands off —
        # so calibration drives max_new=1 and the bundle carries no
        # multi-token decode programs it would never dispatch
        if self.cb_kwargs.get("role", self._rc.serve_role) == "prefill":
            self.max_new_tokens = 1
        self.capture_forward = bool(capture_forward)
        bmax = int(self.cb_kwargs.get("max_batch_size",
                                      self._rc.max_batch_size))
        if batch_sizes is None:
            batch_sizes, n = [], 1
            while n <= bmax:
                batch_sizes.append(n)
                n *= 2
        self.batch_sizes = sorted(set(
            int(n) for n in batch_sizes if 1 <= int(n) <= bmax))
        self._extra = []   # (name, fn, args)

    def add_program(self, name: str, fn, *example_args):
        """Queue an arbitrary jittable function for AOT capture under
        signature ``("custom", name)`` (e.g. an eager Trainer step)."""
        self._extra.append((str(name), fn, example_args))
        return self

    # ------------------------------------------------------------ build --
    def _geometry(self) -> Dict:
        g = dict(self.cb_kwargs)
        rc = self._rc
        g.setdefault("max_batch_size", rc.max_batch_size)
        g.setdefault("page_size", rc.page_size)
        g.setdefault("max_seq_len", rc.max_seq_len)
        g.setdefault("pad_token_id", 0)
        g.setdefault("eos_token_id", None)
        if rc.num_pages is not None:
            g.setdefault("num_pages", rc.num_pages)
        # pinned explicitly (0 = off unless the RuntimeConfig says
        # otherwise): the predictor ctor otherwise falls back to
        # FLAGS_serve_prefill_chunk_tokens, and a flag set on the
        # BUILD host would silently chunk the calibration prompts
        # while the manifest records no threshold — the serving
        # replica would then miss the monolithic-prefill programs the
        # bundle claims to carry. (The default self._rc is the
        # pure-default config, so this stays 0 without an explicit
        # runtime_config.)
        g.setdefault("prefill_chunk_tokens", rc.prefill_chunk_tokens)
        # program variants, pinned explicitly for the same reason as
        # the chunk threshold: a build-host FLAGS_serve_spec_draft_
        # tokens / FLAGS_serve_sampling must not silently reshape what
        # the manifest claims was calibrated
        g.setdefault("spec_draft_tokens", rc.spec_draft_tokens)
        g.setdefault("sampling_enabled", rc.sampling_enabled)
        # per-topology bundles: the tensor-parallel degree is compiled
        # into every executable (GSPMD partitioning), and the manifest
        # records the canonical topology string alongside it so
        # warm_start can reject a topology mismatch by name
        g.setdefault("tp_degree", rc.tp_degree)
        from .engine import _serve_topology
        g.setdefault("mesh_topology", _serve_topology(g["tp_degree"]))
        # per-role bundles: the serve role rides the manifest next to
        # the topology string so warm_start can reject a role mismatch
        # by name ("role" invalidation). The program-set differences
        # fall out of the role overlay (runtime_config.for_role) and
        # the prefill max_new clamp above — this field just names them.
        g.setdefault("role", rc.serve_role)
        return g

    def effective_runtime_config(self):
        """The config the bundle actually encodes: the input
        RuntimeConfig with the builder's resolved geometry and bucket
        table folded in — what gets hashed into the manifest and what
        a warm-started predictor reconstructs."""
        g = self._geometry()
        return self._rc.replace(
            max_batch_size=int(g["max_batch_size"]),
            page_size=int(g["page_size"]),
            max_seq_len=int(g["max_seq_len"]),
            num_pages=g.get("num_pages"),
            prefill_chunk_tokens=int(g["prefill_chunk_tokens"]),
            spec_draft_tokens=int(g["spec_draft_tokens"]),
            sampling_enabled=bool(g["sampling_enabled"]),
            tp_degree=int(g["tp_degree"]),
            serve_role=str(g["role"]),
            prompt_buckets=tuple(self.prompt_buckets))

    def build(self, path: str, wire_cache: bool = True,
              seed: int = 0) -> Dict:
        """Capture, compile, serialize; returns the bundle manifest."""
        from .. import ContinuousBatchingPredictor
        geometry = self._geometry()
        eff_rc = self.effective_runtime_config()
        buckets = {"prompt_buckets": self.prompt_buckets,
                   "batch_sizes": self.batch_sizes,
                   "max_new_tokens": self.max_new_tokens}
        t0 = time.perf_counter()
        with _obstr.span("aot.build", parent=None, path=path,
                         prompt_buckets=str(self.prompt_buckets),
                         batch_sizes=str(self.batch_sizes),
                         config_hash=eff_rc.config_hash()[:12]) as sp:
            bundle = EngineBundle.create(
                path, model_fingerprint(self.model), geometry, buckets,
                runtime_config=eff_rc.to_dict())
            if wire_cache:
                wire_xla_cache(bundle.xla_cache_dir)
            engine = InferenceEngine(bundle, write_back=True,
                                     recording=True)
            # the calibration predictor runs the SAME config the
            # manifest records (bucket table included), so every
            # signature it dispatches is a signature a warm-started
            # replica of this bundle will dispatch
            ctor_geo = {k: v for k, v in geometry.items()
                        if k != "mesh_topology"}   # manifest-only field
            cb = ContinuousBatchingPredictor(self.model, engine=engine,
                                             runtime_config=eff_rc,
                                             **ctor_geo)
            rng = np.random.RandomState(seed)
            vocab = int(getattr(getattr(self.model, "config", None),
                                "vocab_size", 0) or 256)
            for pb in self.prompt_buckets:
                for n in self.batch_sizes:
                    # length == bucket: LLMPredictor._bucket(pb) == pb
                    # for the power-of-two buckets, so the admission
                    # round compiles exactly the (n→pow2, pb) program
                    prompts = [rng.randint(2, vocab, (pb,)).tolist()
                               for _ in range(n)]
                    cb.generate(prompts,
                                max_new_tokens=self.max_new_tokens)
                    sp.event("bucket", prompt_bucket=pb, batch=n)
            if geometry.get("prefill_chunk_tokens"):
                self._capture_mixed(cb, rng, vocab, sp)
            if geometry.get("spec_draft_tokens"):
                self._compile_spec_sig(cb)
                sp.event("spec", draft_tokens=int(
                    geometry["spec_draft_tokens"]))
            if self.capture_forward:
                self._capture_forward(engine, rng, vocab, sp)
            for name, fn, args in self._extra:
                self._capture_custom(engine, name, fn, args, sp)
            manifest = bundle.manifest(refresh=True)
            sp.set_label(artifacts=len(manifest.get("artifacts", {})),
                         build_s=round(time.perf_counter() - t0, 3))
        _obsm.gauge("aot.build_seconds", unit="s").set(
            time.perf_counter() - t0)
        return manifest

    # ---------------------------------------------------------- capture --
    def _capture_mixed(self, cb, rng, vocab, sp):
        """Chunked prefill is part of the geometry: pre-capture every
        ("mixed", Qb, ...) signature the serve loop can dispatch, one
        long synthetic prompt per chunk bucket Qb in
        {page * 2^k <= chunk_max}. The scheduler picks the largest
        bucket while a prompt's remainder exceeds it and the smallest
        covering bucket for the final chunk, so a prompt of length
        chunk_max + Qb/2 + 1 exercises exactly {chunk_max, Qb} (and
        chunk_max + 1 exercises {chunk_max, page}) without steering
        the adaptive policy. A bucket whose steering prompt cannot fit
        max_seq_len is still REACHABLE at serve time (any prompt over
        the threshold dispatches the chunk_max program; decode load
        and final chunks shrink the tick bucket arbitrarily), so it is
        compiled directly with dispatch-shaped operands instead of
        skipped — warm start must stay zero-compile for every
        dispatchable signature."""
        cm = cb._chunk_max
        qb, buckets = cb.page, []
        while qb <= cm:
            buckets.append(qb)
            qb *= 2
        driven = set()
        for qb in buckets:
            tail = 1 if qb in (cb.page, cm) else qb // 2 + 1
            length = cm + tail
            if length + self.max_new_tokens > cb.max_seq_len:
                self._compile_mixed_bucket(cb, qb)
                sp.event("mixed_bucket", q_bucket=qb, direct=True)
            elif length not in driven:   # page and cm share a prompt
                driven.add(length)
                prompt = rng.randint(2, vocab, (length,)).tolist()
                cb.generate([prompt],
                            max_new_tokens=self.max_new_tokens)
                sp.event("mixed_bucket", q_bucket=qb,
                         prompt_len=length)

    def _compile_mixed_bucket(self, cb, qb):
        """Compile one ("mixed", qb, ...) signature with operands
        shaped exactly like `_dispatch_mixed_step`'s (every slot idle
        over the trash page, single-token spans) — the fallback when
        the steering prompt for this bucket cannot fit max_seq_len.
        Keep the signature tuple and operand dtypes in lockstep with
        the dispatcher; tests/test_mixed_step.py's zero-compile case
        guards the pairing."""
        import jax.numpy as jnp
        cb._ensure_ready()
        tables = np.full((cb.B, cb.pages_per_seq), cb._trash, np.int32)
        ctx = np.ones((cb.B,), np.int32)
        span_ids = np.full((cb.B, qb), cb.pad_token_id, np.int32)
        q_lens = np.ones((cb.B,), np.int32)
        tok_in = jnp.asarray(np.zeros((cb.B,), np.int32))
        meta_args = _idle_span_meta(cb)
        sig = ("mixed", qb, tables.shape,
               tuple(np.shape(x) for x in meta_args))
        _, _, new_k, new_v = cb._jit_call(
            sig, cb._mixed_jit, cb._p_vals, cb._b_vals, cb.pool.k,
            cb.pool.v, tables, ctx, span_ids, q_lens, tok_in,
            *meta_args)
        cb.pool.k, cb.pool.v = list(new_k), list(new_v)

    def _compile_spec_sig(self, cb):
        """Compile the ("spec", k+1, ...) speculative-verify signature
        directly with dispatch-shaped operands (every slot idle over
        the trash page, one-token spans, greedy sampling operands).
        Calibration traffic cannot reliably steer the drafter — whether
        a prompt-lookup match fires depends on the synthetic tokens —
        but the signature is dispatchable whenever ANY request's
        history matches, so warm start must carry it. The sampling
        decode variant needs no special handling: with
        ``sampling_enabled`` in the geometry the calibration serve
        loop dispatches ("decode_sample", ...) instead of ("decode",
        ...) on every tick. Keep the sig tuple and operand dtypes in
        lockstep with `_dispatch_spec_step`."""
        import jax.numpy as jnp
        cb._ensure_ready()
        qs = cb._spec_k + 1
        tables = np.full((cb.B, cb.pages_per_seq), cb._trash, np.int32)
        ctx = np.ones((cb.B,), np.int32)
        span_ids = np.full((cb.B, qs), cb.pad_token_id, np.int32)
        q_lens = np.ones((cb.B,), np.int32)
        tok_in = jnp.asarray(np.zeros((cb.B,), np.int32))
        from ...generation.sampling import sampling_operands
        ops = sampling_operands([None] * cb.B)
        samp = (ops["temperature"], ops["top_k"], ops["top_p"],
                ops["seed"], np.zeros((cb.B,), np.int32))
        meta_args = _idle_span_meta(cb)
        sig = ("spec", qs, tables.shape,
               tuple(np.shape(x) for x in meta_args))
        _, _, _, new_k, new_v = cb._jit_call(
            sig, cb._spec_jit, cb._p_vals, cb._b_vals, cb.pool.k,
            cb.pool.v, tables, ctx, span_ids, q_lens, tok_in, *samp,
            *meta_args)
        cb.pool.k, cb.pool.v = list(new_k), list(new_v)

    def _capture_forward(self, engine, rng, vocab, sp):
        """AOT-capture the model's plain forward (logits) per bucket
        through the jit/dy2static front door: ``functionalize`` swaps
        params/buffers for traced arrays and runs the (possibly
        to_static-transformed) python forward under jax tracing."""
        import jax
        import jax.numpy as jnp
        from ...jit.bridge import functionalize
        from ...tensor import Tensor

        pure_fn, p_vals, b_vals, _, _ = functionalize(
            self.model, training=False)

        def logits_fn(p, b, ids):
            out, _, _ = pure_fn(list(p), list(b), jax.random.key(0),
                                Tensor(ids))
            first = out[0] if isinstance(out, (list, tuple)) else out
            return first._value if isinstance(first, Tensor) else first

        jf = jax.jit(logits_fn)
        for pb in self.prompt_buckets:
            ids = rng.randint(2, vocab, (1, pb)).astype(np.int32)
            sig = ("forward", (1, pb))
            engine.compile_fallback(sig, jf, (p_vals, b_vals, ids))
            sp.event("forward", prompt_bucket=pb)

    def _capture_custom(self, engine, name, fn, args, sp):
        import jax
        jf = fn if hasattr(fn, "lower") else jax.jit(fn)
        engine.compile_fallback(("custom", name), jf, args)
        sp.event("custom", name=name)


def _idle_span_meta(cb):
    """The ragged metadata operands of a span program (mixed, verify)
    with every slot idle over the trash page, as the dispatcher would
    pass them; none where the predictor's span programs take none
    (`cb.span_ragged`)."""
    if not cb.span_ragged:
        return ()
    from ...kernels.paged_attention import RaggedMetaBuilder
    mb = RaggedMetaBuilder(cb.B, cb.pages_per_seq, cb.page, cb._trash)
    for b in range(cb.B):
        mb.clear_slot(b)
    m = mb.meta()
    return tuple(m[k].copy() for k in RaggedMetaBuilder.FIELDS)


def build_engine(model, path: str, prompt_buckets=None,
                 batch_sizes=None, max_new_tokens: int = 2,
                 wire_cache: bool = True, runtime_config=None,
                 **cb_kwargs) -> Dict:
    """One-call builder (see :class:`EngineBuilder`)."""
    return EngineBuilder(model, prompt_buckets=prompt_buckets,
                         batch_sizes=batch_sizes,
                         max_new_tokens=max_new_tokens,
                         runtime_config=runtime_config,
                         **cb_kwargs).build(path, wire_cache=wire_cache)

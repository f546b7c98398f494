"""paddle.inference — the deployment predictor.

Reference parity: paddle/fluid/inference/api/analysis_predictor.cc +
python/paddle/inference/wrapper.py (Config, create_predictor, zero-copy
input/output handles). TPU-native design per the north star: the ~200 IR
fusion passes + TensorRT subgraphing are subsumed by whole-graph XLA
compilation with a persistent compile cache; the predictor jit-compiles
the network per input signature and serves from cache.
"""
from __future__ import annotations

import os
import sys
import threading
from typing import Callable, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..tensor import Tensor
from .._grad_mode import no_grad
from ..framework import faults as _faults
from ..observability import metrics as _obsm
from ..observability import runtime as _obsrt
from ..observability import tracing as _obstr
from ..kernels._common import kernel_partition_scope


class DecodeWedgedError(RuntimeError):
    """The decode watchdog tripped: a dispatched decode step's host
    sync did not resolve within the deadline (wedged device/runtime).
    ContinuousBatchingPredictor fails the pending requests
    (last_status 'watchdog') instead of hanging generate()."""


class PrecisionType:
    Float32 = "float32"
    Half = "float16"
    Bfloat16 = "bfloat16"
    Int8 = "int8"


class PlaceType:
    CPU = "cpu"
    GPU = "tpu"  # parity alias
    TPU = "tpu"


class Config:
    """paddle_infer.Config parity."""

    def __init__(self, prog_file=None, params_file=None):
        self.prog_file = prog_file
        self.params_file = params_file
        self._model_dir = None
        self._precision = PrecisionType.Float32
        self._device = "tpu"
        self._device_id = 0
        self._enable_memory_optim = True
        self._compile_cache_dir = None
        self._model_factory: Optional[Callable] = None

    def set_model(self, prog_file, params_file=None):
        self.prog_file = prog_file
        self.params_file = params_file

    def set_prog_file(self, f):
        self.prog_file = f

    def model_dir(self):
        return self._model_dir

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0,
                       precision=PrecisionType.Float32):
        self._device = "tpu"
        self._device_id = device_id
        self._precision = precision

    enable_use_tpu = enable_use_gpu

    def disable_gpu(self):
        self._device = "cpu"

    def enable_xla(self, precision=PrecisionType.Float32):
        self._precision = precision

    def enable_tensorrt_engine(self, *args, **kwargs):
        # TRT is subsumed by XLA; accept and record precision if given
        precision = kwargs.get("precision_mode")
        if precision:
            self._precision = precision

    def enable_memory_optim(self, x=True):
        self._enable_memory_optim = x

    def set_cpu_math_library_num_threads(self, n):
        pass

    def switch_ir_optim(self, x=True):
        pass

    def enable_compile_cache(self, cache_dir):
        self._compile_cache_dir = cache_dir

    def set_model_factory(self, factory: Callable):
        """TPU-native extension: a callable returning the nn.Layer whose
        weights `params_file` holds (replaces ProgramDesc deserialization)."""
        self._model_factory = factory


class _IOHandle:
    def __init__(self, predictor, name, is_input):
        self._p = predictor
        self.name = name
        self._is_input = is_input

    def reshape(self, shape):
        pass

    def copy_from_cpu(self, arr: np.ndarray):
        self._p._feeds[self.name] = jnp.asarray(arr)

    def copy_to_cpu(self) -> np.ndarray:
        return np.asarray(self._p._outputs[self.name])

    def share_external_data(self, data):
        self.copy_from_cpu(np.asarray(data))


class Predictor:
    """XLA compile-and-cache predictor."""

    def __init__(self, config: Config):
        self._config = config
        self._feeds: Dict[str, jax.Array] = {}
        self._outputs: Dict[str, jax.Array] = {}
        self._layer = None
        self._compiled = {}
        self._load()

    def _load(self):
        cfg = self._config
        self._aot = None
        if cfg._model_factory is not None:
            self._layer = cfg._model_factory()
            if cfg.params_file and os.path.exists(cfg.params_file):
                from ..framework_io import load as pload
                self._layer.set_state_dict(pload(cfg.params_file))
        else:
            from ..jit.api import _saved_layers, AOTLayer
            if cfg.prog_file:
                base = cfg.prog_file[:-8] if cfg.prog_file.endswith(".pdmodel") \
                    else cfg.prog_file
                if os.path.exists(base + ".pdexec"):
                    # serialized jax.export artifact: fresh-process load,
                    # no model class, no re-trace (analysis_predictor.cc
                    # LoadProgramDesc role)
                    import pickle
                    with open(base + ".pdmodel", "rb") as f:
                        meta = pickle.load(f)
                    self._aot = AOTLayer(base, meta)
                    self._layer = self._aot
                    self._input_names = ["x%d" % i for i in range(8)]
                    return
                ap = os.path.abspath(base)
                if ap in _saved_layers:
                    self._layer = _saved_layers[ap]
        if self._layer is None:
            raise RuntimeError(
                "Predictor needs a jit.save'd AOT artifact (.pdexec), "
                "config.set_model_factory(...), or an in-process "
                "jit.save'd model")
        if hasattr(self._layer, "eval"):
            self._layer.eval()
        if cfg._precision in (PrecisionType.Bfloat16, PrecisionType.Half) \
                and hasattr(self._layer, "bfloat16"):
            self._layer.bfloat16()
        self._input_names = ["x%d" % i for i in range(8)]

    def get_input_names(self) -> List[str]:
        return self._input_names

    def get_input_handle(self, name) -> _IOHandle:
        return _IOHandle(self, name, True)

    def get_output_names(self) -> List[str]:
        return list(self._outputs.keys()) or ["out0"]

    def get_output_handle(self, name) -> _IOHandle:
        return _IOHandle(self, name, False)

    def run(self, inputs: Optional[List[np.ndarray]] = None):
        if inputs is not None:
            feeds = [jnp.asarray(a) for a in inputs]
        else:
            feeds = [self._feeds[k] for k in
                     sorted(self._feeds, key=self._input_names.index)]
        if self._aot is not None:
            with no_grad():
                out = self._aot(*feeds)
            outs = [o._value for o in (out if isinstance(out, tuple)
                                       else (out,))]
            self._outputs = {f"out{i}": o for i, o in enumerate(outs)}
            if inputs is not None:
                return [np.asarray(o) for o in outs]
            return True
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in feeds)
        if sig not in self._compiled:
            from ..jit.bridge import functionalize
            pure_fn, p_vals, b_vals, _, _ = functionalize(
                self._layer, training=False)

            @jax.jit
            def infer(p, b, args):
                out, _, _ = pure_fn(p, b, jax.random.key(0), *args)
                outs = out if isinstance(out, (list, tuple)) else (out,)
                return [o._value if isinstance(o, Tensor) else o for o in outs]
            self._compiled[sig] = (infer, p_vals, b_vals)
        infer, p_vals, b_vals = self._compiled[sig]
        with no_grad():
            outs = infer(p_vals, b_vals, feeds)
        self._outputs = {f"out{i}": o for i, o in enumerate(outs)}
        if inputs is not None:
            return [np.asarray(o) for o in outs]
        return True


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


def convert_to_mixed_precision(*args, **kwargs):
    raise NotImplementedError("use Config.enable_xla(precision=...) instead")


class LLMPredictor:
    """Batched autoregressive serving predictor.

    Reference parity: PaddleNLP llm/predict/predictor.py (the serving
    entry that drives block_multihead_attention inference) — here backed
    by the jitted static-cache generate loop (paddle_tpu.generation),
    compiled once per (batch, prompt-bucket, max-new) shape and cached.

    Prompts are python lists of token ids (ragged); the predictor
    left-pads to a power-of-two bucket so repeated calls hit the XLA
    compile cache, splits into micro-batches of `max_batch_size`, and
    strips padding from the returned sequences.
    """

    def __init__(self, model, max_batch_size=8, pad_token_id=0,
                 eos_token_id=None, quant_type=None, **generate_defaults):
        self.model = model
        self.max_batch_size = max_batch_size
        self.pad_token_id = pad_token_id
        self.eos_token_id = eos_token_id
        self.generate_defaults = generate_defaults
        model.eval()
        if quant_type is not None:
            self._apply_weight_only(quant_type)

    def _apply_weight_only(self, quant_type):
        """Round every 2-D projection weight (embeddings excluded)
        through weight-only quantization (parity: PaddleNLP predictor
        --quant_type weight_only_int8/int4). The decode loop then reads
        the quantization-error-bearing weights; on TPU the int storage
        is realized by the serving artifact, so here the *numerics* of
        the quantized checkpoint are what's reproduced."""
        from ..nn.quant import weight_quantize, weight_dequantize
        from ..nn.layers_common import Embedding
        from ..distributed.fleet.meta_parallel.mp_layers import (
            VocabParallelEmbedding)
        algo = {"int8": "weight_only_int8", "int4": "weight_only_int4",
                "weight_only_int8": "weight_only_int8",
                "weight_only_int4": "weight_only_int4"}.get(quant_type)
        if algo is None:
            raise ValueError(f"unsupported quant_type {quant_type!r}")
        for name, layer in self.model.named_sublayers():
            w = getattr(layer, "weight", None)
            if (w is None or w.ndim != 2
                    or isinstance(layer, (Embedding,
                                          VocabParallelEmbedding))):
                continue  # embeddings quantize on the wrong axis
            qw, sc = weight_quantize(w, algo=algo)
            deq = weight_dequantize(qw, sc, algo=algo)
            if algo == "weight_only_int4":
                deq = deq[:int(w.shape[0])]
            w.set_value(deq.astype(str(w.dtype)))

    @staticmethod
    def _bucket(n):
        b = 8
        while b < n:
            b *= 2
        return b

    def generate(self, prompts, max_new_tokens=32, **kwargs):
        """prompts: List[List[int]] → List[List[int]] (new tokens only,
        eos/pad stripped)."""
        opts = dict(self.generate_defaults)
        opts.update(kwargs)
        results = []
        for i in range(0, len(prompts), self.max_batch_size):
            chunk = prompts[i:i + self.max_batch_size]
            results.extend(self._run_chunk(chunk, max_new_tokens, opts))
        return results

    def _run_chunk(self, chunk, max_new_tokens, opts):
        n = len(chunk)
        bs = self.max_batch_size
        slen = self._bucket(max(len(p) for p in chunk))
        ids = np.full((bs, slen), self.pad_token_id, np.int32)
        mask = np.zeros((bs, slen), np.int32)
        for r, p in enumerate(chunk):
            ids[r, slen - len(p):] = p    # left padding
            mask[r, slen - len(p):] = 1
        if n < bs:  # fill idle rows with a 1-token dummy prompt
            ids[n:, -1] = self.pad_token_id
            mask[n:, -1] = 1
        call = dict(max_new_tokens=max_new_tokens,
                    eos_token_id=self.eos_token_id,
                    pad_token_id=self.pad_token_id)
        call.update(opts)  # per-call/constructor kwargs win
        eos = call["eos_token_id"]
        pad = call["pad_token_id"]
        out, _ = self.model.generate(ids, attention_mask=mask, **call)
        out = np.asarray(out.numpy())
        decoded = []
        for r in range(n):
            toks = out[r].tolist()
            if eos is not None and eos in toks:
                # cutting at eos also removes the artificial pad tail the
                # finished-row mask emits; rows that never finished (or
                # eos=None) contain only real tokens — return them intact
                toks = toks[:toks.index(eos)]
            decoded.append(toks)
        return decoded


class SpeculativePredictor:
    """Greedy speculative decoding (reference parity: PaddleNLP
    predictor speculate_method draft_model / upstream fused speculative
    decode). A small draft model proposes `gamma` tokens; the target
    model verifies them all with ONE forward pass and accepts the
    longest matching prefix plus its own correction token.

    With greedy acceptance the output is BITWISE IDENTICAL to plain
    greedy decoding of the target model — the draft only changes how
    many target forwards are needed (1 per accepted run instead of 1
    per token). TPU framing: each verify is a batched prefill-shaped
    matmul-heavy forward (MXU-friendly), replacing gamma bandwidth-bound
    single-token decode steps."""

    def __init__(self, model, draft_model, gamma=4, eos_token_id=None):
        self.model = model
        self.draft = draft_model
        self.gamma = int(gamma)
        self.eos_token_id = eos_token_id
        model.eval()
        draft_model.eval()
        self.stats = {"target_calls": 0, "accepted": 0, "proposed": 0}

    @staticmethod
    def _greedy_next(model, ids_np, last_only=False):
        """argmax of the logits; [B, S] int32, or [B] when last_only
        (draft steps need only the final position — avoids shipping the
        whole [S, V] logits array to host per proposed token)."""
        with no_grad():
            out = model(Tensor(jnp.asarray(ids_np, jnp.int32)))
        logits = (out[0] if isinstance(out, tuple) else out)._value
        if last_only:
            return np.argmax(np.asarray(logits[:, -1]), axis=-1)
        return np.argmax(np.asarray(logits), axis=-1)

    def generate(self, prompt, max_new_tokens=32):
        """Single-sequence greedy speculative decode.
        prompt: List[int] -> List[int] (new tokens)."""
        cur = list(prompt)
        new = []
        while len(new) < max_new_tokens:
            g = min(self.gamma, max_new_tokens - len(new))
            # draft proposes g tokens autoregressively (greedy)
            d_cur = list(cur)
            proposal = []
            for _ in range(g):
                nxt = int(self._greedy_next(self.draft,
                                            np.asarray([d_cur]),
                                            last_only=True)[0])
                proposal.append(nxt)
                d_cur.append(nxt)
            # one target forward verifies all proposals
            verify = np.asarray([cur + proposal])
            tgt = self._greedy_next(self.model, verify)[0]
            self.stats["target_calls"] += 1
            self.stats["proposed"] += g
            base = len(cur) - 1   # tgt[base] = target's next after cur
            accepted = 0
            while (accepted < g
                   and proposal[accepted] == int(tgt[base + accepted])):
                accepted += 1
            self.stats["accepted"] += accepted
            # accepted prefix + the target's own next token
            emit = proposal[:accepted] + [int(tgt[base + accepted])]
            for t in emit:
                if len(new) >= max_new_tokens:
                    break
                new.append(t)
                cur.append(t)
                if self.eos_token_id is not None and t == self.eos_token_id:
                    return new
        return new


# PagedKVPool moved to generation.kv_cache (it is cache infrastructure
# shared with PrefixCache); re-exported here for API stability.
from ..generation.kv_cache import (PagedKVPool, PrefixCache,  # noqa: E402
                                   StatePool)


def _raw(x):
    """The array under a Tensor (or the array itself)."""
    return getattr(x, "_value", x)


class ContinuousBatchingPredictor:
    """Continuous-batching LLM server loop (reference parity: the
    PaddleNLP inference server's in-flight batching over
    block_multihead_attention), rebuilt around a device-resident fast
    path (cf. PAPERS.md "Ragged Paged Attention" — paged-KV data
    movement and per-step host/device round-trips dominate TPU serving
    cost):

    - **Caches by the model's declaration.** `model.cache_layout()`
      says what each layer keeps between steps: an attention layer's
      K/V is paged (PagedKVPool, over those layers only); a recurrent
      layer keeps a conv window and a float32 SSM state, one row a
      slot, constant in the context's length (StatePool). Every program
      threads both, donated alike. With recurrent layers the prefix
      cache is derived off and chunked prefill, speculative decoding,
      tensor parallelism and the prefill/decode roles are refused by
      name (docs/SERVING.md "Hybrid models: state beside pages"). A
      layer whose attention selects its keys by a learned indexer
      declares an index key a token: a third page array under the same
      page ids, threaded and donated with K and V; the same four are
      refused and the prefix cache is derived off (docs/SERVING.md
      "Sparse attention: an index cache beside the pages").
      A latent-attention layer declares ONE row a token for all
      heads: one page array in place of K and V, under the same page
      ids (docs/SERVING.md "Latent pages: one row a token"). A model
      that sets `long_prefill` gets the prefill for prompts of
      thousands of tokens: key validity in, the last position's logits
      out, at most two prompts a program.
    - **Device-resident prefill.** Admission runs ONE jitted program
      per (batch, prompt-bucket) that embeds the causal/padding mask
      in-graph, runs the forward, computes the greedy next token for
      every position on device, and scatters the attention layers' K/V
      straight into the paged pool (and each prompt's final recurrent
      state into its slot's row). Prompt K/V never visits the host; the only
      admission download is the small int32 next-token matrix. Multiple
      queued prompts sharing a length bucket prefill as one batch.
    - **Prefix caching.** A hash-trie over page-aligned prompt prefixes
      (generation.kv_cache.PrefixCache) with refcounted pages: a
      repeated prefix reuses the cached pages — a full hit admits with
      ZERO forward passes (the cached greedy continuation token is
      stored in the trie) and a partial hit prefills only the suffix
      against the cached pages. Divergence inside a shared page is
      resolved by copy-on-write. Cached-but-idle pages are reclaimed
      LRU-first under allocation pressure.
    - **Sync-free decode.** The decode step is ONE jitted program that
      writes K/V, attends via the paged kernel, and arg-maxes the
      logits on device; the host dispatches step t+1 (feeding step t's
      device-resident token straight back in) BEFORE syncing step t's
      token, so the device never idles on the host fetch. The paged
      kernel finds a slot's live pages from the block table itself;
      the ragged-grid metadata the span programs (mixed, verify) read
      is maintained incrementally (kernels.paged_attention.
      RaggedMetaBuilder) — O(1) per step instead of a full rebuild.
    - **No head-of-line blocking.** Admission scans the whole queue for
      admissible requests instead of only the head; a large request
      waiting for pages no longer starves small ones behind it
      (serving.hol_skips counts the pass-overs).
    - **Chunked prefill (mixed steps).** With `prefill_chunk_tokens`
      set (or FLAGS_serve_prefill_chunk_tokens), prompts over the
      threshold ingest as page-aligned chunks through ONE mixed
      prefill+decode program per tick (the variable-query ragged
      kernel): a long prompt no longer monopolizes the device — the
      in-flight decodes take their normal token step in the SAME
      dispatch, and the chunk size adapts to the decode load
      (docs/SERVING.md "Chunked prefill"; serving.chunked_prefill.*
      and serve.mixed_step_seconds in the catalog). Greedy output is
      token-identical to the unchunked path.

    Greedy decoding (argmax), matching model.generate's default.
    """

    # Tombstone of a deleted option, accepted and ignored by the
    # constructor: benchmarks/rehearse.py:102 passes the keyword, and
    # rehearse.py:118, runners/serve_open.py:129 and
    # runners/serve_closed.py:140 read the attribute (as does an AOT
    # bundle manifest written with the key). Goes when they do.
    use_ragged = property(lambda self: False)

    def __init__(self, model, max_batch_size=None, page_size=None,
                 num_pages=None, max_seq_len=None, pad_token_id=0,
                 eos_token_id=None, kv_dtype=None, use_ragged="auto",
                 enable_prefix_cache=True, max_queue=None,
                 shed_policy=None, decode_watchdog_s=None,
                 name=None, engine=None, prefill_chunk_tokens=None,
                 runtime_config=None, spec_draft_tokens=None,
                 spec_ngram_max=None, sampling_enabled=None,
                 tp_degree=None, devices=None, role=None):
        import math as _m
        import time as _time
        from ..framework.runtime_config import RuntimeConfig
        model.eval()
        # RuntimeConfig (framework/runtime_config.py): the typed knob
        # bag. Explicit ctor args override it; unset args fall back to
        # the config; a missing config falls back to the FLAGS-sourced
        # default (the pre-migration behavior, bit for bit). The
        # config rides into AOT bundle manifests so an autotune
        # proposal ships as a versioned artifact (docs/DEPLOYMENT.md).
        self._rc = runtime_config
        rc = runtime_config if runtime_config is not None \
            else RuntimeConfig.from_flags()
        if max_batch_size is None:
            max_batch_size = rc.max_batch_size
        if page_size is None:
            page_size = rc.page_size
        if num_pages is None:
            num_pages = rc.num_pages      # may stay None: derived below
        if max_seq_len is None:
            max_seq_len = rc.max_seq_len
        if max_queue is None:
            max_queue = rc.max_queue
        if shed_policy is None:
            shed_policy = rc.shed_policy
        # tuned admission bucket table; () = power-of-two auto
        self._rc_buckets = tuple(rc.prompt_buckets)
        # AOT warm start (inference.aot): when an engine is attached,
        # _jit_call consults its serialized-executable table first — a
        # bucket hit dispatches with ZERO trace/compile; a miss falls
        # back to live JIT and writes the new executable back into the
        # bundle. serve.cold_start_seconds (construction → first token)
        # is recorded either way, labeled cold/warm.
        self._engine = engine
        self._t_ctor = _time.perf_counter()
        self._cold_start_pending = True
        # `name` identifies this predictor as one replica of a pool
        # (serving/router.py): when set, every serving.* metric and
        # serve.request span carries a replica=<name> label so
        # per-replica cache hits/utilization are separable downstream
        self.name = name
        self._mlbl = {"replica": name} if name else {}
        # the serve loop's current pass (observability.tracing.tick):
        # the resolvers time their blocking read as a stage of it
        self._tick = _obstr.NULL_TICK
        # disaggregated serving role (docs/SERVING.md "Disaggregated
        # prefill/decode"): "prefill" replicas fill KV pages and hand
        # off at first token, "decode" replicas resume the sync-free
        # loop from an imported KVPageSpan, "unified" (the default)
        # keeps the historical do-everything behavior — including the
        # exact metric label sets (role joins labels only when set, so
        # unified fleets stay byte-identical downstream).
        if role is None:
            role = str(getattr(rc, "serve_role", "unified") or "unified")
        from ..framework.runtime_config import SERVE_ROLES
        if role not in SERVE_ROLES:
            raise ValueError(
                f"role must be one of {SERVE_ROLES}, got {role!r}")
        self.role = role
        if role != "unified":
            self._mlbl["role"] = role
        # tensor-parallel serving (docs/SERVING.md "Tensor-parallel
        # replicas"): tp_degree > 1 runs every serve program under
        # GSPMD over a 'model' mesh spanning this replica's device
        # group — weights NamedSharding'ed over 'model', KV pages
        # sharded over KV heads. `devices` pins the group (the router
        # partitions the host's devices across replicas); default: the
        # first tp_degree devices.
        if tp_degree is None:
            tp_degree = int(getattr(rc, "tp_degree", 1) or 1)
        self.tp = max(1, int(tp_degree))
        self._tp_mesh = None
        self._tp_plan = None
        self.tp_devices = []
        self.tp_topology = "replicated"
        # a single-device replica given its device (the router hands
        # every replica its own) commits weights and pool there; the
        # serve programs follow their committed operands
        self._device = None
        if self.tp == 1 and devices is not None:
            devs = list(devices)
            if len(devs) != 1:
                raise ValueError(
                    f"tp_degree=1 takes one device, got {len(devs)}")
            self._device = devs[0]
        if self.tp > 1:
            from ..distributed.fleet.hybrid.plan import HybridParallelPlan
            devs = list(devices) if devices is not None else jax.devices()
            if len(devs) < self.tp:
                raise ValueError(
                    f"tp_degree={self.tp} needs {self.tp} devices, got "
                    f"{len(devs)}")
            self.tp_devices = devs[:self.tp]
            self._tp_plan = HybridParallelPlan.from_spec(
                f"model={self.tp}", zero_stage=0)
            self._tp_mesh = self._tp_plan.build_mesh(
                devices=self.tp_devices)
            self.tp_topology = self._tp_plan.topology()
            # device-group label: per-replica report views group the
            # utilization table by it so a 2-device replica reads as
            # one row spanning "0-1", not two phantom replicas
            ids = [getattr(d, "id", i)
                   for i, d in enumerate(self.tp_devices)]
            self._mlbl["devices"] = (
                f"{ids[0]}-{ids[-1]}"
                if ids == list(range(ids[0], ids[-1] + 1))
                else ",".join(str(i) for i in ids))
        # where the small per-step operands are committed (_place):
        # replicated over the TP mesh, on the replica's own device, or
        # nowhere in particular
        self._operand_placement = self._device
        if self._tp_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._operand_placement = NamedSharding(self._tp_mesh,
                                                    PartitionSpec())
        # replicas of one model run in separate threads (serving/
        # router.py) but TRACE through the same model object: jax
        # tracing executes the Python forward with jit.bridge
        # .bound_state swapping the shared Tensor._values for tracers,
        # so two concurrent first-compiles would leak each other's
        # tracers. One lock per MODEL serializes tracing only;
        # already-compiled signatures dispatch without it.
        self._trace_lock = model.__dict__.setdefault(
            "_cb_trace_lock", threading.Lock())
        self._traced_sigs = set()
        if shed_policy not in ("newest", "oldest"):
            raise ValueError(
                f"shed_policy must be 'newest' or 'oldest', "
                f"got {shed_policy!r}")
        # robustness knobs (docs/ROBUSTNESS.md): bounded admission queue
        # with load shedding, and a decode-step watchdog (None defers to
        # FLAGS_serve_decode_watchdog_s at generate time; <=0 disables)
        self.max_queue = None if max_queue is None else int(max_queue)
        self.shed_policy = shed_policy
        self._watchdog_s = decode_watchdog_s
        if kv_dtype is None:
            # KV pages match the model's compute dtype (a bf16 model
            # must not pay fp32 page bandwidth)
            kv_dtype = str(next(iter(model.parameters())).dtype)
        self.model = model
        cfg = model.config
        self.B = int(max_batch_size)
        self.page = int(page_size)
        self.max_seq_len = int(max_seq_len)
        self.pages_per_seq = _m.ceil(max_seq_len / page_size)
        if num_pages is None:
            num_pages = self.B * self.pages_per_seq
        self.capacity = int(num_pages)  # pages available to requests
        self.pad_token_id = pad_token_id
        self.eos_token_id = eos_token_id
        # what each layer keeps between steps, as the model declares it:
        # K/V pages for attention layers (one geometry), a row a slot of
        # (conv window, SSM state) for recurrent ones
        self._layout = list(model.cache_layout())
        kv_shapes = {c.shape for c in self._layout if c.kind == "kv"}
        latent_shapes = {c.shape for c in self._layout if c.kind == "latent"}
        state_shapes = {c.shape for c in self._layout if c.kind == "state"}
        # one index key a token beside the pages (or none): every paged
        # layer's alike, under the pool's one set of page ids
        index_dims = {c.index_dim for c in self._layout
                      if c.kind != "state"}
        if len(kv_shapes) > 1 or len(latent_shapes) > 1 \
                or len(index_dims) != 1 \
                or len(state_shapes) > 1 or any(
                    c.kind not in ("kv", "latent", "state")
                    for c in self._layout):
            raise ValueError(
                f"cache_layout(): served are layers of kind 'kv' (K/V "
                f"pages) in ONE geometry, layers of kind 'latent' (one "
                f"row a token for all heads, paged in place of K and V) "
                f"in one geometry, every paged layer with the same "
                f"`index_dim` (a page array of index keys beside the "
                f"pages, or 0), at least one paged layer of either kind, "
                f"and layers of kind 'state' (a conv window and a state "
                f"matrix a slot) in one geometry; got kv "
                f"{sorted(kv_shapes)}, latent {sorted(latent_shapes)}, "
                f"index_dim {sorted(index_dims)}, state "
                f"{sorted(state_shapes)}, kinds "
                f"{sorted({c.kind for c in self._layout})}")
        (n_kv_heads, head_dim), = kv_shapes or {(0, 0)}
        self._index_dim, = index_dims
        self._latent_dim = next(iter(latent_shapes), (0,))[0]
        self._state_shape = next(iter(state_shapes), None)
        # head-sharded paged KV: pages shard over the KV-head axis of
        # the TP mesh when the head count divides; an indivisible model
        # keeps replicated pages (still served, fast path lost) and the
        # downgrade is recorded like any other lost kernel path
        kv_mesh = self._tp_mesh
        if kv_mesh is not None and (self._latent_dim
                                    or n_kv_heads % self.tp):
            from ..kernels._common import note_fallback
            note_fallback("paged_kv_pool", "tp_head_shard")
            kv_mesh = None
        paged = [c for c in self._layout if c.kind != "state"]
        self.pool = PagedKVPool(
            len(paged), num_pages + 1,
            page_size, n_kv_heads, head_dim, dtype=kv_dtype, mesh=kv_mesh,
            device=self._device, index_dim=self._index_dim,
            latent_dim=self._latent_dim,
            latent_layers=[i for i, c in enumerate(paged)
                           if c.kind == "latent"])
        self.state_pool = None      # built once the refusals have passed
        # cached pages hold the attention layers' K/V only: a hit would
        # resume recurrent layers from no state at all, and a suffix
        # prefill neither selects over cached index pages nor reads
        # latent rows
        lost = "recurrent_state" if self._state_shape is not None \
            else "sparse_index" if self._index_dim \
            else "latent_pages" if self._latent_dim else None
        if lost and enable_prefix_cache:
            enable_prefix_cache = False
            _obsm.counter("kernels.pallas_fallbacks").inc(
                kernel="prefix_cache", reason=lost)
        # inactive slots need somewhere harmless to point their block
        # table (the decode step writes one K/V row for EVERY slot):
        # a dedicated trash page absorbs those writes
        self._trash = self.pool.alloc(1)[0]
        self.prefix_cache = PrefixCache(page_size) if enable_prefix_cache \
            else None
        if self.prefix_cache is not None:
            self.pool.reclaimer = self.prefix_cache
        self.stats = {"prefills": 0, "prefill_batches": 0,
                      "decode_steps": 0, "evictions": 0,
                      "max_in_flight": 0, "prefix_hits": 0,
                      "prefix_partial_hits": 0, "prefix_misses": 0,
                      "pages_reused": 0, "hol_skips": 0,
                      "deadline_evictions": 0, "shed_requests": 0,
                      "watchdog_trips": 0, "cancelled_requests": 0}
        self.last_status: List[str] = []
        # serving telemetry (docs/SERVING.md catalog); recording no-ops
        # when paddle_tpu.observability.enabled(False)
        self._m_queue = _obsm.gauge("serving.queue_depth")
        self._m_util = _obsm.gauge("serving.page_utilization")
        self._m_flight = _obsm.gauge("serving.in_flight")
        self._m_adm = _obsm.counter("serving.admissions")
        self._m_evt = _obsm.counter("serving.evictions")
        self._m_rej = _obsm.counter("serving.rejected_requests")
        self._m_done = _obsm.counter("serving.completed_requests")
        self._m_steps = _obsm.counter("serving.decode_steps")
        # slots that rode a decode step (or a self-drafting tick) with
        # no request
        self._m_idle = _obsm.counter("serving.idle_slot_steps")
        self._m_ttft = _obsm.histogram("serving.ttft_seconds", unit="s")
        self._m_tok = _obsm.histogram("serving.token_latency_seconds",
                                      unit="s")
        self._m_prefill = _obsm.histogram("serving.prefill_seconds",
                                          unit="s")
        # a prefill's own account (docs/OBSERVABILITY.md "What a
        # prefill forwarded, padded and stalled"): prompt tokens it
        # forwarded against positions it computed, and the seconds
        # decoding slots stood still for it
        self._m_pf_tokens = _obsm.counter("serving.prefill_tokens")
        self._m_stall = _obsm.counter(
            "serving.decode_stall_slot_seconds", unit="s")
        # (dispatch, first tokens on the host) of the last prefill
        # program on `time.perf_counter`: each end is stamped where it
        # happens, so the pair still says what a decoding slot waited
        # once the first tokens come down with a later step's
        self._prefill_clock = (0.0, 0.0)
        self._m_pfx_hit = _obsm.counter("serving.prefix_cache_hits")
        self._m_pfx_miss = _obsm.counter("serving.prefix_cache_misses")
        self._m_pfx_pages = _obsm.counter(
            "serving.prefix_cache_pages_reused")
        self._m_hol = _obsm.counter("serving.hol_skips")
        self._m_deadline = _obsm.counter("robustness.deadline_evictions")
        self._m_shed = _obsm.counter("robustness.shed_requests")
        self._m_wedge = _obsm.counter("robustness.watchdog_trips")
        # multi-tenant front end (docs/SERVING.md): per-tier queue/
        # admission/shed accounting and stream cancellations
        self._m_tier_q = _obsm.gauge("serving.tier.queue_depth")
        self._m_tier_adm = _obsm.counter("serving.tier.admissions")
        self._m_tier_shed = _obsm.counter("serving.tier.shed_requests")
        self._m_cancel = _obsm.counter("serving.cancelled_requests")
        # static capacity, exported so a registry-only autoscaler can
        # normalize serving.in_flight into a utilization (autoscale.py)
        _obsm.gauge("serving.slots").set(self.B, **self._mlbl)
        # TP shape of this replica + the analytic per-token all-reduce
        # payload: GSPMD inserts the model-axis all-reduces itself (two
        # row-parallel projections per layer — attention output and MLP
        # down-projection), so the predictor declares them to the comm
        # ledger per dispatch (collective.account_gspmd). Bytes per
        # token = 2 * layers * hidden * itemsize.
        self._tp_tok_bytes = 0
        if self.tp > 1:
            _obsm.gauge("serving.tp.degree").set(self.tp, **self._mlbl)
            _obsm.gauge("serving.tp.kv_shards").set(
                self.tp if self.pool.kv_sharding is not None else 1,
                **self._mlbl)
            self._tp_tok_bytes = (
                2 * int(cfg.num_hidden_layers) * int(cfg.hidden_size)
                * np.dtype(kv_dtype).itemsize)
        # chunked prefill (docs/SERVING.md "Chunked prefill"): prompts
        # longer than the threshold are ingested as page-aligned chunks
        # through the MIXED prefill+decode program — one tick at a time,
        # interleaved with decode — instead of one monolithic prefill
        # that stalls every in-flight decode until it finishes. The
        # threshold is a latency bound, so it normalizes DOWN to a
        # power-of-two multiple of page_size (min one page): chunk
        # buckets (compile signatures) form the fixed set
        # {page * 2^k <= chunk_max} that the AOT builder pre-captures,
        # and a tick never exceeds what the operator asked for.
        # 0/None disables (None defers to the RuntimeConfig, whose
        # FLAGS-sourced default reads serve_prefill_chunk_tokens).
        if prefill_chunk_tokens is None:
            prefill_chunk_tokens = int(rc.prefill_chunk_tokens)
        chunk = int(prefill_chunk_tokens or 0)
        if chunk > 0:
            b = self.page
            while b * 2 <= chunk:
                b *= 2
            chunk = b
        self._chunk_max = chunk
        # speculative decoding + on-device sampling (docs/SERVING.md
        # "Speculative decoding & sampling"): spec_draft_tokens > 0
        # turns decode ticks into multi-token verify steps — up to k
        # prompt-lookup drafted tokens enter as a q_lens = k+1 span
        # through the variable-query ragged kernel, the longest
        # accepted prefix is computed ON DEVICE, and rejected
        # positions' K/V roll back in-graph. sampling_enabled compiles
        # the sampling decode variant (per-request temperature/top-k/
        # top-p/seed as batched operands — one program for any mix of
        # greedy and sampled tenants, no retrace per config). Both are
        # compiled-in geometry (program variants); the AOT builder
        # pre-captures them so warm start stays zero-compile.
        if spec_draft_tokens is None:
            spec_draft_tokens = int(rc.spec_draft_tokens)
        if spec_ngram_max is None:
            spec_ngram_max = int(rc.spec_ngram_max)
        if sampling_enabled is None:
            sampling_enabled = bool(rc.sampling_enabled)
        self._spec_k = max(0, int(spec_draft_tokens))
        self._ngram_max = max(1, int(spec_ngram_max))
        self.sampling_enabled = bool(sampling_enabled)
        # which paged kernel a program attends through. The single-token
        # decode programs take the block-table kernel (`paged_attention`:
        # live pages by its own DMAs, any group ratio, no host metadata).
        # The programs with a query span (mixed, verify) ride the ragged
        # varq kernel, which needs the metadata and MHA: they get it
        # when a Pallas path exists, such a program is compiled in and
        # the varq gate admits the head geometry (kernels.
        # paged_attention.paged_gate_reason: H == Hkv, D % 128 == 0, 8
        # heads a shard); else they attend through the XLA varq path.
        # The metadata grid is the constant B * pages_per_seq, so every
        # step reuses one compile.
        from ..kernels._common import (use_pallas as _use_pallas,
                                       pallas_interpret)
        from ..kernels.paged_attention import paged_gate_reason
        span = max(self._chunk_max, self._spec_k + 1 if self._spec_k else 0)
        self.span_ragged = (
            span > 1 and bool(kv_shapes)
            and (_use_pallas() or pallas_interpret())
            and paged_gate_reason(
                "paged_attention_ragged_varq", cfg.num_attention_heads,
                cfg.num_key_value_heads, head_dim, self.tp) is None)
        # the varq kernel's VMEM need grows with the span bucket: refuse
        # a bucket the TPU compiler would refuse, here and by name,
        # instead of at the first long prompt
        if self.span_ragged:
            from ..kernels.paged_attention import max_varq_span
            fit = max_varq_span(cfg.num_attention_heads // self.tp,
                                head_dim, self.page,
                                np.dtype(kv_dtype).itemsize)
            if not pallas_interpret() and span > fit:
                raise ValueError(
                    f"prefill_chunk_tokens/spec_draft_tokens ask for a "
                    f"{span}-query span; the ragged varq kernel fits at "
                    f"most {fit} at {cfg.num_attention_heads // self.tp} "
                    f"heads x {head_dim} (kernels.paged_attention."
                    f"max_varq_span)")
        # a model that drafts for itself (`model.drafter()`: a multi-
        # token-prediction module with latent rows of its own) is the
        # one speculation served over latent pages: the self-drafting
        # tick (`_raw_mtp_step`) verifies its draft as a two-query span
        # over the rows, greedily
        drafter = getattr(model, "drafter", lambda: None)()
        drafts_itself = drafter is not None and bool(self._latent_dim) \
            and not kv_shapes and self._state_shape is None \
            and not self._index_dim
        self._drafter = drafter if drafts_itself and self._spec_k else None
        if self._drafter is not None:
            if self._spec_k != drafter.depth:
                raise ValueError(
                    f"spec_draft_tokens={self._spec_k}: the model's "
                    f"drafter drafts {drafter.depth} a tick")
            if self.sampling_enabled:
                raise ValueError(
                    "sampling_enabled: not served with a model's own "
                    "drafter, whose tick verifies greedily")
        if self._state_shape is not None or self._index_dim \
                or self._latent_dim:
            refused = [n for n, on in (
                ("prefill_chunk_tokens", self._chunk_max > 0),
                ("spec_draft_tokens",
                 self._spec_k > 0 and self._drafter is None),
                ("tp_degree", self.tp > 1),
                (f"role={self.role!r}", self.role != "unified")) if on]
            why = (
                "recurrent layers. Their state is a row a slot, advanced "
                "one token a step: a query span (chunked prefill, "
                "speculative verify) would need snapshots to roll back "
                "to, a page span carries no state to hand off, and the "
                "mixer has no sharding rule (docs/SERVING.md 'Hybrid "
                "models')") if self._state_shape is not None else (
                "latent pages. A layer's one row a token is written and "
                "attended to one query token a slot a step: the span "
                "programs (chunked prefill, speculative verify) and the "
                "page span of a hand-off carry K and V arrays, and a row "
                "has no head axis to shard; the one span served is the "
                "verify of a model's own drafter (docs/SERVING.md 'Latent "
                "pages')") if not self._index_dim else (
                "an attention indexer. Its keys are selected for one "
                "query token a slot a step: a query span (chunked "
                "prefill, speculative verify) would select a set for "
                "each of its positions, a page span carries no index "
                "pages to hand off, and the one index key a token has no "
                "head axis to shard (docs/SERVING.md 'Sparse attention')")
            if refused:
                raise ValueError(f"{', '.join(refused)}: not served for a "
                                 f"model with {why}")
        # the long prefill, for a model that asks for it
        # (`model.long_prefill`: one served at prompts of thousands of
        # tokens): the keys' validity and the positions go in, the model
        # builds causality inside its kernels (no [bucket, bucket] mask)
        # and gives the logits of the LAST position alone, and a program
        # takes at most two prompts, or as many as the model's
        # `long_prefill_rows` says (None: a round's whole bucket). Such
        # a prompt is compute-bound work on its own: a larger batch
        # amortises nothing, its temporaries grow with rows x bucket
        # (2 x 16384 at 12 layers: 2.0 GB), and every further row count
        # is one more program a bucket to compile before serving
        self._long_prefill = bool(getattr(model, "long_prefill", False))
        self._prefill_rows = getattr(model, "long_prefill_rows", 2) \
            if self._long_prefill else None
        if self._index_dim:
            _obsm.gauge("serving.index_pool_bytes").set(
                sum(a.nbytes for a in self.pool.index), **self._mlbl)
        if self._latent_dim:
            _obsm.gauge("serving.latent_pool_bytes").set(
                sum(a.nbytes for a in self.pool.latent), **self._mlbl)
        if self._state_shape is not None:
            conv_shape, ssm_shape = self._state_shape
            self.state_pool = StatePool(
                sum(c.kind == "state" for c in self._layout), self.B,
                conv_shape, ssm_shape, conv_dtype=kv_dtype,
                device=self._device)
            _obsm.gauge("serving.state_slots").set(self.B, **self._mlbl)
            _obsm.gauge("serving.state_pool_bytes").set(
                self.state_pool.nbytes, **self._mlbl)
        # counts the model sums over its layers on the device, a vector a
        # name: each element is one (metric, labels) of `step_counters`,
        # or (metric, labels, "max"): a gauge that keeps the largest
        # value any step gave, where a sum over steps would mean nothing
        self._step_counters = [
            (key, [(_obsm.gauge(name) if kind else _obsm.counter(name),
                    dict(lbl, **self._mlbl))
                   for name, lbl, *kind in spec])
            for key, spec in sorted(
                getattr(model, "step_counters", dict)().items())]
        self._m_spec_prop =_obsm.counter("serving.spec.proposed_tokens")
        self._m_spec_acc = _obsm.counter("serving.spec.accepted_tokens")
        self._m_spec_rate = _obsm.gauge("serve.spec.accept_rate")
        self.stats["spec_ticks"] = 0
        self.stats["spec_ticks_chained"] = 0    # self-drafting ticks
        # dispatched with their predecessor still in flight
        self.stats["spec_proposed"] = 0
        self.stats["spec_accepted"] = 0
        self._m_chunks = _obsm.counter("serving.chunked_prefill.chunks")
        self._m_chunk_reqs = _obsm.counter(
            "serving.chunked_prefill.requests")
        self._m_chunk_tok = _obsm.counter(
            "serving.chunked_prefill.tokens")
        self._m_mixed = _obsm.histogram("serve.mixed_step_seconds",
                                        unit="s")
        self.stats["prefill_chunks"] = 0
        self.stats["chunked_requests"] = 0
        self.stats["mixed_steps"] = 0
        self._ready = False
        self._req_seq = 0   # process-unique request ids across calls

    @property
    def runtime_config(self):
        """The effective RuntimeConfig: the explicit ctor config, else
        a fresh FLAGS-sourced snapshot (fresh per read so runtime-only
        knobs like the watchdog keep their historical read-at-serve-
        time flag semantics)."""
        if self._rc is not None:
            return self._rc
        from ..framework.runtime_config import RuntimeConfig
        return RuntimeConfig.from_flags()

    # ---------------------------------------------------- disaggregation --
    def export_page_span(self, prompt):
        """Serialize the KV pages covering `prompt` into a KVPageSpan
        for prefill→decode handoff (docs/SERVING.md "Disaggregated
        prefill/decode"). The pages and the first generated token come
        from the prefix-cache trie — the prefill serve loop inserts
        every finished ingest there (chunked prompts included on a
        prefill-role replica). Returns None when the span is not
        exportable (pages evicted, sampled request, prefix cache off,
        or the first token unknown) — the router records that as an
        `export_miss` handoff fallback and dispatches without a span.

        Runs on the replica worker thread between serve-generator
        ticks, so the pool/trie bookkeeping is touched single-threaded.
        """
        if self.prefix_cache is None or not len(prompt):
            return None
        prompt = list(prompt)
        pages, covered, partial, next_token = \
            self.prefix_cache.lookup(prompt)
        ids = list(pages)
        if partial is not None and covered + partial[1] == len(prompt):
            ids.append(partial[0])
            covered += partial[1]
        if covered != len(prompt) or next_token is None:
            return None
        return self.pool.export_span(prompt, ids, next_token)

    def import_page_span(self, span):
        """Materialize a handoff KVPageSpan into this replica's pool +
        prefix trie (decode side), deduping against already-resident
        prefix pages. Returns the pool's import stats dict; raises on a
        corrupted span (checksum) or geometry mismatch — the caller
        falls back to a plain prefill. After a successful import the
        serve loop's full-prefix-hit admission path resumes the request
        with no prefill forward pass.

        Runs on the replica worker thread between serve-generator
        ticks (same single-threaded bookkeeping contract as
        `export_page_span`).
        """
        if self.prefix_cache is None:
            raise ValueError(
                "import_page_span needs the prefix cache "
                "(enable_prefix_cache=True) — the imported span is "
                "handed to the serve loop through the trie")
        return self.pool.import_span(span, self.prefix_cache)

    def _bucket_len(self, n):
        """Admission prompt bucket: smallest tuned-table entry covering
        n (RuntimeConfig.prompt_buckets), else the historical
        power-of-two bucketing — a table tuned on observed traffic
        never rejects an outlier, it just compiles one more program."""
        for b in self._rc_buckets:
            if b >= n:
                return b
        return LLMPredictor._bucket(n)

    # ------------------------------------------------------- jitted core --
    def _ensure_ready(self):
        """Refresh the model's parameter/buffer array snapshot and (on
        first use) build the jitted admission/decode programs. Called at
        every generate() / serve-loop start so weight updates between
        calls are honored — and since cached prefix K/V was computed
        with the OLD weights, a weight change flushes the prefix cache.

        Runs under the shared per-model trace lock: while ANOTHER
        replica of the same model is inside its first trace, bound_state
        has the shared parameter Tensors rebound to tracers — a
        snapshot read outside the lock would see those tracers as a
        "weight update" and commit them into _p_vals (leaked-tracer
        dispatch + a spurious prefix-cache flush). The lock holder
        restores the real arrays before releasing, so a locked read
        only ever sees concrete values."""
        with self._trace_lock:
            self._ensure_ready_locked()

    def _ensure_ready_locked(self):
        if not self._ready:
            self._p_tensors = [p for _, p in self.model.named_parameters()]
            self._b_tensors = [b for _, b in self.model.named_buffers()]
            # donate the paged pool (args 2/3): each program's output
            # pools alias the inputs in place instead of materializing
            # a full pool copy per call — the old arrays are dropped
            # right after every call. CPU's runtime has no donation
            # (it would only warn), so gate on backend.
            dn = (2, 3) if jax.default_backend() != "cpu" else ()
            self._prefill_jit = jax.jit(self._raw_prefill,
                                        donate_argnums=dn)
            self._suffix_jit = jax.jit(self._raw_suffix_prefill,
                                       donate_argnums=dn)
            self._decode_jit = jax.jit(self._raw_decode_step,
                                       donate_argnums=dn)
            self._mixed_jit = jax.jit(self._raw_mixed_step,
                                      donate_argnums=dn)
            self._decode_sample_jit = jax.jit(
                self._raw_decode_sample_step, donate_argnums=dn)
            self._spec_jit = jax.jit(self._raw_spec_step,
                                     donate_argnums=dn)
            self._mtp_jit = jax.jit(self._raw_mtp_step, donate_argnums=dn)
            # identity snapshot of the RAW tensor values: the sharded
            # device_put copies below are different objects, so change
            # detection must compare against what the model holds, not
            # what we serve
            self._p_src = [t._value for t in self._p_tensors]
            self._b_src = [t._value for t in self._b_tensors]
            self._p_vals = self._tp_shard_all(self._p_src)
            self._b_vals = self._tp_shard_all(self._b_src)
            self._ready = True
            return
        p_vals = [t._value for t in self._p_tensors]
        b_vals = [t._value for t in self._b_tensors]
        changed = any(a is not b for a, b in zip(p_vals, self._p_src)) \
            or any(a is not b for a, b in zip(b_vals, self._b_src))
        if changed:
            self._p_src, self._b_src = p_vals, b_vals
            self._p_vals = self._tp_shard_all(p_vals)
            self._b_vals = self._tp_shard_all(b_vals)
            if self.prefix_cache is not None:
                self.prefix_cache.clear(self.pool)

    def _tp_shard_all(self, vals):
        """Commit weight arrays onto the TP mesh. NamedSharding rule
        (the SNIPPETS-[2] naive-sharding idiom): shard the TRAILING
        axis over 'model' when divisible by tp — the column-parallel
        orientation, so head/output dims split and no contraction runs
        over a sharded dim — else the leading axis (embedding tables:
        vocab rows), else replicate. 1-D tensors (bias/norm vectors)
        stay replicated: every shard needs them whole and they are
        cheap. GSPMD propagates the rest of the partitioning through
        the jitted serve programs."""
        if self._tp_mesh is None:
            if self._device is None:
                return vals
            return [jax.device_put(v, self._device) for v in vals]
        from jax.sharding import NamedSharding, PartitionSpec
        out = []
        for v in vals:
            shape = getattr(v, "shape", ())
            spec = [None] * len(shape)
            if len(shape) >= 2:
                for ax in (len(shape) - 1, 0):
                    if shape[ax] % self.tp == 0 and shape[ax] >= self.tp:
                        spec[ax] = "model"
                        break
            out.append(jax.device_put(
                v, NamedSharding(self._tp_mesh, PartitionSpec(*spec))))
        return out

    def _tp_account(self, n_tokens):
        """Declare one dispatch's compiler-inserted model-axis
        all-reduces to the comm ledger (collective.account_gspmd):
        per-tick ``comm.bytes{op=all_reduce,axis=model}`` is the
        all-reduce tax attribution the bench and autotune read. No-op
        at tp=1. Analytic host arithmetic only — nothing here touches
        the device."""
        if not self._tp_tok_bytes:
            return
        from ..distributed.collective import account_gspmd
        account_gspmd("all_reduce", "model",
                      self._tp_tok_bytes * max(1, int(n_tokens)))

    def _jit_call(self, sig, fn, *args):
        """Dispatch a jitted program, holding the shared per-model
        trace lock iff this (program, shape) signature has not been
        traced by THIS predictor yet — see _trace_lock above. The set
        is per-predictor (each has its own jit wrappers/cache), and the
        serve loop is single-threaded per predictor, so the unlocked
        fast path never races its own first trace.

        With an AOT engine attached (inference.aot), the engine's
        serialized-executable table is consulted first: a hit executes
        the deserialized program directly (no trace, no compile —
        aot.bundle_hits); a miss AOT-compiles live under the trace
        lock, serves the result, and writes the executable back into
        the bundle (aot.bucket_misses + aot.compile_fallback span)."""
        if self._engine is not None:
            hit = self._engine.get(sig)
            if hit is not None:
                return hit(*args)
            with self._kernel_scope(), _obsrt.jit_tag(sig):
                return self._engine.compile_fallback(sig, fn, args,
                                                     self._trace_lock)
        if sig in self._traced_sigs:
            # jit keys on argument placement too, so a signature this
            # table calls traced can still trace again: keep the kernel
            # scope around every dispatch (entering it costs nothing),
            # and the tag that names the signature in the compile log
            with self._kernel_scope(), _obsrt.jit_tag(sig):
                return fn(*args)
        with self._trace_lock, self._kernel_scope(), _obsrt.jit_tag(sig):
            out = fn(*args)
        self._traced_sigs.add(sig)
        return out

    def _place(self, x):
        """Commit a small step operand where this replica's programs
        run. The first dispatch feeds a host value and the chained ones
        the previous step's device output; placed alike, jit sees one
        argument placement and compiles the step once."""
        if self._operand_placement is None:
            return x
        return jax.device_put(x, self._operand_placement)

    def _kernel_scope(self):
        """Trace-time declaration of this replica's TP mesh: the Pallas
        gates judge PER-SHARD head counts under it and the kernels are
        partitioned over it by hand (kernels._common.partitioned)."""
        return kernel_partition_scope(self._tp_mesh)

    def lower_decode_step(self):
        """The greedy decode step, lowered for this predictor's weights,
        pool and batch geometry exactly as the serve loop dispatches it
        without running it: ``.as_text()`` shows whether the paged
        kernel is in the program, ``.compile()`` gives its memory
        analysis and the collectives a tensor-parallel replica got."""
        self._ensure_ready()
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        with self._trace_lock, self._kernel_scope():
            return self._decode_jit.lower(
                self._p_vals, self._b_vals, *self._cache_args(),
                i32(self.B, self.pages_per_seq), i32(self.B), i32(self.B))

    # The serve programs take the caches as two lists in layer order
    # (donated): for a "kv" layer its K and V pages, for a "state" layer
    # its conv window and state rows, for a "latent" layer its one page
    # array and None. A layer with an indexer gives its keys as the pair
    # (K pages, index-key pages).
    def _cache_args(self):
        keys = list(zip(self.pool.k, self.pool.index)) if self._index_dim \
            else self.pool.k
        if self.state_pool is None:
            return keys, self.pool.v
        pages = iter(zip(keys, self.pool.v))
        rows = iter(zip(self.state_pool.conv, self.state_pool.ssm))
        pairs = [next(rows if c.kind == "state" else pages)
                 for c in self._layout]
        return [a for a, _ in pairs], [b for _, b in pairs]

    def _cache_store(self, first, second):
        """Adopt a program's output caches (the inputs were donated)."""
        kinds = [c.kind for c in self._layout]
        keys = [a for a, k in zip(first, kinds) if k != "state"]
        if self._index_dim:
            self.pool.index = [x for _, x in keys]
            keys = [k for k, _ in keys]
        self.pool.k = keys
        self.pool.v = [a for a, k in zip(second, kinds) if k != "state"]
        if self.state_pool is not None:
            self.state_pool.conv = [a for a, k in zip(first, kinds)
                                    if k == "state"]
            self.state_pool.ssm = [a for a, k in zip(second, kinds)
                                   if k == "state"]

    def _step_cache(self, kl, vl, tables, ctx):
        """The decode step's `past_key_values`: one entry a layer, of
        its kind. Every page entry is told which slots carry a request
        (`live`: an empty slot's table is all trash). An empty slot
        still WRITES its token, at the `ctx` of 1 the loop keeps for it,
        which lands on the trash page; under the contracts that read
        `live` (an indexer's, latent pages') it READS nothing: they
        give it a length of 0 (`generation.kv_cache.attend_lens`), at
        which the slot-walk kernels fetch and contract no block, where
        a length of 2 cost a whole one. A model that counts what its
        tokens do (recurrent layers, step counters) gets the same rows
        as the cache's `active`."""
        from ..generation.kv_cache import (LatentCacheEntry, PagedCacheEntry,
                                           PagedKVCache, StateCacheEntry)
        paged = (Tensor(tables), Tensor(ctx))
        live = tables[:, 0] != jnp.int32(self._trash)

        def entry(i, c):
            if c.kind == "state":
                return StateCacheEntry(kl[i], vl[i])
            pages, index = kl[i] if c.index_dim else (kl[i], None)
            if c.kind == "latent":
                return LatentCacheEntry(pages, *paged,
                                        index_pages=index, live=live)
            return PagedCacheEntry(pages, vl[i], *paged, index_pages=index,
                                   live=live)

        entries = [entry(i, c) for i, c in enumerate(self._layout)]
        if self.state_pool is None and not self._step_counters:
            return PagedKVCache(entries)
        return PagedKVCache(entries, active=live)

    def _step_caches_out(self, caches):
        """A decode step's updated caches, as the two operand lists."""
        first = [(_raw(e[0]), _raw(e.index_pages)) if c.index_dim
                 else _raw(e[0]) for c, e in zip(self._layout, caches)]
        return first, [None if c.kind == "latent" else _raw(e[1])
                       for c, e in zip(self._layout, caches)]

    def _counter_outputs(self, caches):
        """The model's summed counts, as extra outputs of a program."""
        counts = getattr(caches, "counters", None) or {}
        return tuple(_raw(counts[key]) for key, _ in self._step_counters)

    def _note_counters(self, aux):
        """Add a resolved step's counts to their metrics (the vectors
        came down with the step's tokens: no read of their own). A
        program may give the head of a vector only: what it does not
        count (a decode step, a prefill's chunks) is not in it."""
        for (_, spec), vec in zip(self._step_counters, aux):
            for (ctr, lbl), n in zip(spec, np.asarray(vec).tolist()):
                if not n:
                    continue
                if isinstance(ctr, _obsm.Gauge):
                    ctr.set(max(n, ctr.value(**lbl)), **lbl)
                else:
                    ctr.inc(n, **lbl)

    def _raw_prefill(self, p_vals, b_vals, kl, vl, ids, pos, lens,
                     page_rows, *slots):
        """One admission program per (batch, bucket): forward + on-device
        argmax + K/V scatter into the paged pool. ids/pos [N, bucket]
        (left-padded), lens [N], page_rows [N, ceil(bucket/page)].
        Returns (next_tokens [N, bucket] int32, new_k, new_v[, first
        drafts [N]][, counts]).
        Rows with lens == 0 are dummies: every write lands on the trash
        page. A model with recurrent layers also gets `slots` [N] (the
        state row each prompt's final state is written to, whole; a
        dummy's is the pool's last row)."""
        from ..jit.bridge import bound_state
        from ..kernels.paged_attention import index_key_rows
        from ..kernels.latent_attention import latent_rows
        n, bucket = ids.shape
        j = jnp.arange(bucket, dtype=jnp.int32)
        key_valid = j[None, :] >= (bucket - lens)[:, None]      # [N, S]
        if self._long_prefill:
            # the model takes the keys' validity alone: it builds
            # causality (and a selection, where it has an indexer) from
            # the positions inside its kernels (no [bucket, bucket]
            # array), and returns the logits of the last position only
            mask = key_valid
        else:
            causal = j[None, :] <= j[:, None]                   # [Sq, Sk]
            ok = key_valid[:, None, :] & causal[None, :, :]     # [N, Sq, Sk]
            mask = jnp.where(ok, jnp.float32(0),
                             jnp.float32(-1e30))[:, None, :, :]
        with no_grad(), bound_state(self._p_tensors, p_vals,
                                    self._b_tensors, b_vals):
            logits, caches = self.model(
                Tensor(ids), attn_mask=Tensor(mask),
                position_ids=Tensor(pos), use_cache=True)
        nexts = jnp.argmax(logits._value, axis=-1).astype(jnp.int32)
        tokpos = j[None, :] - (bucket - lens)[:, None]          # [N, S]
        pidx = jnp.clip(tokpos // self.page, 0,
                        page_rows.shape[1] - 1).astype(jnp.int32)
        dst_page = jnp.where(key_valid,
                             jnp.take_along_axis(page_rows, pidx, axis=1),
                             jnp.int32(self._trash))
        dst_off = jnp.where(key_valid, tokpos % self.page,
                            0).astype(jnp.int32)
        new_k, new_v = [], []
        for li, (layer, kept) in enumerate(zip(self._layout, caches)):
            where = slots[0] if layer.kind == "state" \
                else (dst_page, dst_off)
            put = lambda old, new: old.at[where].set(
                _raw(new).astype(old.dtype))
            if layer.kind == "latent":
                rows, keys = (kl[li], None) if not layer.index_dim \
                    else kl[li]
                rows = put(rows, latent_rows(_raw(kept[0]), rows))
                new_k.append(rows if keys is None else (
                    rows, put(keys, index_key_rows(_raw(kept[1]), keys))))
                new_v.append(None)
                continue
            new_k.append((put(kl[li][0], kept[0]),
                          put(kl[li][1], index_key_rows(_raw(kept[2]),
                                                        kl[li][1])))
                         if layer.index_dim else put(kl[li], kept[0]))
            new_v.append(put(vl[li], kept[1]))
        # a model that drafts for itself: each row's first draft, the
        # token after the first one, comes down with it
        draft = () if self._drafter is None else (_raw(caches.draft),)
        return (nexts, new_k, new_v) + draft + self._counter_outputs(caches)

    def _raw_suffix_prefill(self, p_vals, b_vals, kl, vl, ids, pos, m,
                            slen, past_rows, page_rows):
        """Prefix-cache partial hit: run only the prompt SUFFIX through
        the forward, attending to the cached prefix K/V gathered from
        its pages on device. ids/pos [1, sb] (left-padded suffix), m =
        cached prefix length (traced scalar), slen = suffix length,
        past_rows [Wp] page ids covering the prefix (trash-padded),
        page_rows [pages_per_seq] the request's full table row.
        Returns (next_tokens [sb] int32, new_k, new_v)."""
        from ..jit.bridge import bound_state
        sb = ids.shape[1]
        page = self.page
        past_len = past_rows.shape[0] * page
        j = jnp.arange(sb, dtype=jnp.int32)
        key_valid = j >= sb - slen                              # [sb]
        causal = j[None, :] <= j[:, None]
        suf_ok = key_valid[None, :] & causal                    # [q, k_suf]
        past_ok = jnp.arange(past_len, dtype=jnp.int32)[None, :] < m
        mask = jnp.concatenate(
            [jnp.where(jnp.broadcast_to(past_ok, (sb, past_len)),
                       jnp.float32(0), jnp.float32(-1e30)),
             jnp.where(suf_ok, jnp.float32(0), jnp.float32(-1e30))],
            axis=1)[None, None, :, :]
        pasts = []
        for li in range(len(kl)):
            hk, hd = kl[li].shape[2], kl[li].shape[3]
            pk = kl[li][past_rows].reshape(1, past_len, hk, hd)
            pv = vl[li][past_rows].reshape(1, past_len, hk, hd)
            pasts.append((Tensor(pk), Tensor(pv)))
        with no_grad(), bound_state(self._p_tensors, p_vals,
                                    self._b_tensors, b_vals):
            logits, caches = self.model(
                Tensor(ids), attn_mask=Tensor(mask),
                position_ids=Tensor(pos), past_key_values=pasts,
                use_cache=True)
        nexts = jnp.argmax(logits._value[0], axis=-1).astype(jnp.int32)
        apos = m + (j - (sb - slen))                            # [sb]
        pidx = jnp.clip(apos // page, 0,
                        page_rows.shape[0] - 1).astype(jnp.int32)
        dst_page = jnp.where(key_valid, page_rows[pidx],
                             jnp.int32(self._trash))[None, :]
        dst_off = jnp.where(key_valid, apos % page,
                            0).astype(jnp.int32)[None, :]
        new_k, new_v = [], []
        for li, (ck, cv) in enumerate(caches):
            ka = (ck._value if isinstance(ck, Tensor) else ck)[:, past_len:]
            va = (cv._value if isinstance(cv, Tensor) else cv)[:, past_len:]
            new_k.append(kl[li].at[dst_page, dst_off].set(
                ka.astype(kl[li].dtype)))
            new_v.append(vl[li].at[dst_page, dst_off].set(
                va.astype(vl[li].dtype)))
        return nexts, new_k, new_v

    def _raw_decode_step(self, p_vals, b_vals, kl, vl, tables, ctx,
                         last_tok):
        """ONE compiled decode step for all slots: paged cache write +
        paged attention + greedy argmax + eos detection, all on device.
        Returns (next_token [B] int32, done [B] bool, new_k, new_v) —
        the host fetches only the two small vectors, and only AFTER
        dispatching the next step (double buffering)."""
        from ..jit.bridge import bound_state
        with no_grad(), bound_state(self._p_tensors, p_vals,
                                    self._b_tensors, b_vals):
            logits, caches = self.model(
                Tensor(last_tok[:, None]),
                position_ids=Tensor(ctx[:, None]),
                past_key_values=self._step_cache(kl, vl, tables, ctx),
                use_cache=True)
        nxt = jnp.argmax(logits._value[:, -1], axis=-1).astype(jnp.int32)
        if self.eos_token_id is not None:
            done = nxt == jnp.int32(self.eos_token_id)
        else:
            done = jnp.zeros(nxt.shape, jnp.bool_)
        return (nxt, done, *self._step_caches_out(caches)) \
            + self._counter_outputs(caches)

    def _raw_mixed_step(self, p_vals, b_vals, kl, vl, tables, ctx,
                        span_ids, q_lens, tok_in, *meta_flat):
        """ONE compiled MIXED prefill+decode step: every slot carries a
        query span — a prefill chunk of q_lens[b] prompt tokens, or a
        single decode token (q_lens[b] == 1) — starting at absolute
        position ctx[b]. Per layer the span's K/V scatters into the
        slot's pages and the span attends causally over them via the
        variable-query ragged kernel (generation/kv_cache.
        paged_cache_mixed_update_attend), so a long prompt ingests
        chunk-by-chunk WHILE the other slots keep decoding — in the
        same dispatch.

        span_ids: [B, Qb] span tokens (host-built; column 0 of decode
        slots is a placeholder); tok_in: [B] the decode-chained token
        (device-resident from the in-flight step, or the host override
        already selected by the dispatcher) — it replaces column 0 for
        EVERY slot: a chunk slot's dispatcher routes its first chunk
        token through the same override mechanism decode uses, so the
        program needs no is-chunk operand. Returns (next_token [B]
        int32 — argmax at each slot's LAST span position, done [B]
        bool, new_k, new_v): for a slot finishing its prompt this tick
        that argmax IS its first generated token; mid-prompt slots'
        outputs are ignored by the resolver."""
        from ..jit.bridge import bound_state
        from ..generation.kv_cache import PagedCacheEntry, PagedKVCache
        meta = None
        if meta_flat:
            from ..kernels.paged_attention import RaggedMetaBuilder
            meta = dict(zip(RaggedMetaBuilder.FIELDS, meta_flat))
        qb = span_ids.shape[1]
        ids = span_ids.at[:, 0].set(tok_in.astype(span_ids.dtype))
        pos = ctx[:, None].astype(jnp.int32) \
            + jnp.arange(qb, dtype=jnp.int32)[None, :]
        entries = [PagedCacheEntry(kl[i], vl[i], Tensor(tables),
                                   Tensor(ctx), meta, Tensor(q_lens))
                   for i in range(len(kl))]
        with no_grad(), bound_state(self._p_tensors, p_vals,
                                    self._b_tensors, b_vals):
            logits, caches = self.model(
                Tensor(ids), position_ids=Tensor(pos),
                past_key_values=PagedKVCache(entries), use_cache=True)
        last = jnp.clip(q_lens.astype(jnp.int32) - 1, 0, qb - 1)
        lg = jnp.take_along_axis(logits._value,
                                 last[:, None, None], axis=1)[:, 0]
        nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        if self.eos_token_id is not None:
            done = nxt == jnp.int32(self.eos_token_id)
        else:
            done = jnp.zeros(nxt.shape, jnp.bool_)
        new_k = [getattr(e.k_pages, "_value", e.k_pages) for e in caches]
        new_v = [getattr(e.v_pages, "_value", e.v_pages) for e in caches]
        return nxt, done, new_k, new_v

    def _raw_decode_sample_step(self, p_vals, b_vals, kl, vl, tables,
                                ctx, last_tok, s_temp, s_topk, s_topp,
                                s_seed, s_ctr):
        """The sampling variant of THE decode step: identical cache
        write + paged attention, but the next token comes from the
        on-device sampling kernel (generation.sampling.sample_tokens)
        with per-slot temperature/top-k/top-p/seed as batched operands
        and the per-request generated-token counter driving the key
        stream. Slots with temperature <= 0 take the raw argmax —
        bitwise the greedy program's token — selected in-graph, so one
        compiled program serves any greedy/sampled tenant mix."""
        from ..jit.bridge import bound_state
        from ..generation import sampling as _samp
        with no_grad(), bound_state(self._p_tensors, p_vals,
                                    self._b_tensors, b_vals):
            logits, caches = self.model(
                Tensor(last_tok[:, None]),
                position_ids=Tensor(ctx[:, None]),
                past_key_values=self._step_cache(kl, vl, tables, ctx),
                use_cache=True)
        nxt, _ = _samp.sample_tokens(logits._value[:, -1], s_temp,
                                     s_topk, s_topp, s_seed, s_ctr)
        if self.eos_token_id is not None:
            done = nxt == jnp.int32(self.eos_token_id)
        else:
            done = jnp.zeros(nxt.shape, jnp.bool_)
        return (nxt, done, *self._step_caches_out(caches)) \
            + self._counter_outputs(caches)

    def _raw_spec_step(self, p_vals, b_vals, kl, vl, tables, ctx,
                       span_ids, q_lens, tok_in, s_temp, s_topk, s_topp,
                       s_seed, s_ctr, *meta_flat):
        """ONE compiled speculative verify step: every slot carries a
        span of q_lens[b] tokens — its committed last token (column 0,
        via the same tok_in override mechanism decode uses) followed by
        q_lens[b]-1 prompt-lookup DRAFTED tokens — through the mixed
        update+attend path (span K/V scatter + the variable-query
        ragged kernel). The longest accepted draft prefix and the
        bonus/correction token are computed ON DEVICE
        (generation.sampling.verify_spans: greedy rows compare against
        the raw argmax — lossless; sampled rows apply the
        rejection-sampling accept rule), and the REJECTED positions'
        K/V is rolled back in-graph: their pre-write page contents were
        gathered before the forward and are scattered back, so the
        pages hold exactly the kept prefix. Returns (bonus [B] int32,
        accepted [B] int32, done [B] bool, new_k, new_v) — the host
        commits drafts[:accepted] + bonus, rewinds ctx/ragged meta to
        the kept length (RaggedMetaBuilder.rollback_slot), and syncs
        only the three small vectors. Slots with q_lens == 1 carried no
        drafts: the step degenerates to a plain decode/sampling tick."""
        from ..jit.bridge import bound_state
        from ..generation.kv_cache import PagedCacheEntry, PagedKVCache
        from ..generation import sampling as _samp
        meta = None
        if meta_flat:
            from ..kernels.paged_attention import RaggedMetaBuilder
            meta = dict(zip(RaggedMetaBuilder.FIELDS, meta_flat))
        qb = span_ids.shape[1]
        ids = span_ids.at[:, 0].set(tok_in.astype(span_ids.dtype))
        pos = ctx[:, None].astype(jnp.int32) \
            + jnp.arange(qb, dtype=jnp.int32)[None, :]
        # pre-write snapshot of the span's K/V destinations: the
        # rollback source. Same destination math as the mixed-step
        # scatter (generation/kv_cache.paged_cache_mixed_update_attend)
        pslot = jnp.clip(pos // self.page, 0,
                         tables.shape[1] - 1).astype(jnp.int32)
        pg = jnp.take_along_axis(tables, pslot, axis=1)       # [B, Qb]
        off = (pos % self.page).astype(jnp.int32)
        old_k = [k[pg, off] for k in kl]        # [B, Qb, Hkv, D] each
        old_v = [v[pg, off] for v in vl]
        entries = [PagedCacheEntry(kl[i], vl[i], Tensor(tables),
                                   Tensor(ctx), meta,
                                   Tensor(q_lens))
                   for i in range(len(kl))]
        with no_grad(), bound_state(self._p_tensors, p_vals,
                                    self._b_tensors, b_vals):
            logits, caches = self.model(
                Tensor(ids), position_ids=Tensor(pos),
                past_key_values=PagedKVCache(entries), use_cache=True)
        accepted, bonus = _samp.verify_spans(
            logits._value, ids, q_lens, s_temp, s_topk, s_topp,
            s_seed, s_ctr, sampled_mode=self.sampling_enabled)
        # in-graph rollback: positions past the accepted prefix (span
        # index i in (accepted, q_lens)) restore their pre-write page
        # contents; kept and padding positions are dropped via an
        # out-of-bounds destination (the mixed-scatter idiom)
        i = jnp.arange(qb, dtype=jnp.int32)[None, :]
        rej = (i > accepted[:, None]) \
            & (i < q_lens[:, None].astype(jnp.int32))
        dst_page = jnp.where(rej, pg, jnp.int32(kl[0].shape[0]))
        new_k, new_v = [], []
        for li, e in enumerate(caches):
            ka = getattr(e.k_pages, "_value", e.k_pages)
            va = getattr(e.v_pages, "_value", e.v_pages)
            new_k.append(ka.at[dst_page, off].set(
                old_k[li], mode="drop"))
            new_v.append(va.at[dst_page, off].set(
                old_v[li], mode="drop"))
        if self.eos_token_id is not None:
            done = bonus == jnp.int32(self.eos_token_id)
        else:
            done = jnp.zeros(bonus.shape, jnp.bool_)
        return bonus, accepted, done, new_k, new_v

    def _raw_mtp_step(self, p_vals, b_vals, kl, vl, tables, ctx, span_ids,
                      fresh, ctx_chain, span_chain):
        """One self-drafting tick, ONE program, for a model that
        declares a drafter of depth 1 over latent pages
        (`model.drafter()`): the verify span through the trunk, then the
        draft pass through the model's MTP module (scope `mtp.draft`).
        (One program and not two: measured on the chip, PERF.md section
        6, PR 41; the name is what the benchmark's readers look for.)

        CHAIN. A slot that continues the request of the tick before
        takes its span and its position from that tick's outputs,
        `span_chain` / `ctx_chain` (its `span_next` / `ctx_next`, still
        on the device: the tick is dispatched before its predecessor is
        read back); a slot marked `fresh [B]` (newly placed, idle or
        evicted) takes the host's `span_ids` / `ctx`. Selected here, so
        that the host launches no op of its own. A slot whose request
        ended in the tick before rides this one as a junk row, one span
        past its budget: the position is held inside the table (a kept
        row never reaches the bound), so its rows land in its own
        released pages or the trash page.

        VERIFY. span_ids [B, 2] = each slot's committed last token `x_t`
        and the token drafted after it, `d_{t+1}`, at positions ctx,
        ctx + 1: the span goes through every trunk layer's latent pages
        (two rows written a layer; the second query sees the first's
        row; absorbed form). `y = argmax logits`; the draft is accepted
        iff `y[0] == d_{t+1}` (`generation.sampling.verify_spans`,
        greedy: lossless), and `y[accepted]` is the token after the last
        kept one.

        DRAFT. The MTP module over the same two positions, from the
        trunk's output there (`caches.hidden`) and the tokens that
        followed them, `y` (the second position counts only where the
        draft was accepted). It writes its own layer's rows (the pool's
        `drafter().layer`) and drafts the token after each position;
        the next tick's draft is row `accepted`'s.

        A rejected position's rows (the trunk's and the MTP layer's, at
        ctx + 1) are not restored: every reader of latent pages goes by
        the slot's length, and the next tick writes that position before
        any length reaches it (generation/kv_cache.
        paged_cache_latent_span_update_attend). Returns (span_next [B,
        2] int32 = the next tick's span: the last committed token
        `y[accepted]` and its draft; accepted [B] int32; ctx_next; new_k;
        new_v; counts): the host commits the accepted draft and `span_next[:,
        0]`, and syncs those two small arrays only; `ctx_next [B]` (the
        position + accepted + 1, the next tick's) comes third and stays
        on the device beside `span_next`."""
        from ..jit.bridge import bound_state
        from ..generation import sampling as _samp
        at = self._drafter.layer
        ctx = jnp.minimum(jnp.where(fresh, ctx, ctx_chain),
                          jnp.int32(tables.shape[1] * self.page - 2))
        span_ids = jnp.where(fresh[:, None], span_ids, span_chain)
        pos = ctx[:, None].astype(jnp.int32) \
            + jnp.arange(2, dtype=jnp.int32)[None, :]
        with no_grad(), bound_state(self._p_tensors, p_vals,
                                    self._b_tensors, b_vals):
            cache = self._step_cache(kl, vl, tables, ctx)
            logits, caches = self.model(
                Tensor(span_ids), position_ids=Tensor(pos),
                past_key_values=cache, use_cache=True)
            greedy = jnp.argmax(logits._value, axis=-1).astype(jnp.int32)
            accepted, _ = _samp.verify_spans(
                logits._value, span_ids,
                jnp.full(span_ids.shape[:1], 2, jnp.int32), 0.0, 0, 1.0,
                0, 0, sampled_mode=False)
            kept = cache.active[:, None] & (
                jnp.arange(2, dtype=jnp.int32)[None, :] <= accepted[:, None])
            drafts, entry, more = self.model.draft(
                caches.hidden, Tensor(greedy), Tensor(pos), caches[at],
                Tensor(kept))
        caches[at] = entry
        n_active = jnp.sum(cache.active, dtype=jnp.int32)
        n_accepted = jnp.sum(jnp.where(cache.active, accepted, 0),
                             dtype=jnp.int32)
        caches.counters = dict(
            {key: _raw(vec) + _raw(more[key]) if key in more else vec
             for key, vec in caches.counters.items()},
            mtp=jnp.stack([n_active, n_accepted, n_active + n_accepted]))
        both = jnp.stack([greedy, jnp.argmax(drafts._value, axis=-1).astype(
            jnp.int32)], axis=1)                    # [B, token | draft, 2]
        span_next = jnp.take_along_axis(both, accepted[:, None, None],
                                        axis=2)[:, :, 0]
        return (span_next, accepted, ctx + accepted + 1,
                *self._step_caches_out(caches)) \
            + self._counter_outputs(caches)

    # ------------------------------------------------------------ serve --
    def generate(self, prompts, max_new_tokens=32, strict=True,
                 deadline_s=None, tiers=None, tier_weights=None,
                 sampling=None):
        """Continuous batching over a stream of prompts: List[List[int]]
        → List[List[int]] (new tokens per prompt, in request order).
        Sequences join and leave the running batch mid-flight.

        Requests that can NEVER be served — prompt + max_new_tokens
        over `max_seq_len`, or a KV-page need exceeding the whole pool —
        raise ValueError up front (strict=True, default). With
        strict=False they are rejected per-request instead: their result
        is [], `self.last_status[r]` records the reason
        ('rejected_over_max_seq_len' / 'rejected_over_pool_capacity',
        'ok' for served requests), and the serving.rejected_requests
        counter increments.

        Robustness (docs/ROBUSTNESS.md):

        - `deadline_s` (scalar or per-request list, seconds from call
          entry): an expired request is evicted — from the queue with
          result [] or mid-decode with its partial tokens — and
          `last_status[r] == "deadline"`, without blocking the others
          (robustness.deadline_evictions). Expired QUEUED requests are
          always evicted BEFORE any shed decision, so a backlog of dead
          entries can never push live ones over `max_queue`.
        - constructor `max_queue` bounds the admission backlog; excess
          requests are shed at entry per `shed_policy` ('newest' sheds
          the latest arrivals, 'oldest' the stalest) with
          `last_status[r] == "shed"` (robustness.shed_requests). With
          tiers, shedding is priority-aware: the lowest-weight tier
          over its weight share of `max_queue` sheds first, and a tier
          within its share is never shed (serving/scheduler.py).
        - the decode watchdog (constructor `decode_watchdog_s`, else
          `FLAGS_serve_decode_watchdog_s`) fails pending requests with
          `last_status "watchdog"` when a decode step wedges, instead
          of hanging; the KV pool is NOT reclaimed from a wedged step —
          treat the predictor as poisoned and rebuild it.

        Multi-tenancy (docs/SERVING.md): `tiers` (per-request tier
        names) + `tier_weights` ({tier: weight}) switch the admission
        queue to weighted deficit-round-robin — each tier's admission
        share converges to weight/Σweights, so a flood of low-tier
        requests cannot starve interactive ones. TTFT/admission/shed
        metrics gain a tier label.
        """
        return self.generate_stream(
            prompts, max_new_tokens=max_new_tokens, strict=strict,
            deadline_s=deadline_s, tiers=tiers,
            tier_weights=tier_weights, sampling=sampling).drain()

    def generate_stream(self, prompts, max_new_tokens=32, strict=True,
                        deadline_s=None, tiers=None, tier_weights=None,
                        sampling=None):
        """Streaming generate: same admission/fairness/robustness
        semantics as :meth:`generate`, but returns a
        ``serving.TokenStream`` that yields ``StreamEvent``s as decode
        ticks complete — kind "token" per decoded token (timestamps
        from the request span's token events, the PR-5 timing source)
        and one terminal kind "end" per request carrying its final
        status. `results`/`last_status` fill in place as requests
        finish.

        Cancellation: ``stream.cancel(r)`` evicts request `r` at the
        next loop iteration (pages released, ``last_status[r] ==
        "cancelled"``); closing/abandoning the stream cancels every
        still-pending request the same way — a consumer that stops
        iterating cannot leak KV pages or batch slots.
        """
        from ..serving.streaming import ServeRequest, TokenStream
        n = len(prompts)
        # per-request sampling (docs/SERVING.md "Speculative decoding &
        # sampling"): a SamplingParams (scalar = every request) whose
        # temperature > 0 requests the on-device sampling decode
        # program — a program VARIANT this predictor must have been
        # constructed for (sampling_enabled=True); silently falling
        # back to greedy would misreport what was served
        if sampling is None:
            per_sp = [None] * n
        else:
            from ..generation.sampling import SamplingParams
            per_sp = list(sampling) \
                if isinstance(sampling, (list, tuple)) \
                and not isinstance(sampling, SamplingParams) \
                else [sampling] * n
            if len(per_sp) != n:
                raise ValueError(
                    f"sampling has {len(per_sp)} entries for "
                    f"{n} prompts")
            if not self.sampling_enabled and any(
                    self._wants_sampling(sp) for sp in per_sp):
                raise ValueError(
                    "sampling requested but this predictor was built "
                    "with sampling_enabled=False — the sampling decode "
                    "program variant is compiled-in geometry (pass "
                    "sampling_enabled=True, or bake it into the "
                    "RuntimeConfig/engine bundle)")
        if deadline_s is None:
            per_dl = [None] * n
        else:
            per_req = deadline_s if isinstance(deadline_s, (list, tuple)) \
                else [deadline_s] * n
            if len(per_req) != n:
                raise ValueError(
                    f"deadline_s has {len(per_req)} entries for "
                    f"{n} prompts")
            per_dl = [None if d is None else float(d) for d in per_req]
        if tiers is not None and len(tiers) != n:
            raise ValueError(
                f"tiers has {len(tiers)} entries for {n} prompts")
        if strict:
            # validation precedes span creation: raising after
            # start_span would leak the spans open in the recorder
            for r, p in enumerate(prompts):
                uns = self._unservable(p, max_new_tokens)
                if uns is not None:
                    raise ValueError(
                        f"request {r} can never be served: {uns[1]}. "
                        "Raise max_seq_len/num_pages, shorten the "
                        "prompt, or pass strict=False to reject it and "
                        "serve the rest.")
        # graft-lint: ok[GL108] local list API: roots under serve.generate
        reqs = [ServeRequest(list(p), int(max_new_tokens),
                             tiers[r] if tiers is not None else None,
                             per_dl[r], None, per_sp[r])
                for r, p in enumerate(prompts)]
        results = [None] * n
        status = ["queued"] * n
        cancel = set()
        gen = self._serve(reqs, None, results, status, cancel,
                          tier_weights, max_new_tokens)
        return TokenStream(gen, results, status, cancel)

    def serve_stream(self, intake, tier_weights=None):
        """Open-ended continuous serving for a replica loop
        (serving/router.py): instead of a fixed prompt list, `intake()`
        is polled every loop iteration for new work and requests join
        the running batch as slots free up — admission granularity is
        one decode tick, not one generate() call.

        `intake() -> list[ServeRequest] | None`: a list (possibly
        empty) of new requests, or None to close the stream — the loop
        then drains what it has and ends. `intake` may block briefly
        while the loop is idle (the router's does, on a condition
        variable) so an idle replica doesn't spin.

        Returns a ``serving.TokenStream``; `results`/`last_status`
        grow as requests arrive, and every StreamEvent carries the
        originating ``ServeRequest.meta``.
        """
        from ..serving.streaming import TokenStream
        results, status, cancel = [], [], set()
        gen = self._serve([], intake, results, status, cancel,
                          tier_weights, None)
        return TokenStream(gen, results, status, cancel)

    def set_tier_weight(self, tier, weight):
        """Shift this replica's live fair-queueing share for `tier`
        (serving/controller.py quantum shifts). No-op until a tiered
        serve loop is running; the next loop start picks weights up
        from the router's tier_weights anyway."""
        q = getattr(self, "_live_sched", None)
        if q is not None:
            q.set_weight(tier, weight)

    @staticmethod
    def _wants_sampling(sp):
        """True when the request needs the sampling program: an
        explicit SamplingParams with temperature > 0 (temperature <= 0
        is greedy — argmax is filter-invariant, so top_k/top_p are
        moot and the plain path serves it bit-identically)."""
        return sp is not None and float(sp.temperature) > 0

    def _unservable(self, prompt, max_new):
        """(kind, detail) when the request can never be served on this
        predictor's geometry, else None."""
        L = len(prompt)
        need = -(-(L + max_new) // self.page)
        if L + max_new > self.max_seq_len:
            return ("over_max_seq_len",
                    f"prompt len {L} + max_new_tokens {max_new} "
                    f"exceeds max_seq_len {self.max_seq_len}")
        if need > self.capacity:
            return ("over_pool_capacity",
                    f"needs {need} KV pages but the pool holds "
                    f"{self.capacity}")
        return None

    def _serve(self, initial, intake, results, status, cancel,
               tier_weights, call_max_new):
        """THE serve loop, as a generator of StreamEvents. Both public
        entry points wrap it: `generate_stream` seeds `initial` and
        passes intake=None (the classic bounded call), `serve_stream`
        starts empty and polls `intake` (the replica loop). All
        admission, fairness, shedding, deadline, cancellation, decode
        and watchdog behavior lives here once."""
        import collections as _coll
        import time as _time
        from ..serving.scheduler import FifoQueue, WeightedFairScheduler
        from ..serving.streaming import StreamEvent
        from ..kernels.paged_attention import RaggedMetaBuilder

        self._ensure_ready()
        rc = self.runtime_config
        wd = self._watchdog_s
        if wd is None:
            wd = float(rc.decode_watchdog_s)
            if not wd and self._rc is not None:
                # an explicit (e.g. bundle-baked) config that never
                # armed the watchdog must not disable the host's
                # FLAGS_serve_decode_watchdog_s safety net: 0 in a
                # config means "unset", not "off" (pass the ctor arg
                # decode_watchdog_s=0 to force it off)
                from ..framework.runtime_config import RuntimeConfig
                wd = float(RuntimeConfig.from_flags().decode_watchdog_s)
        self._wd_cur = wd if wd and wd > 0 else None
        self.last_status = status
        mlbl = self._mlbl
        # refreshed every loop start, not just at construction: a
        # registry reset() between calls would otherwise leave the
        # registry-only autoscale path with no capacity to normalize by
        _obsm.gauge("serving.slots").set(self.B, **mlbl)
        use_tiers = tier_weights is not None or any(
            r.tier is not None for r in initial)
        q = WeightedFairScheduler(tier_weights,
                                  quantum=float(rc.wfs_quantum)) \
            if use_tiers else FifoQueue()
        # published so the serving controller can shift tier quanta on
        # the LIVE scheduler (set_tier_weight) — the loop itself never
        # reads this attribute
        self._live_sched = q if use_tiers else None

        # per-request parallel state (grows under dynamic intake)
        prompts, max_new, tier_of, metas = [], [], [], []
        deadlines, arrival, req_sp, samp_of = [], [], [], []
        has_deadlines = False   # no deadlines → expire_queued is a no-op
        out = _coll.deque()          # StreamEvents awaiting the consumer
        n_tok = n_first = 0          # tokens this pass handed to `out`
        closed = intake is None
        tiers_seen = set()

        gen_sp = _obstr.start_span("serve.generate", parent=None,
                                   n_prompts=len(initial),
                                   dynamic=bool(intake), **mlbl)

        def _ts(r):
            # span events are the stream's timing source — but a span
            # stops recording at its event cap (long generations), and
            # a frozen evs[-1] would stamp every tail token with the
            # same stale ts; fall back to the wall clock there
            evs = getattr(req_sp[r], "events", None)
            if evs and len(evs) < _obstr._MAX_EVENTS:
                return evs[-1]["ts"]
            return _time.time()

        def emit(r, kind, token=None, index=0, st=None, span=None,
                 drafted=()):
            # one "token" event per TICK: `span` carries every token
            # the tick committed (speculative ticks commit several),
            # `token`/`index` stay the last one for single-token
            # consumers (serving/streaming.py StreamEvent); `drafted`
            # is what the model's own drafter proposed for the span's
            # first places, accepted or not
            nonlocal n_tok, n_first
            if span is None and token is not None:
                span = (token,)
            if kind == "token":
                # the pass's count for the tick ring: a request's first
                # token closes no gap between tokens
                n_tok += len(span)
                n_first += index == len(span)
            out.append(StreamEvent(r, kind, token, index, _ts(r), st,
                                   metas[r], tuple(span or ()),
                                   tuple(drafted)))

        def add_request(sreq):
            nonlocal has_deadlines
            r = len(prompts)
            p = list(sreq.prompt)
            mn = int(sreq.max_new_tokens if sreq.max_new_tokens
                     is not None else (call_max_new or 32))
            prompts.append(p)
            max_new.append(mn)
            tier_of.append(sreq.tier)
            metas.append(sreq.meta)
            samp_of.append(getattr(sreq, "sampling", None))
            now = _time.perf_counter()
            arrival.append(now)
            deadlines.append(None if sreq.deadline_s is None
                             else now + float(sreq.deadline_s))
            has_deadlines = has_deadlines or sreq.deadline_s is not None
            if r >= len(results):
                results.append(None)
                status.append("queued")
            self._req_seq += 1
            tl = {"tier": sreq.tier} if sreq.tier is not None else {}
            # cross-boundary trace adoption: a ServeRequest carrying a
            # TraceContext (the router's admission-minted identity)
            # parents this span on it, so the replica's spans join the
            # submitter's trace; without one the span roots locally
            # under this call's serve.generate span
            tr = getattr(sreq, "trace", None)
            req_sp.append(_obstr.start_span(
                "serve.request", parent=(tr if tr is not None
                                         else gen_sp),
                request_id=f"req{self._req_seq}", idx=r,
                prompt_len=len(p), **tl, **mlbl))
            uns = self._unservable(p, mn)
            if uns is None and not self.sampling_enabled \
                    and self._wants_sampling(samp_of[r]):
                # dynamic-intake requests can't raise at the API edge
                # (generate_stream does); reject per-request instead
                # of silently serving greedy under a sampled label
                uns = ("sampling_disabled",
                       "sampling requested but the predictor was built "
                       "with sampling_enabled=False")
            if uns is not None:
                results[r] = []
                status[r] = "rejected_" + uns[0]
                req_sp[r].event("rejected", reason=uns[0])
                req_sp[r].end(status=status[r])
                self._m_rej.inc(reason=uns[0], **mlbl)
                self._m_done.inc(status=status[r], **mlbl)
                emit(r, "end", st=status[r])
                return
            q.push(r, tier=sreq.tier, cost=len(p) + mn)
            req_sp[r].event("queued")

        def finish_queued(r, st, span_event_kw=None):
            """Terminal outcome for a request that never held a slot."""
            results[r] = []
            status[r] = st
            req_sp[r].event(st, **(span_event_kw or {}))
            req_sp[r].end(status=st)
            self._m_done.inc(status=st, **mlbl)
            emit(r, "end", st=st)

        def expire_queued():
            """Evict deadline-expired QUEUED requests. Runs before any
            shed decision — expired low-tier entries must never cause a
            live (high-tier) request to shed — and every iteration.
            Deadline-free workloads skip the O(queue) scan entirely."""
            if not has_deadlines:
                return
            now = _time.perf_counter()
            for r in q.ids():
                dl = deadlines[r]
                if dl is not None and now >= dl:
                    q.remove(r)
                    self.stats["deadline_evictions"] += 1
                    self._m_deadline.inc(stage="queued", **mlbl)
                    finish_queued(r, "deadline", {"stage": "queued"})

        def shed_overflow():
            """Bounded admission queue: shed the overflow instead of
            letting the backlog grow without bound. Priority-aware
            under tiers (lowest tier first, weight-share floors); the
            serve_flood fault site inflates the apparent depth so this
            path is exercisable without real overload."""
            if self.max_queue is None:
                return
            flood = 0
            ff = _faults.check("serve_flood")
            if ff is not None and ff.mode == "flood":
                flood = int(ff.params.get("n", self.B))
            while len(q) and len(q) + flood > self.max_queue:
                r = q.pick_shed(self.shed_policy, self.max_queue)
                if r is None:
                    break
                self.stats["shed_requests"] += 1
                self._m_shed.inc(policy=self.shed_policy, **mlbl)
                if tier_of[r] is not None:
                    self._m_tier_shed.inc(tier=tier_of[r], **mlbl)
                finish_queued(r, "shed", {"policy": self.shed_policy})

        for sreq in initial:
            add_request(sreq)
        expire_queued()      # expired entries never count against
        shed_overflow()      # max_queue, and never trigger sheds

        # slot state (host): -1 = free
        slot_req = [-1] * self.B
        slot_pages = [[] for _ in range(self.B)]
        slot_new = [[] for _ in range(self.B)]
        # chunked prefill: un-ingested prompt tails + ingested counts
        # (a non-empty tail turns the next dispatch into a MIXED step)
        slot_pending = [[] for _ in range(self.B)]
        slot_ingested = [0] * self.B
        tables = np.full((self.B, self.pages_per_seq), self._trash,
                         np.int32)
        # an inactive slot keeps a position of 1 on a table that is all
        # trash: its step's token is written there (the write needs a
        # place, and the host-metadata kernels of the span programs one
        # valid entry a slot), but where a contract reads `live` it
        # attends over nothing (`_step_cache`: its read length is 0)
        ctx = np.ones((self.B,), np.int32)
        last_tok_host = np.zeros((self.B,), np.int32)
        override = np.zeros((self.B,), bool)  # host token overrides device
        # kept in step on every tick, decode ticks included, so that a
        # span program finds it current; absent when no program reads it
        builder = RaggedMetaBuilder(self.B, self.pages_per_seq, self.page,
                                    self._trash) if self.span_ragged \
            else None
        # speculative decoding + sampling slot state: per-slot sampling
        # operand rows (greedy zeros), the host token history the
        # prompt-lookup drafter matches against (prompt + committed
        # generation, maintained off already-resolved tokens only), and
        # the awaiting-first-sampled-token flag (a sampled request's
        # first token cannot come from the admission argmax — it is
        # drawn by replaying the last prompt token through the decode
        # program, which rewrites that position's K/V byte-identically)
        s_temp = np.zeros((self.B,), np.float32)
        s_topk = np.zeros((self.B,), np.int32)
        s_topp = np.ones((self.B,), np.float32)
        s_seed = np.zeros((self.B,), np.int32)
        slot_hist = [[] for _ in range(self.B)]
        slot_await_first = [False] * self.B
        spec_mode = self._spec_k > 0
        # the token each slot's model drafted after its last committed
        # one (a model with a drafter): the next tick's second query
        draft_host = np.zeros((self.B,), np.int32)

        def set_samp(b, sp):
            if sp is None:
                s_temp[b], s_topk[b], s_topp[b], s_seed[b] = 0, 0, 1, 0
            else:
                s_temp[b] = float(sp.temperature)
                s_topk[b] = int(sp.top_k)
                s_topp[b] = float(sp.top_p)
                s_seed[b] = int(sp.seed)

        def samp_vec(pend):
            """Sampling operand bundle for one dispatch: the per-slot
            param rows plus the generated-token counter that anchors
            each request's key stream — exact even under double
            buffering: a slot with a step in flight counts its pending
            token, and an in-flight step that commits NO token for a
            slot (a mixed tick's chunk/paused slots) is never in
            flight here — mixed steps resolve before the next dispatch
            on a sampling-enabled predictor (see the loop head)."""
            ctr = np.fromiter(
                (len(slot_new[b]) + (1 if b in pend else 0)
                 for b in range(self.B)), np.int32, self.B)
            return (s_temp.copy(), s_topk.copy(), s_topp.copy(),
                    s_seed.copy(), ctr)

        def evict(b, status_val="ok"):
            r = slot_req[b]
            results[r] = slot_new[b]
            status[r] = status_val
            if status_val == "ok":
                req_sp[r].event("finish", tokens=len(slot_new[b]))
            else:
                req_sp[r].event(status_val, tokens=len(slot_new[b]))
            req_sp[r].end(status=status_val)
            self.pool.release(slot_pages[b])
            slot_req[b], slot_pages[b], slot_new[b] = -1, [], []
            slot_pending[b], slot_ingested[b] = [], 0
            slot_hist[b], slot_await_first[b] = [], False
            set_samp(b, None)
            tables[b, :] = self._trash
            ctx[b] = 1
            if builder is not None:
                builder.clear_slot(b)
            self.stats["evictions"] += 1
            self._m_evt.inc(**mlbl)
            self._m_done.inc(status=status_val, **mlbl)
            emit(r, "end", st=status_val)

        def apply_cancels():
            """Consumer-driven cancellation: queued requests leave the
            queue, running ones are evicted (pages released) with
            last_status 'cancelled'. '*' cancels everything pending and
            closes the intake."""
            nonlocal closed
            if not cancel:
                return
            # snapshot before filtering: TokenStream.cancel adds from
            # other threads, and set(x) is one atomic C-level copy under
            # the GIL while a Python-level comprehension over the live
            # set is not ("Set changed size during iteration")
            snap = set(cancel)
            if "*" in snap:
                closed = True
                targets = None
            else:
                targets = {r for r in snap
                           if isinstance(r, int) and r < len(prompts)}
                if not targets:
                    return
            for r in list(q.ids()):
                if targets is None or r in targets:
                    q.remove(r)
                    self.stats["cancelled_requests"] += 1
                    self._m_cancel.inc(stage="queued", **mlbl)
                    finish_queued(r, "cancelled", {"stage": "queued"})
            for b in range(self.B):
                r = slot_req[b]
                if r >= 0 and (targets is None or r in targets):
                    self.stats["cancelled_requests"] += 1
                    self._m_cancel.inc(stage="decoding", **mlbl)
                    evict(b, "cancelled")
            if targets is not None:
                cancel.difference_update(targets)

        def expire_deadlines():
            """Evict every request whose deadline passed: queued ones
            return [] and running ones their partial tokens, both with
            last_status 'deadline' — an expired request must not keep
            holding a slot/pages the live ones need."""
            expire_queued()
            now = _time.perf_counter()
            for b in range(self.B):
                r = slot_req[b]
                if r >= 0 and deadlines[r] is not None \
                        and now >= deadlines[r]:
                    self.stats["deadline_evictions"] += 1
                    self._m_deadline.inc(stage="decoding", **mlbl)
                    evict(b, "deadline")

        def reserve(r):
            """Try to reserve pages for request r (prefix-cache lookup +
            retain + alloc + copy-on-write). Returns the admission plan
            or None when the pool can't satisfy it right now."""
            prompt = prompts[r]
            L = len(prompt)
            need = -(-(L + max_new[r]) // self.page)
            # chunked prefill: prompts over the threshold ingest
            # chunk-by-chunk through the mixed step; they bypass the
            # prefix cache (no monolithic prefill computes the
            # per-position continuation tokens the trie stores).
            # SAMPLED requests bypass it too: their first-token replay
            # rewrites position L-1's K/V, and that write must land in
            # an exclusively-owned page (a cache-shared page is read
            # by other requests; the recomputed values are numerically
            # equal but not guaranteed bit-exact across program
            # shapes) — nor may their prompts be INSERTED, or the trie
            # would pin the page the replay rewrites.
            sampled = self._wants_sampling(samp_of[r])
            chunked = bool(self._chunk_max) and L > self._chunk_max
            full_pages, covered, partial, cached_next = [], 0, None, None
            if self.prefix_cache is not None and not chunked \
                    and not sampled:
                full_pages, covered, partial, cached_next = \
                    self.prefix_cache.lookup(prompt)
                if covered + (partial[1] if partial else 0) == L \
                        and cached_next is None:
                    # cached prefix covers the whole prompt but the
                    # continuation token was never recorded: back off
                    # so a real (non-empty) suffix forward runs
                    if partial is not None:
                        partial = None
                    elif full_pages:
                        covered -= self.page
                        full_pages = full_pages[:-1]
            shared = full_pages + ([partial[0]] if partial else [])
            self.pool.retain(shared)  # pin before alloc may reclaim
            fresh = self.pool.alloc(need - len(full_pages))
            if fresh is None:
                self.pool.release(shared)
                if not shared:
                    return None
                # sharing pins cached pages the request would otherwise
                # reclaim; on a tight pool fall back to a plain full
                # prefill (the un-pinned cache pages become allocatable)
                fresh = self.pool.alloc(need)
                if fresh is None:
                    return None
                return {"r": r, "prompt": prompt, "covered": 0,
                        "pages": fresh, "reused": 0, "next": None,
                        "chunked": False, "no_cache": sampled}
            if partial is not None:
                # copy-on-write at the divergence page: the request
                # appends into this page, the trie keeps reading the
                # original
                self.pool.copy_into(partial[0], fresh[0])
                self.pool.release([partial[0]])
                covered += partial[1]
            return {"r": r, "prompt": prompt, "covered": covered,
                    "pages": full_pages + fresh,
                    "reused": len(full_pages) + (1 if partial else 0),
                    "next": cached_next if covered == L else None,
                    "chunked": chunked, "no_cache": sampled}

        def note_cold_start():
            # cold-start-to-first-token SLO (docs/DEPLOYMENT.md):
            # construction → first token, once per predictor. A warm
            # AOT engine turns this from minutes of compile into file
            # loads — mode labels the two regimes. The builder's
            # calibration predictor (recording engine) is not serving
            # and records nothing.
            if not self._cold_start_pending:
                return
            self._cold_start_pending = False
            if not (self._engine is not None
                    and getattr(self._engine, "recording", False)):
                _obsm.gauge("serve.cold_start_seconds", unit="s").set(
                    _time.perf_counter() - self._t_ctor,
                    mode=("warm" if self._engine is not None
                          and self._engine.warm else "cold"),
                    **self._mlbl)

        def place_chunked(b, plan):
            """Install a chunk-prefill admission into slot b: pages
            reserved, NO forward pass yet — the prompt ingests chunk-
            by-chunk through the mixed step at subsequent decode ticks
            (docs/SERVING.md "Chunked prefill"). TTFT is recorded when
            the FINAL chunk's first generated token resolves, not
            here."""
            r = plan["r"]
            pages = plan["pages"]
            slot_req[b], slot_pages[b] = r, pages
            slot_new[b] = []
            tables[b, :] = self._trash
            tables[b, :len(pages)] = pages
            ctx[b] = 0
            slot_pending[b] = list(plan["prompt"])
            slot_ingested[b] = 0
            slot_hist[b] = list(plan["prompt"])
            set_samp(b, samp_of[r])
            override[b] = False
            if builder is not None:
                builder.set_slot(b, tables[b], 1)
            status[r] = "running"
            req_sp[r].event("admitted", slot=b, chunked=True)
            self.stats["chunked_requests"] += 1
            self._m_chunk_reqs.inc(**mlbl)
            self._m_adm.inc(**mlbl)
            if tier_of[r] is not None:
                self._m_tier_adm.inc(tier=tier_of[r], **mlbl)

        def chunk_first_token(b, r, first=None):
            """The final chunk resolved: its last-position argmax is
            the request's FIRST generated token — the TTFT sample and
            first_token span event land here. On a PREFILL-role replica
            the finished ingest is additionally inserted into the
            prefix trie (chunked prompts bypass it on admission), so
            the handoff span export finds the pages and the first token
            resident."""
            req_sp[r].event("first_token")
            note_cold_start()
            tl = {"tier": tier_of[r]} if tier_of[r] is not None else {}
            self._m_ttft.observe(_time.perf_counter() - arrival[r],
                                 **tl, **mlbl)
            if (self.role == "prefill" and first is not None
                    and self.prefix_cache is not None
                    and not self._wants_sampling(samp_of[r])):
                L = len(prompts[r])
                npages = -(-L // self.page)
                nts = [None] * (L - 1) + [int(first)]
                self.prefix_cache.insert(prompts[r],
                                         slot_pages[b][:npages], nts,
                                         self.pool)

        def place(b, plan, first):
            """Install an admitted request into slot b. `first` is the
            admission argmax — a SAMPLED request discards it and waits
            for its first token to be DRAWN: the slot replays the last
            prompt token through the decode program (ctx backs up one
            position; the rewrite recomputes byte-identical K/V, so a
            prefix-shared page is unharmed) and the next resolve treats
            the program's sample as the first token (TTFT lands
            there)."""
            r = plan["r"]
            L = len(plan["prompt"])
            pages = plan["pages"]
            slot_req[b], slot_pages[b] = r, pages
            tables[b, :] = self._trash
            tables[b, :len(pages)] = pages
            slot_hist[b] = list(plan["prompt"])
            set_samp(b, samp_of[r])
            status[r] = "running"
            tl = {"tier": tier_of[r]} if tier_of[r] is not None else {}
            if self._wants_sampling(samp_of[r]):
                slot_new[b] = []
                ctx[b] = L - 1
                last_tok_host[b] = plan["prompt"][-1]
                override[b] = True
                slot_await_first[b] = True
                if builder is not None:
                    builder.set_slot(b, tables[b], L)
                req_sp[r].event("admitted", slot=b, sampled=True)
                self._m_adm.inc(**mlbl)
                if tl:
                    self._m_tier_adm.inc(**tl, **mlbl)
                return
            slot_new[b] = [first]
            slot_hist[b].append(first)
            ctx[b] = L
            last_tok_host[b] = first
            draft_host[b] = plan.get("draft", 0)
            override[b] = True
            if builder is not None:
                builder.set_slot(b, tables[b], L + 1)
            req_sp[r].event("admitted", slot=b)
            req_sp[r].event("first_token")
            note_cold_start()
            self._m_adm.inc(**mlbl)
            if tl:
                self._m_tier_adm.inc(**tl, **mlbl)
            self._m_ttft.observe(_time.perf_counter() - arrival[r],
                                 **tl, **mlbl)
            if (self.eos_token_id is not None
                    and first == self.eos_token_id):
                slot_new[b] = []     # parity: eos is stripped
                evict(b)
            elif max_new[r] <= 1:
                emit(r, "token", token=first, index=1)
                evict(b)             # budget met at admission
            else:
                emit(r, "token", token=first, index=1)

        def owed():
            """Slots whose next token stands still while a prefill
            runs: they hold a request that has had its first token and
            is owed another. Those of the step in flight, whose tokens
            the host reads only after the prefill's; with none in
            flight, every occupied slot."""
            held = inflight["snap"] if inflight is not None \
                else enumerate(slot_req)
            return sum(1 for b, r in held
                       if r >= 0 and slot_req[b] == r
                       and 0 < len(slot_new[b]) < max_new[r])

        def prefill_facts(kind, group, bucket, rows):
            """What one prefill program is, computed once where the
            round builds it and given to every record of it: the
            stage's annotation, the tick's record, the round's span
            and the counters (`prefill_account`)."""
            return {"kind": kind, "n": len(group), "rows": rows,
                    "bucket": bucket,
                    "tokens": sum(len(p["prompt"]) - p["covered"]
                                  for p in group),
                    "padded": rows * bucket, "stalled": owed()}

        def prefill_stage(group, facts):
            """The stage around one prefill program's dispatch. Its
            annotation carries the prefill's facts and names the
            requests' traces, so a `serve.request` span (wall clock) is
            found on the profiler's clock. (A `with` at the call site,
            not a wrapper around the call: a program traced two Python
            frames deeper lowered a second slower on the chip's host,
            PERF.md, PR 37.)"""
            return self._tick.stage(
                "serve.prefill", **facts,
                traces=",".join(str(req_sp[p["r"]].trace_id)
                                for p in group))

        def prefill_account(facts, acct):
            """The program that just ran (`_prefill_clock` holds its
            dispatch and its first tokens' arrival) into the tick's
            record, the counters and the round's span labels."""
            dispatched, first_tokens = self._prefill_clock
            seconds = first_tokens - dispatched
            self._tick.add(pf_n=facts["n"], pf_tokens=facts["tokens"],
                           pf_padded=facts["padded"], pf_s=seconds,
                           pf_stalled=facts["stalled"])
            self._m_pf_tokens.inc(facts["tokens"], kind="forwarded",
                                  **mlbl)
            self._m_pf_tokens.inc(facts["padded"], kind="padded", **mlbl)
            self._m_stall.inc(facts["stalled"] * seconds, **mlbl)
            for key in ("tokens", "padded", "stalled"):
                acct[key] = acct.get(key, 0) + facts[key]
            acct["seconds"] = acct.get("seconds", 0.0) + seconds

        def admission_round():
            """One pass over the queue in discipline order (FIFO, or
            weighted deficit-round-robin under tiers): fill every free
            slot with the first admissible requests (HOL fix: a stuck
            large request no longer blocks later small ones), then run
            the round's prefills — full misses batched per length
            bucket. Returns how many requests it admitted."""
            free = [b for b in range(self.B) if slot_req[b] < 0]
            if not free or not len(q):
                return 0
            plans, skipped, seq = [], [], []
            budget = len(q)
            while len(plans) < len(free) and budget > 0:
                r = q.pop()
                if r is None:
                    break
                budget -= 1
                plan = reserve(r)
                if plan is None:
                    skipped.append(r)
                    seq.append(False)
                else:
                    q.consume(r)
                    plans.append(plan)
                    seq.append(True)
            for r in reversed(skipped):
                q.push_front(r)
            if plans and skipped:
                last_pick = max(i for i, s in enumerate(seq) if s)
                n_hol = sum(1 for i, s in enumerate(seq)
                            if not s and i < last_pick)
                if n_hol:
                    self.stats["hol_skips"] += n_hol
                    self._m_hol.inc(n_hol, **mlbl)
            if not plans:
                return 0

            for plan, b in zip(plans, free):
                plan["slot"] = b    # a prefill writes its state row
            t0 = _time.perf_counter()
            chunked_plans = [p for p in plans if p.get("chunked")]
            now_plans = [p for p in plans if not p.get("chunked")]
            hits = [p for p in now_plans if p["next"] is not None]
            partials = [p for p in now_plans
                        if p["next"] is None and p["covered"] > 0]
            misses = [p for p in now_plans
                      if p["next"] is None and p["covered"] == 0]
            pf_sp = _obstr.start_span(
                "serve.prefill", parent=gen_sp, n=len(plans),
                hits=len(hits), partial=len(partials),
                misses=len(misses), chunked=len(chunked_plans))
            for plan in now_plans:
                req_sp[plan["r"]].event(
                    "prefill", covered=plan["covered"],
                    reused=plan["reused"])
            firsts, acct = {}, {}

            for plan in hits:
                firsts[plan["r"]] = int(plan["next"])
                self.stats["prefix_hits"] += 1
                self.stats["pages_reused"] += plan["reused"]
                self._m_pfx_hit.inc(**mlbl)
                self._m_pfx_pages.inc(plan["reused"], **mlbl)

            for plan in partials:
                facts = prefill_facts("suffix", [plan], self._bucket_len(
                    len(plan["prompt"]) - plan["covered"]), 1)
                with prefill_stage([plan], facts):
                    firsts[plan["r"]] = self._suffix_prefill(plan)
                prefill_account(facts, acct)
                self.stats["prefix_partial_hits"] += 1
                self.stats["pages_reused"] += plan["reused"]
                self._m_pfx_hit.inc(kind="partial", **mlbl)
                self._m_pfx_pages.inc(plan["reused"], **mlbl)

            by_bucket = {}
            for plan in misses:
                by_bucket.setdefault(
                    self._bucket_len(len(plan["prompt"])),
                    []).append(plan)
                self.stats["prefix_misses"] += 1
                self._m_pfx_miss.inc(**mlbl)
            for bucket, group in sorted(by_bucket.items()):
                rows = self._prefill_rows or len(group)
                for at in range(0, len(group), rows):
                    part = group[at:at + rows]
                    facts = prefill_facts(
                        "long" if self._long_prefill else "batch", part,
                        bucket, self._prefill_batch_rows(len(part)))
                    with prefill_stage(part, facts):
                        firsts.update(self._batch_prefill(bucket, part))
                    prefill_account(facts, acct)

            if now_plans:
                self._m_prefill.observe(_time.perf_counter() - t0,
                                        **mlbl)
            pf_sp.end(**acct)
            for plan in plans:
                if plan.get("chunked"):
                    place_chunked(plan["slot"], plan)
                else:
                    place(plan["slot"], plan, firsts[plan["r"]])
            return len(plans)

        def _active():
            return [b for b in range(self.B) if slot_req[b] >= 0]

        inflight = None
        finished = False

        def sampled_chunk_first(b, r):
            """A sampled request's FINAL chunk resolved: the mixed
            step's argmax is discarded and the slot switches to
            first-token replay (see place()) — the next decode tick
            DRAWS the first token with the request's own operands."""
            ctx[b] -= 1
            last_tok_host[b] = prompts[r][-1]
            override[b] = True
            slot_await_first[b] = True

        def on_wedged():
            """Watchdog tripped mid-resolve: fail everything still
            pending instead of hanging. Pages of the wedged step are
            NOT reclaimed (the in-flight program owns the pool arrays)
            — the predictor should be rebuilt."""
            self.stats["watchdog_trips"] += 1
            self._m_wedge.inc(**mlbl)
            for b in range(self.B):
                r = slot_req[b]
                if r >= 0:
                    results[r] = slot_new[b]
                    status[r] = "watchdog"
                    slot_req[b] = -1
                    req_sp[r].event("watchdog", stage="decoding",
                                    tokens=len(slot_new[b]))
                    req_sp[r].end(status="watchdog")
                    self._m_done.inc(status="watchdog", **mlbl)
                    emit(r, "end", st="watchdog")
            for r in list(q.ids()):
                q.remove(r)
                finish_queued(r, "watchdog", {"stage": "queued"})
            gen_sp.event("decode_wedged")
            gen_sp.end(status="watchdog")
            # crash-time forensics: the dump carries the wedged
            # requests' spans
            _obstr.flight_dump(reason="decode_wedged")

        def resolve(prev):
            """Resolve a dispatched step, routing speculative steps to
            the spec resolver. False = the watchdog tripped (cleanup
            done) — the caller terminates the loop."""
            with self._tick.stage("serve.resolve"):
                try:
                    if prev.get("spec"):
                        self._resolve_spec_step(
                            prev, slot_req, slot_new, slot_hist,
                            last_tok_host, max_new, ctx, override,
                            builder, evict, req_sp, emit,
                            chunk_first_token, draft_host)
                    else:
                        self._resolve_step(
                            prev, slot_req, slot_new, last_tok_host,
                            max_new, evict, req_sp, emit,
                            chunk_first_token,
                            sampled_first=sampled_chunk_first,
                            hist=slot_hist)
                    return True
                except DecodeWedgedError:
                    on_wedged()
                    return False

        def dispatch(active):
            """Dispatch this tick's step for the active slots (a mixed,
            speculative or plain decode step); None when none is due."""
            cur = None
            if active:
                self.stats["max_in_flight"] = max(
                    self.stats["max_in_flight"], len(active))
                # a dispatch is useless if every active slot's
                # budget is already met once the in-flight step
                # resolves — resolve first instead of burning a
                # junk step
                # keyed (slot, request): a slot recycled while its
                # old step is in flight commits NOTHING at resolve
                # (snap guard) — counting it would start the new
                # request's sampling-key counter at 1 and shift its
                # whole fixed-seed stream
                pend = {b for b, r in inflight["snap"]
                        if slot_req[b] == r} if inflight else set()
                useful = any(
                    len(slot_new[b]) + (1 if b in pend else 0)
                    < max_new[slot_req[b]] for b in active)
                if any(slot_pending[b] for b in active):
                    # a prompt is mid-ingest: this tick runs the
                    # MIXED program — its chunk advances WHILE the
                    # decode slots take their normal token step.
                    # Sampled decode slots PAUSE for the tick (the
                    # mixed program has no sampling operands): they
                    # re-dispatch their committed token
                    # idempotently and resume after the ingest.
                    paused = [b for b in active
                              if not slot_pending[b]
                              and self._wants_sampling(
                                  samp_of[slot_req[b]])]
                    for b in paused:
                        override[b] = True
                    cur = self._dispatch_mixed_step(
                        active, slot_req, slot_pending,
                        slot_ingested, tables, ctx, last_tok_host,
                        override, builder, inflight, req_sp,
                        paused=paused)
                elif useful:
                    if self._drafter is not None:
                        cur = self._dispatch_mtp_step(
                            active, slot_req, tables, ctx, last_tok_host,
                            draft_host, inflight)
                    elif spec_mode:
                        sv = samp_vec(set()) \
                            if self.sampling_enabled else None
                        cur = self._dispatch_spec_step(
                            active, slot_req, slot_hist, tables,
                            ctx, last_tok_host, override, builder,
                            sv, max_new, slot_new, req_sp)
                    else:
                        sv = samp_vec(pend) \
                            if self.sampling_enabled else None
                        cur = self._dispatch_step(
                            active, slot_req, tables, ctx,
                            last_tok_host, override, builder,
                            inflight, sv)
            if cur is not None:
                # slots awaiting their first SAMPLED token resolve
                # it this step — ride the chunk_final first-token
                # machinery in the resolver (TTFT lands there).
                # Paused slots (mixed tick) keep waiting.
                firsts = {b for b in active if slot_await_first[b]
                          and b not in (cur.get("chunk_mid") or ())}
                if firsts:
                    cur["chunk_final"] = set(
                        cur.get("chunk_final") or ()) | firsts
                    for b in firsts:
                        slot_await_first[b] = False
                # sampled requests' FINAL chunks: reroute from the
                # argmax first-token path to first-token replay
                cfs = {b for b in (cur.get("chunk_final") or ())
                       if b not in firsts and slot_req[b] >= 0
                       and self._wants_sampling(
                           samp_of[slot_req[b]])}
                if cfs:
                    cur["chunk_final"] = \
                        set(cur["chunk_final"]) - cfs
                    cur["chunk_final_sampled"] = cfs
            return cur

        try:
            while True:
                with _obstr.tick("serve.tick", self.name or "") as tick:
                    self._tick = tick
                    n_tok = n_first = 0
                    with tick.stage("serve.intake"):
                        apply_cancels()
                        expire_deadlines()
                    if inflight is not None and (
                            (spec_mode and self._drafter is None)
                            or (self.sampling_enabled
                                and "chunk_mid" in inflight)):
                        # resolve BEFORE dispatching when the next
                        # dispatch depends on this step's host-state
                        # transitions: (a) the n-gram drafter — it
                        # looks its drafts up in the slot histories, so
                        # it needs the freshly committed tokens there
                        # and ctx/ragged meta rewound to the accepted
                        # prefix (its multi-token step replaces the
                        # one-step pipeline at the same single sync per
                        # tick). Not a model's OWN drafter: it is part
                        # of the tick's program, its next span and
                        # position are on the device when the tick
                        # ends, and the self-drafting tick is pipelined
                        # like a plain decode step (`_dispatch_mtp_step`
                        # chains them); (b) a MIXED step on a
                        # sampling-enabled predictor — its resolve flips
                        # sampled slots into first-token replay
                        # (sampled_chunk_first) and un-pauses sampled
                        # decode slots, and a double-buffered dispatch
                        # in between would chain the discarded argmax /
                        # advance ctx past the replay position. Greedy
                        # predictors keep the fully pipelined mixed path.
                        prev, inflight = inflight, None
                        if not resolve(prev):
                            break
                    if not closed:
                        with tick.stage("serve.intake"):
                            batch = intake()
                            if batch is None:
                                closed = True
                            elif batch:
                                for sreq in batch:
                                    add_request(sreq)
                                expire_queued()
                                shed_overflow()
                    n_admitted, prefills = 0, self.stats["prefills"]
                    with tick.stage("serve.admit"):
                        while (n := admission_round()):
                            n_admitted += n
                    active = _active()
                    with tick.stage("serve.gauges"):
                        self._m_queue.set(len(q), **mlbl)
                        self._m_flight.set(len(active), **mlbl)
                        if use_tiers:
                            depths = q.depths()
                            for t_name in tiers_seen - set(depths):
                                self._m_tier_q.set(0, tier=t_name, **mlbl)
                            for t_name, d in depths.items():
                                tiers_seen.add(t_name)
                                self._m_tier_q.set(d, tier=t_name, **mlbl)
                        self._m_util.set(
                            (self.capacity - self.pool.free_count)
                            / max(self.capacity, 1), **mlbl)
                    tick.note(active=len(active), admitted=n_admitted,
                              prefill=self.stats["prefills"] > prefills)
                    with tick.stage("serve.dispatch"):
                        cur = dispatch(active)
                    prev, inflight = inflight, cur
                    if prev is not None:
                        if not resolve(prev):
                            break
                    elif cur is None:
                        if closed:
                            if n_tok:   # a speculative tick resolved at
                                # this pass's head: its tokens are the
                                # last this loop hands out
                                tick.note(tokens=n_tok, first=n_first)
                            break
                        # idle dynamic loop: intake() is expected to block
                        # briefly itself; this is only spin insurance
                        if not out:
                            _time.sleep(0.0002)
                    # by the pass's order (admit with its prefills,
                    # dispatch, resolve of the PREVIOUS step) the tokens
                    # handed out after a prefill are those whose gap
                    # held it: `pf_s` beside them says for how long
                    tick.note(tokens=n_tok, first=n_first)
                    if out:
                        # _serve is a generator: the consumer handles
                        # each event on this thread before the loop
                        # goes on, and that time is this stage's
                        with tick.stage("serve.emit"):
                            while out:
                                yield out.popleft()

            for r, res in enumerate(results):
                if res is None:   # queue leftovers the loop could not
                    results[r] = []   # place (defensive path)
                    if status[r] in ("queued", "running"):
                        status[r] = "incomplete"
                        self._m_done.inc(status="incomplete", **mlbl)
                        emit(r, "end", st="incomplete")
            for r, sp in enumerate(req_sp):
                if not sp.ended:  # stragglers (defensive path above)
                    sp.end(status=status[r])
            gen_sp.end()
            while out:
                yield out.popleft()
            finished = True
        finally:
            self._tick = _obstr.NULL_TICK
            if not finished:
                # Two ways here: the consumer abandoned the raw
                # generator (GeneratorExit; TokenStream.close drains
                # instead, so normally unreachable) → "cancelled", or
                # an exception unwound out of the serve loop → "error".
                # A crash must NOT masquerade as consumer cancellation:
                # the router readmits these requests as replica
                # failures, and forensics need the terminal status on
                # this replica to say so. Either way: free pages + end
                # spans; pending StreamEvents are lost.
                exc = sys.exc_info()[1]
                aborted = exc is not None and not isinstance(
                    exc, GeneratorExit)
                st = "error" if aborted else "cancelled"
                for b in range(self.B):
                    if slot_req[b] >= 0:
                        if not aborted:
                            self.stats["cancelled_requests"] += 1
                            self._m_cancel.inc(stage="decoding", **mlbl)
                        evict(b, st)
                for r in list(q.ids()):
                    q.remove(r)
                    if not aborted:
                        self.stats["cancelled_requests"] += 1
                        self._m_cancel.inc(stage="queued", **mlbl)
                    finish_queued(r, st, {"stage": "queued"})
                for r, s in enumerate(status):
                    # popped from the queue for an admission round but
                    # not yet slotted when the loop died: neither sweep
                    # above saw it — same terminal label
                    if s in ("queued", "running"):
                        status[r] = st
                        if not aborted:
                            self.stats["cancelled_requests"] += 1
                            self._m_cancel.inc(stage="queued", **mlbl)
                        self._m_done.inc(status=st, **mlbl)
                for r, res in enumerate(results):
                    if res is None:
                        results[r] = []
                for r, sp in enumerate(req_sp):
                    if not sp.ended:
                        sp.end(status=status[r])
                if not gen_sp.ended:
                    gen_sp.end(status=st)

    # ---------------------------------------------------- admission ops --
    @staticmethod
    def _prefill_batch_rows(n):
        """Rows of the batched prefill program that takes `n` prompts:
        the next power of two (the others are dummies)."""
        nb = 1
        while nb < n:
            nb *= 2
        return nb

    def _batch_prefill(self, bucket, group):
        """Batched same-bucket device-resident prefill for a round's
        cache misses; returns {request: first token} and records the
        prompts in the prefix cache."""
        import time as _time
        n = len(group)
        nb = self._prefill_batch_rows(n)
        W = -(-bucket // self.page)
        ids = np.full((nb, bucket), self.pad_token_id, np.int32)
        pos = np.zeros((nb, bucket), np.int32)
        lens = np.zeros((nb,), np.int32)
        rows = np.full((nb, W), self._trash, np.int32)
        for i, plan in enumerate(group):
            prompt = plan["prompt"]
            L = len(prompt)
            ids[i, bucket - L:] = prompt
            pos[i, bucket - L:] = np.arange(L)
            lens[i] = L
            rows[i, :min(W, len(plan["pages"]))] = \
                plan["pages"][:W]
        slots = ()
        if self.state_pool is not None:     # dummy rows: the last row
            slots = (np.full((nb,), self.B, np.int32),)
            for i, plan in enumerate(group):
                slots[0][i] = plan["slot"]
        dispatched = _time.perf_counter()
        nexts, new_k, new_v, *aux = self._jit_call(
            ("prefill", ids.shape, rows.shape), self._prefill_jit,
            self._p_vals, self._b_vals, *self._cache_args(),
            ids, pos, lens, rows, *slots)
        self._cache_store(new_k, new_v)
        self._tp_account(nb * bucket)
        # graft-lint: ok[GL102] — the ONLY admission download: [nb,
        # bucket] small ints (every position's argmax, for the prefix
        # cache's cached-continuation tokens)
        nexts = np.asarray(nexts)
        self._prefill_clock = (dispatched, _time.perf_counter())
        if self._drafter is not None:
            drafts, *aux = aux
            # graft-lint: ok[GL102] — [nb] small ints beside the first
            # tokens: each row's first draft
            drafts = np.asarray(drafts)
            for plan, d in zip(group, drafts.tolist()):
                plan["draft"] = d
        self._note_counters(aux)
        firsts = {}
        for i, plan in enumerate(group):
            prompt = plan["prompt"]
            L = len(prompt)
            firsts[plan["r"]] = int(nexts[i, -1])
            if self.prefix_cache is not None \
                    and not plan.get("no_cache"):
                toks = [int(t) for t in nexts[i, bucket - L:]]
                npages = -(-L // self.page)
                self.prefix_cache.insert(prompt,
                                         plan["pages"][:npages],
                                         toks, self.pool)
        self.stats["prefills"] += n
        self.stats["prefill_batches"] += 1
        return firsts

    def _suffix_prefill(self, plan):
        """Partial prefix hit: forward only prompt[covered:] against the
        cached pages; returns the first generated token."""
        import time as _time
        prompt, covered = plan["prompt"], plan["covered"]
        L = len(prompt)
        suffix = prompt[covered:]
        sl = len(suffix)
        sb = self._bucket_len(sl)
        wp = -(-covered // self.page)
        wpb = 1
        while wpb < wp:
            wpb *= 2
        ids = np.full((1, sb), self.pad_token_id, np.int32)
        pos = np.zeros((1, sb), np.int32)
        ids[0, sb - sl:] = suffix
        pos[0, sb - sl:] = covered + np.arange(sl)
        past_rows = np.full((wpb,), self._trash, np.int32)
        past_rows[:wp] = plan["pages"][:wp]
        row = np.full((self.pages_per_seq,), self._trash, np.int32)
        row[:len(plan["pages"])] = plan["pages"]
        dispatched = _time.perf_counter()
        nexts, new_k, new_v = self._jit_call(
            ("suffix", ids.shape, past_rows.shape), self._suffix_jit,
            self._p_vals, self._b_vals, *self._cache_args(),
            ids, pos, np.int32(covered), np.int32(sl), past_rows, row)
        self._cache_store(new_k, new_v)
        self._tp_account(sb)
        # graft-lint: ok[GL102] — the suffix-prefill admission
        # download, same contract as _batch_prefill's
        nexts = np.asarray(nexts)
        self._prefill_clock = (dispatched, _time.perf_counter())
        first = int(nexts[-1])
        if self.prefix_cache is not None:
            toks = [None] * covered + [int(t) for t in nexts[sb - sl:]]
            npages = -(-L // self.page)
            self.prefix_cache.insert(prompt, plan["pages"][:npages],
                                     toks, self.pool)
        self.stats["prefills"] += 1
        return first

    # ------------------------------------------------------- decode ops --
    def _dispatch_step(self, active, slot_req, tables, ctx,
                       last_tok_host, override, builder, inflight,
                       samp=None):
        """Dispatch one decode step WITHOUT waiting for the previous
        step's token: continuing slots chain the device-resident next
        token straight back in; only newly admitted slots inject their
        host-known first token. With `samp` (the per-slot sampling
        operand bundle — temperature/top-k/top-p/seed/counter vectors)
        the SAMPLING program variant runs instead: same cache write and
        attention, next token drawn on device (greedy slots select the
        raw argmax in-graph, token-identical to the plain program)."""
        import time as _time
        t0 = _time.perf_counter()
        if builder is not None:
            # a later mixed or verify tick reads the metadata
            for b in active:
                builder.advance_slot(b, int(ctx[b]) + 1)
        if inflight is None:
            tok_in = jnp.asarray(last_tok_host.copy())
        else:
            tok_in = jnp.where(jnp.asarray(override.copy()),
                               jnp.asarray(last_tok_host.copy()),
                               inflight["tok"])
        tok_in = self._place(tok_in)
        override[:] = False
        # .copy(): the CPU backend may alias numpy memory zero-copy into
        # the device buffer, and the host mutates tables/ctx in
        # place while this step is still in flight (double buffering) —
        # snapshot them at dispatch. The signatures keep the empty third
        # part where the span programs' carry their metadata shapes: they
        # are the AOT bundles' table keys
        if samp is not None:
            st, sk, sp_, ss, sc = samp
            nxt, done, new_k, new_v, *aux = self._jit_call(
                ("decode_sample", tables.shape, ()),
                self._decode_sample_jit,
                self._p_vals, self._b_vals, *self._cache_args(),
                tables.copy(), ctx.copy(), tok_in, st, sk, sp_, ss, sc)
        else:
            nxt, done, new_k, new_v, *aux = self._jit_call(
                ("decode", tables.shape, ()), self._decode_jit,
                self._p_vals, self._b_vals, *self._cache_args(),
                tables.copy(), ctx.copy(), tok_in)
        self._cache_store(new_k, new_v)
        self._tp_account(self.B)
        snap = [(b, slot_req[b]) for b in active]
        ctx[active] += 1
        self.stats["decode_steps"] += 1
        self._m_steps.inc(**self._mlbl)
        self._m_idle.inc(self.B - len(active), **self._mlbl)
        return {"tok": nxt, "done": done, "snap": snap, "t": t0,
                "aux": aux}

    def _chunk_bucket(self, remaining, n_decode):
        """Adaptive page-aligned chunk bucket for one mixed tick:
        target ~chunk_max / (1 + in-flight decode load) so a long
        prompt's ingest never holds the decode slots hostage for more
        than a bounded slice, bucketed to {page * 2^k} for compile
        reuse, shrunk to the smallest bucket covering what is left of
        the prompt (late chunks re-use the small programs)."""
        tgt = max(self.page, self._chunk_max // (1 + max(0, n_decode)))
        b = self.page
        while b * 2 <= tgt:
            b *= 2
        while b > self.page and b // 2 >= remaining:
            b //= 2
        return b

    def _dispatch_mixed_step(self, active, slot_req, slot_pending,
                             slot_ingested, tables, ctx, last_tok_host,
                             override, builder, inflight, req_sp,
                             paused=()):
        """Dispatch one MIXED prefill+decode step: every slot with a
        pending prompt tail ingests its next chunk (page-aligned, up to
        this tick's adaptive bucket) while the decode slots take their
        normal single-token step — ONE compiled program, chained off
        the in-flight step exactly like `_dispatch_step` (the chunk
        tokens are host-known, so chunk ticks pipeline sync-free too).

        `paused` slots (sampled-mode decodes — the mixed program has no
        sampling operands, so their argmax output would be wrong)
        re-dispatch their committed token without advancing: the K/V
        rewrite at their frozen position is byte-identical, the output
        is discarded (they ride the chunk_mid no-token path), and they
        resume sampling decode once the chunk ingest finishes.
        """
        import time as _time
        t0 = _time.perf_counter()
        mlbl = self._mlbl
        chunk_slots = [b for b in active if slot_pending[b]]
        n_dec = len(active) - len(chunk_slots)
        qb = self._chunk_bucket(
            max(len(slot_pending[b]) for b in chunk_slots), n_dec)
        span_ids = np.full((self.B, qb), self.pad_token_id, np.int32)
        q_lens = np.ones((self.B,), np.int32)
        mid, final = set(paused), set()
        took = 0
        for b in chunk_slots:
            take = min(len(slot_pending[b]), qb)
            took += take
            chunk = slot_pending[b][:take]
            span_ids[b, :take] = chunk
            q_lens[b] = take
            # the chunk's first token rides the same host-override
            # path a newly admitted decode slot uses (column 0 of the
            # program's ids comes from tok_in)
            last_tok_host[b] = chunk[0]
            override[b] = True
            del slot_pending[b][:take]
            slot_ingested[b] += take
            (final if not slot_pending[b] else mid).add(b)
            self.stats["prefill_chunks"] += 1
            self._m_chunks.inc(**mlbl)
            self._m_chunk_tok.inc(take, **mlbl)
            req_sp[slot_req[b]].event("prefill_chunk", tokens=take,
                                      covered=slot_ingested[b])
        # the prefill account's kind "chunk": tokens forwarded and
        # positions computed, no seconds and no stalled slot (nobody
        # waits; the step is longer)
        self._tick.add(pf_tokens=took, pf_chunk=took,
                       pf_padded=len(chunk_slots) * qb)
        self._m_pf_tokens.inc(took, kind="forwarded", **mlbl)
        self._m_pf_tokens.inc(len(chunk_slots) * qb, kind="padded",
                              **mlbl)
        meta_args = ()
        if builder is not None:
            for b in active:
                if b in mid and b not in chunk_slots:
                    continue   # paused: position frozen, meta unchanged
                builder.advance_slot(b, int(ctx[b]) + int(q_lens[b]))
            m = builder.meta()
            from ..kernels.paged_attention import RaggedMetaBuilder
            meta_args = tuple(m[k].copy()
                              for k in RaggedMetaBuilder.FIELDS)
        if inflight is None:
            tok_in = jnp.asarray(last_tok_host.copy())
        else:
            tok_in = jnp.where(jnp.asarray(override.copy()),
                               jnp.asarray(last_tok_host.copy()),
                               inflight["tok"])
        tok_in = self._place(tok_in)
        override[:] = False
        # .copy() on every host operand: double buffering mutates them
        # while this step is still in flight (see _dispatch_step)
        nxt, done, new_k, new_v = self._jit_call(
            ("mixed", qb, tables.shape,
             tuple(np.shape(m) for m in meta_args)), self._mixed_jit,
            self._p_vals, self._b_vals, *self._cache_args(),
            tables.copy(), ctx.copy(), span_ids, q_lens.copy(), tok_in,
            *meta_args)
        self._cache_store(new_k, new_v)
        self._tp_account(self.B * qb)
        snap = [(b, slot_req[b]) for b in active]
        adv = [b for b in active if b not in paused]
        ctx[adv] += q_lens[adv]
        self.stats["decode_steps"] += 1
        self.stats["mixed_steps"] += 1
        self._m_steps.inc(**mlbl)
        return {"tok": nxt, "done": done, "snap": snap, "t": t0,
                "chunk_mid": mid, "chunk_final": final}

    def _dispatch_spec_step(self, active, slot_req, slot_hist, tables,
                            ctx, last_tok_host, override, builder,
                            samp, max_new, slot_new, req_sp):
        """Dispatch one SPECULATIVE multi-token decode step: each
        slot's prompt-lookup drafter matches the request's recent token
        suffix against its own prompt+generation history and proposes
        up to spec_draft_tokens continuations; the committed last token
        plus the drafts enter as a q_lens = 1+k span through the
        variable-query ragged kernel, verified on device in ONE
        compiled program (`_raw_spec_step`). ctx and the ragged meta
        advance optimistically over the whole span — the resolver
        rewinds them to the accepted prefix. A tick where no slot drew
        drafts falls back to the plain (or sampling) decode program —
        the spec span width is not paid for nothing.

        Spec mode runs resolve-before-dispatch (the drafter needs the
        resolved history), so there is never an in-flight step here:
        tok_in comes entirely from the host-committed last tokens."""
        import time as _time
        from ..generation.sampling import (propose_ngram_drafts,
                                           sampling_operands)
        t0 = _time.perf_counter()
        mlbl = self._mlbl
        qs = self._spec_k + 1
        span_ids = np.full((self.B, qs), self.pad_token_id, np.int32)
        q_lens = np.ones((self.B,), np.int32)
        drafts = {}
        proposed = 0
        for b in active:
            r = slot_req[b]
            room = max_new[r] - len(slot_new[b]) - 1
            kb = min(self._spec_k, max(0, room))
            d = propose_ngram_drafts(slot_hist[b], kb,
                                     self._ngram_max) if kb > 0 else []
            if d:
                span_ids[b, 1:1 + len(d)] = d
                q_lens[b] = 1 + len(d)
                drafts[b] = list(d)
                proposed += len(d)
        if not drafts:
            return self._dispatch_step(active, slot_req, tables, ctx,
                                       last_tok_host, override,
                                       builder, None, samp)
        meta_args = ()
        if builder is not None:
            for b in active:
                builder.advance_slot(b, int(ctx[b]) + int(q_lens[b]))
            m = builder.meta()
            from ..kernels.paged_attention import RaggedMetaBuilder
            meta_args = tuple(m[k].copy()
                              for k in RaggedMetaBuilder.FIELDS)
        tok_in = self._place(jnp.asarray(last_tok_host.copy()))
        override[:] = False
        if samp is None:
            # sampling disabled: constant greedy operands — one spec
            # program serves both modes (temperature 0 == argmax)
            ops = sampling_operands([None] * self.B)
            samp = (ops["temperature"], ops["top_k"], ops["top_p"],
                    ops["seed"],
                    np.fromiter((len(slot_new[b])
                                 for b in range(self.B)),
                                np.int32, self.B))
        st, sk, sp_, ss, sc = samp
        # .copy() on every host operand: the resolver mutates
        # tables/ctx/meta before this step's buffers are read back
        bonus, accepted, done, new_k, new_v = self._jit_call(
            ("spec", qs, tables.shape,
             tuple(np.shape(m) for m in meta_args)), self._spec_jit,
            self._p_vals, self._b_vals, *self._cache_args(),
            tables.copy(), ctx.copy(), span_ids, q_lens.copy(), tok_in,
            st, sk, sp_, ss, sc, *meta_args)
        self._cache_store(new_k, new_v)
        self._tp_account(self.B * qs)
        snap = [(b, slot_req[b]) for b in active]
        ctx0 = {b: int(ctx[b]) for b in active}
        ctx[active] += q_lens[active]   # optimistic; resolve rewinds
        self.stats["decode_steps"] += 1
        self.stats["spec_ticks"] += 1
        self.stats["spec_proposed"] += proposed
        self._m_steps.inc(**mlbl)
        self._m_spec_prop.inc(proposed, **mlbl)
        return {"spec": True, "tok": bonus, "acc": accepted,
                "done": done, "snap": snap, "t": t0, "ctx0": ctx0,
                "drafts": drafts,
                "qlen": {b: int(q_lens[b]) for b in active}}

    def _dispatch_mtp_step(self, active, slot_req, tables, ctx,
                           last_tok_host, draft_host, inflight):
        """Dispatch one self-drafting tick (`_raw_mtp_step`: the verify
        and, in the same program, the draft pass) WITHOUT waiting for
        the tick before it, as `_dispatch_step` does for a decode step:
        a slot that continues the in-flight tick's request chains that
        tick's device-resident `span_next` / `ctx_next`; every other
        slot (newly placed, idle, evicted) is `fresh` and takes the
        host's span (its committed last token and the token drafted
        after it, from admission) and the host's `ctx`. With nothing in
        flight every slot is fresh and the host's arrays stand in for
        the chain: one program signature. The host's `ctx` is what the
        resolver has committed and is not advanced here. Resolved by
        `_resolve_spec_step`, as a verify of one draft a slot."""
        import time as _time
        t0 = _time.perf_counter()
        span_ids = self._place(jnp.asarray(
            np.stack([last_tok_host, draft_host], axis=1)))
        chained = inflight is not None
        if chained:
            held = set(inflight["snap"])
            fresh = np.fromiter(((b, r) not in held
                                 for b, r in enumerate(slot_req)),
                                bool, self.B)
            chain = inflight["ctx_next"], inflight["span"]
        else:
            fresh = np.ones((self.B,), bool)
            chain = self._place(jnp.asarray(ctx.copy())), span_ids
        self.stats["spec_ticks_chained"] += chained
        self._tick.note(chained=int(chained))
        # .copy(): evictions and admissions rewrite tables and ctx
        # while this tick is still in flight
        span_next, accepted, ctx_next, new_k, new_v, *aux = self._jit_call(
            ("mtp", tables.shape), self._mtp_jit,
            self._p_vals, self._b_vals, *self._cache_args(),
            tables.copy(), ctx.copy(), span_ids, fresh, *chain)
        self._cache_store(new_k, new_v)
        self.stats["decode_steps"] += 1
        self.stats["spec_ticks"] += 1
        self.stats["spec_proposed"] += len(active)
        self._m_steps.inc(**self._mlbl)
        self._m_idle.inc(self.B - len(active), **self._mlbl)
        self._m_spec_prop.inc(len(active), **self._mlbl)
        return {"spec": True, "span": span_next, "acc": accepted,
                "ctx_next": ctx_next, "t": t0, "aux": (aux,),
                "snap": [(b, slot_req[b]) for b in active]}

    def _resolve_spec_step(self, step, slot_req, slot_new, slot_hist,
                           last_tok_host, max_new, ctx, override,
                           builder, evict, req_sp, emit, first_cb,
                           draft_host=None):
        """Sync one speculative verify step — three [B] vectors, the
        decode loop's one designed sync point — and commit each slot's
        accepted drafts plus the bonus/correction token: tokens append
        (eos/budget truncate and evict exactly like plain decode), ctx
        and the ragged meta REWIND to the kept prefix (rejected
        positions' K/V was already rolled back in-graph by the
        program), the drafting history extends, and the whole tick
        streams as ONE multi-token StreamEvent span. Slots marked
        chunk_final are resolving their first (sampled) token — TTFT
        lands here via `first_cb`.

        An n-gram verify (`_dispatch_spec_step`) is resolved before the
        next is dispatched. A self-drafting tick (`_dispatch_mtp_step`)
        is resolved while its SUCCESSOR runs, like a decode step in
        `_resolve_step`: a slot recycled since the dispatch is skipped
        by `snap`, and a slot whose budget or eos is met here rides the
        successor as a junk row of which nothing is committed. Such a
        tick gives `span`, the next tick's span, in place of the bonus:
        its first column is the bonus, its second the slot's next
        draft. The draft THIS tick verified was not on the host when it
        was dispatched: it is what `draft_host` holds now, written by
        the resolve before this one or by the slot's admission, and it
        goes out as the event's `drafted` before the next draft
        replaces it. ctx was not advanced at dispatch: it moves on by
        what was kept."""
        import time as _time
        mtp = "span" in step
        with self._tick.stage("serve.resolve.wait"):
            if mtp:
                self._await_step(step, (step["span"], step["acc"]))
                # graft-lint: ok[GL102] — the same sync point for the
                # self-drafting tick, whose successor is already
                # dispatched: [B, 2] and [B]
                span_next = np.asarray(step["span"])
                bonus = span_next[:, 0]
            else:
                self._await_step(step, (step["tok"], step["acc"],
                                        step["done"]))
                # graft-lint: ok[GL102] — THE decode-loop sync point:
                # three [B] vectors of the verify step (an n-gram
                # verify resolves before the next dispatch; the
                # multi-token step replaces the one-step pipeline at
                # the same one sync per tick)
                bonus = np.asarray(step["tok"])
            acc = np.asarray(step["acc"])    # graft-lint: ok[GL102] (ditto)
        for aux in step.get("aux") or ():
            self._note_counters(aux)
        self._m_tok.observe(_time.perf_counter() - step["t"],
                            **self._mlbl)
        firsts = step.get("chunk_final") or ()
        accepted_total = 0
        for b, r in step["snap"]:
            if slot_req[b] != r:
                continue             # evicted (and maybe re-admitted)
            drafts = [int(draft_host[b])] if mtp \
                else step["drafts"].get(b, [])
            a = min(int(acc[b]), len(drafts))
            emitted = drafts[:a] + [int(bonus[b])]
            # an n-gram verify advanced ctx over its whole span at
            # dispatch; a self-drafting tick left it where it was
            new_ctx = (int(ctx[b]) if mtp else step["ctx0"][b]) + a + 1
            ctx[b] = new_ctx
            if builder is not None and a + 1 < step["qlen"][b]:
                builder.rollback_slot(b, new_ctx)
            if drafts:
                accepted_total += a
                req_sp[r].event("spec", proposed=len(drafts),
                                accepted=a)
            if b in firsts:
                first_cb(b, r)       # first (sampled) token resolves
            span_toks = []
            ended = False
            for t in emitted:
                if self.eos_token_id is not None \
                        and t == self.eos_token_id:
                    ended = True     # parity: eos is stripped
                    break
                slot_new[b].append(t)
                span_toks.append(t)
                req_sp[r].event("token", i=len(slot_new[b]))
                if len(slot_new[b]) >= max_new[r]:
                    break
            if span_toks:
                slot_hist[b].extend(span_toks)
                last_tok_host[b] = span_toks[-1]
                override[b] = True
                if mtp:
                    draft_host[b] = span_next[b, 1]
                emit(r, "token", token=span_toks[-1],
                     index=len(slot_new[b]), span=tuple(span_toks),
                     drafted=drafts if mtp else ())
            if ended or len(slot_new[b]) >= max_new[r]:
                evict(b)
        if accepted_total:
            self.stats["spec_accepted"] += accepted_total
            self._m_spec_acc.inc(accepted_total, **self._mlbl)
        if self.stats["spec_proposed"]:
            self._m_spec_rate.set(
                self.stats["spec_accepted"]
                / self.stats["spec_proposed"], **self._mlbl)

    def _await_step(self, step, arrays):
        """Watchdog-aware wait for a dispatched step's result buffers.
        With the watchdog armed (self._wd_cur), polls the buffers'
        is_ready() against a deadline instead of blocking
        unconditionally — no thread spawn on the hot decode path; a
        step that never resolves raises DecodeWedgedError. (The
        decode_wedge fault holds is_ready 'false' for its sleep=
        duration to drive this path in CI.)"""
        import time as _time
        wd = getattr(self, "_wd_cur", None)
        if not wd:
            return
        fa = _faults.check("decode_wedge")
        wedged_until = (_time.perf_counter()
                        + float(fa.params.get("sleep", 2 * wd))) \
            if fa is not None else 0.0
        deadline = _time.perf_counter() + wd

        def _ready(a):
            return getattr(a, "is_ready", lambda: True)()

        while True:
            now = _time.perf_counter()
            if now >= wedged_until and all(_ready(a) for a in arrays):
                break
            if now >= deadline:
                raise DecodeWedgedError(
                    f"decode step did not resolve within {wd}s")
            _time.sleep(min(0.002, wd / 100.0))

    def _resolve_step(self, step, slot_req, slot_new, last_tok_host,
                      max_new, evict, req_sp=None, emit=None,
                      first_cb=None, sampled_first=None, hist=None):
        """Sync a PREVIOUSLY dispatched step (the next one is already in
        flight) and apply its tokens: append, detect completion, evict,
        and stream each applied token through `emit` (request-indexed
        per-request budgets come in as the `max_new` list). Slots that
        were recycled since the dispatch are skipped — their in-flight
        token belongs to the evicted request.

        Mixed steps (`_dispatch_mixed_step`) carry chunk roles:
        mid-prompt chunk slots produce no token this tick; a slot whose
        FINAL chunk just resolved treats the step's argmax as its first
        generated token (`first_cb(b, r)` records TTFT/first_token
        before the append/eos/budget handling). A SAMPLED request's
        final chunk instead routes to `sampled_first(b, r)` — the
        argmax is discarded and the serve loop switches the slot to
        first-token replay. Decode ticks of slots awaiting that first
        sampled token ride the same chunk_final path (the serve loop
        marks them at dispatch). Committed tokens are appended to
        `hist` (the prompt-lookup drafting history) when given."""
        import time as _time
        with self._tick.stage("serve.resolve.wait"):
            self._await_step(step, (step["tok"], step["done"]))
            # graft-lint: ok[GL102] — THE decode-loop sync point (and
            # the only one): two [B] vectors of a step whose successor
            # is already dispatched (double buffering)
            nxt = np.asarray(step["tok"])
            done = np.asarray(step["done"])  # graft-lint: ok[GL102] (ditto)
        self._note_counters(step.get("aux") or ())
        self._m_tok.observe(_time.perf_counter() - step["t"],
                            **self._mlbl)
        chunk_mid = step.get("chunk_mid") or ()
        chunk_final = step.get("chunk_final") or ()
        chunk_final_sampled = step.get("chunk_final_sampled") or ()
        if "chunk_mid" in step:
            self._m_mixed.observe(_time.perf_counter() - step["t"],
                                  **self._mlbl)
        for b, r in step["snap"]:
            if slot_req[b] != r:
                continue             # evicted (and maybe re-admitted)
            if b in chunk_mid:
                continue             # mid-prompt chunk: no token yet
            if b in chunk_final_sampled:
                # sampled request finished ingesting: discard the
                # argmax, hand the slot to first-token replay
                if sampled_first is not None:
                    sampled_first(b, r)
                continue
            if b in chunk_final:
                # the prompt just finished ingesting: this step's
                # argmax is the request's FIRST generated token
                t = int(nxt[b])
                if first_cb is not None:
                    first_cb(b, r, t)
                if bool(done[b]):    # first token is eos: stripped,
                    evict(b)         # parity with place()
                    continue
                slot_new[b].append(t)
                last_tok_host[b] = t
                if hist is not None:
                    hist[b].append(t)
                if req_sp is not None:
                    req_sp[r].event("token", i=1)
                if emit is not None:
                    emit(r, "token", token=t, index=1)
                if len(slot_new[b]) >= max_new[r]:
                    evict(b)
                continue
            if len(slot_new[b]) >= max_new[r]:
                continue             # token from a post-budget junk step
            t = int(nxt[b])
            slot_new[b].append(t)
            last_tok_host[b] = t
            if hist is not None:
                hist[b].append(t)
            if req_sp is not None:
                # decode tick: per-token latency reconstructable from
                # consecutive event timestamps (capped per span) — the
                # stream event below reads THIS timestamp
                req_sp[r].event("token", i=len(slot_new[b]))
            if bool(done[b]):        # eos computed on device
                slot_new[b].pop()    # parity: eos is stripped
                evict(b)
            else:
                if emit is not None:
                    emit(r, "token", token=t, index=len(slot_new[b]))
                if len(slot_new[b]) >= max_new[r]:
                    evict(b)


# AOT engine (bundle build/load/warm-start) — imported last: its
# entry points construct ContinuousBatchingPredictor lazily.
from . import aot  # noqa: E402,F401

"""to_static / jit.save / jit.load.

Reference parity: python/paddle/jit/api.py (to_static decorator,
paddle.jit.save → inference model) and dy2static/program_translator.py
(StaticFunction with per-input-spec program cache). Here the "program" is
a jitted XLA executable cached per (shapes, dtypes) signature; jit.save
exports via jax AOT serialization + weights (loaded by inference.Predictor
or jit.load).
"""
from __future__ import annotations

import functools
import os
import pickle
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..tensor import Tensor, Parameter
from ..framework.random import default_generator
from .._grad_mode import no_grad

_IN_TO_STATIC = False


def _in_to_static():
    return _IN_TO_STATIC


class InputSpec:
    """paddle.static.InputSpec parity."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = list(shape)
        from ..framework.dtype import convert_dtype
        self.dtype = convert_dtype(dtype)
        self.name = name
        self.stop_gradient = stop_gradient


def _flatten_tensors(obj, acc):
    if isinstance(obj, Tensor):
        acc.append(obj)
        return "*"
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):
        # NamedTuple (e.g. generation.kv_cache.PagedCacheEntry): the
        # constructor takes positional fields, not an iterable
        return type(obj)(*(_flatten_tensors(o, acc) for o in obj))
    if isinstance(obj, (list, tuple)):
        return type(obj)(_flatten_tensors(o, acc) for o in obj)
    if isinstance(obj, dict):
        return {k: _flatten_tensors(v, acc) for k, v in obj.items()}
    return obj


def _freeze(obj):
    """Hashable key for a struct of non-tensor leaves ("*" marks tensor
    slots)."""
    if isinstance(obj, (list, tuple)):
        return (type(obj).__name__,) + tuple(_freeze(o) for o in obj)
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    try:
        hash(obj)
        return obj
    except TypeError:
        return repr(obj)


def _rebuild(struct, it, wrap):
    if struct == "*":
        return wrap(next(it))
    if isinstance(struct, tuple) and hasattr(struct, "_fields"):
        return type(struct)(*(_rebuild(s, it, wrap) for s in struct))
    if isinstance(struct, (list, tuple)):
        return type(struct)(_rebuild(s, it, wrap) for s in struct)
    if isinstance(struct, dict):
        return {k: _rebuild(v, it, wrap) for k, v in struct.items()}
    return struct


# paddle.jit.enable_to_static / set_code_level / set_verbosity state
_TO_STATIC_ENABLED = True
_CODE_LEVEL = 100
_VERBOSITY = 0


class StaticFunction:
    """Wraps a python function/Layer method; compiles per input signature."""

    def __init__(self, fn, layer=None, input_spec=None, full_graph=True):
        from .dy2static import maybe_ast_transform
        # dy2static pass: rewrite python if/while over tensors into
        # lax.cond/while_loop dispatchers so data-dependent control flow
        # compiles instead of freezing at trace time
        self._fn = maybe_ast_transform(fn)
        self._layer = layer
        self._input_spec = input_spec
        self._cache = {}
        functools.update_wrapper(self, fn)

    @property
    def concrete_programs(self):
        return list(self._cache.values())

    def _state(self):
        if self._layer is None:
            return [], []
        named_p = list(self._layer.named_parameters())
        named_b = list(self._layer.named_buffers())
        return named_p, named_b

    def __call__(self, *args, **kwargs):
        global _IN_TO_STATIC
        if not _TO_STATIC_ENABLED:
            return self._fn(*args, **kwargs)
        if not jax.core.trace_ctx.is_top_level():
            # already under an outer jax trace (another to_static, a
            # jitted serving program, the AOT engine builder): nesting
            # a second jax.jit here would pin trace-time constants
            # (the rng key) as hoisted executable inputs — which the
            # AOT lower().compile() path cannot re-supply — and buys
            # nothing, since the outer trace is already compiling.
            # Run the dy2static-transformed python directly under it.
            return self._fn(*args, **kwargs)
        named_p, named_b = self._state()
        p_tensors = [p for _, p in named_p]
        b_tensors = [b for _, b in named_b]

        struct = _flatten_tensors((args, kwargs), acc := [])
        in_tensors = acc
        in_arrays = [t._value for t in in_tensors]
        # non-tensor leaves (python ints/bools/strs...) are baked into
        # the traced program as constants, so they MUST be part of the
        # cache key — f(x, 0) and f(x, 3) are different programs
        training_now = (self._layer.training if self._layer is not None
                        else False)
        sig = (tuple((tuple(a.shape), str(a.dtype)) for a in in_arrays),
               _freeze(struct), training_now)

        if sig not in self._cache:
            # verbosity/code-level are read at trace time, not decoration
            # time, so set_verbosity() after @to_static still takes effect
            src = getattr(self._fn, "__transformed_source__", None)
            if src is not None and (_VERBOSITY > 0 or _CODE_LEVEL < 100):
                import logging
                logging.getLogger("paddle_tpu.dy2static").info(
                    "transformed code of %s:\n%s",
                    getattr(self._fn, "__qualname__", self._fn), src)
            fn = self._fn
            training = self._layer.training if self._layer is not None else False

            def jax_fn(p_vals, b_vals, rng_key, arg_vals):
                global _IN_TO_STATIC
                gen = default_generator()
                old_key = gen._key
                gen._key = rng_key
                olds = [t._value for t in p_tensors + b_tensors]
                for t, v in zip(p_tensors, p_vals):
                    t._value = v
                for t, v in zip(b_tensors, b_vals):
                    t._value = v
                prev_flag = _IN_TO_STATIC
                _IN_TO_STATIC = True
                try:
                    it = iter(arg_vals)
                    a2, kw2 = _rebuild(struct, it, lambda v: Tensor(v))
                    out = fn(*a2, **kw2)
                    out_struct = _flatten_tensors(out, out_acc := [])
                    out_arrays = [t._value for t in out_acc]
                    new_b = [t._value for t in b_tensors]
                    new_key = gen._key
                    return out_arrays, new_b, new_key, out_struct
                finally:
                    _IN_TO_STATIC = prev_flag
                    for t, v in zip(p_tensors + b_tensors, olds):
                        t._value = v
                    gen._key = old_key

            out_struct_box = {}

            @functools.partial(jax.jit)
            def compiled(p_vals, b_vals, rng_key, arg_vals):
                outs, new_b, new_key, ostruct = jax_fn(p_vals, b_vals,
                                                       rng_key, arg_vals)
                out_struct_box["s"] = ostruct
                return outs, new_b, new_key

            self._cache[sig] = (compiled, out_struct_box)

        compiled, out_struct_box = self._cache[sig]
        gen = default_generator()
        key_in = gen.split()

        from ..autograd.grad_mode import is_grad_enabled
        needs_grad = is_grad_enabled() and any(
            not t.stop_gradient for t in p_tensors + in_tensors)
        if needs_grad:
            # route through the eager tape so loss.backward() on the
            # compiled forward reaches params/inputs (paddle semantics:
            # a to_static layer trains like its dygraph form). jax.vjp
            # differentiates straight through the jitted callable.
            from ..ops._dispatch import apply
            n_p, n_b = len(p_tensors), len(b_tensors)

            def tape_fn(*arrays):
                p_vals = list(arrays[:n_p])
                b_vals = list(arrays[n_p:n_p + n_b])
                key = arrays[n_p + n_b]
                arg_vals = list(arrays[n_p + n_b + 1:])
                outs, new_b, new_key = compiled(p_vals, b_vals, key,
                                                arg_vals)
                return tuple(outs) + tuple(new_b) + (new_key,)

            res = apply(tape_fn, *p_tensors, *b_tensors, key_in,
                        *in_tensors, _name="to_static")
            res = res if isinstance(res, tuple) else (res,)
            n_out = len(res) - n_b - 1
            for t, v in zip(b_tensors, res[n_out:n_out + n_b]):
                t._value = v._value
            # rng: gen.split() above already advanced the host key (the
            # no-grad path relies on the same convention)
            it = iter(res[:n_out])
            return _rebuild(out_struct_box["s"], it, lambda t: t)

        outs, new_b, new_key = compiled(
            [t._value for t in p_tensors], [t._value for t in b_tensors],
            key_in, in_arrays)
        # propagate buffer mutations (BN running stats) & rng advance
        for t, v in zip(b_tensors, new_b):
            t._value = v
        it = iter(outs)
        result = _rebuild(out_struct_box["s"], it, lambda v: Tensor(v))
        return result

    def rollback(self):
        return self._fn


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=True, **kwargs):
    """@paddle.jit.to_static"""
    from ..nn.layer_base import Layer

    def decorate(fn):
        if isinstance(fn, Layer):
            layer = fn
            sf = StaticFunction(layer.forward, layer=layer,
                                input_spec=input_spec)
            layer.forward = sf
            return layer
        # plain function or unbound method
        layer = getattr(fn, "__self__", None)
        if layer is not None and isinstance(layer, Layer):
            return StaticFunction(fn, layer=layer, input_spec=input_spec)

        # late-bound: resolve the owning layer at first call when used as a
        # method decorator inside a Layer subclass
        sf_holder = {}

        @functools.wraps(fn)
        def wrapper(*args, **kw):
            if args and isinstance(args[0], Layer):
                key = id(args[0])
                if key not in sf_holder:
                    sf_holder[key] = StaticFunction(
                        fn.__get__(args[0]), layer=args[0],
                        input_spec=input_spec)
                return sf_holder[key](*args[1:], **kw)
            if "plain" not in sf_holder:
                sf_holder["plain"] = StaticFunction(fn, input_spec=input_spec)
            return sf_holder["plain"](*args, **kw)
        wrapper.__wrapped__ = fn
        return wrapper

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


# --------------------------------------------------------------- save/load --
def _export_aot(layer, path, input_spec, meta):
    """Serialize a true AOT artifact: the layer's inference function lowered
    to StableHLO via jax.export (multi-platform: cpu + tpu), callable in a
    fresh process WITHOUT the model class and with no re-trace. This is the
    TPU-native role of the reference's serialized ProgramDesc + Paddle
    Inference format (analysis_predictor.cc LoadProgramDesc)."""
    import jax
    from jax import export as jexport
    from .bridge import functionalize

    pure_fn, p_vals, b_vals, p_names, b_names = functionalize(
        layer, training=False)

    def infer(p, b, *xs):
        out, _, _ = pure_fn(list(p), list(b), jax.random.key(0), *xs)
        outs = out if isinstance(out, (list, tuple)) else (out,)
        return tuple(o._value if isinstance(o, Tensor) else o for o in outs)

    # input avals: None/-1 dims become symbolic (shared scope), so one
    # artifact serves any batch size
    scope = jexport.SymbolicScope()
    arg_avals = []
    for i, s in enumerate(input_spec):
        dims = []
        for jdim, d in enumerate(s.shape):
            dims.append(f"s{i}_{jdim}" if d is None
                        or (isinstance(d, int) and d < 0) else str(int(d)))
        shape = jexport.symbolic_shape(",".join(dims) or "",
                                       scope=scope) if dims else ()
        from ..framework import dtype as dtypes
        arg_avals.append(jax.ShapeDtypeStruct(
            shape, dtypes.convert_dtype(s.dtype)))
    p_avals = [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in p_vals]
    b_avals = [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in b_vals]

    exp = jexport.export(jax.jit(infer), platforms=("cpu", "tpu"))(
        p_avals, b_avals, *arg_avals)
    # non-persistable buffers (rope caches etc.) are NOT in state_dict /
    # .pdiparams — they are derived constants, so their values ship
    # inside the artifact itself
    persisted = set(layer.state_dict().keys())
    b_const = {}
    for name, val in zip(b_names, b_vals):
        if name not in persisted:
            arr = np.asarray(val)
            if str(arr.dtype) == "bfloat16":
                b_const[name] = ("bfloat16", arr.view(np.uint16))
            else:
                b_const[name] = (str(arr.dtype), arr)
    blob = {
        "stablehlo": exp.serialize(),
        "p_names": p_names,
        "b_names": b_names,
        "b_const": b_const,
    }
    with open(path + ".pdexec", "wb") as f:
        pickle.dump(blob, f, protocol=4)


def save(layer, path, input_spec=None, **configs):
    """paddle.jit.save — weights (.pdiparams) + net meta (.pdmodel) + a
    serialized StableHLO AOT artifact (.pdexec, via jax.export) that a
    fresh process can execute without the model class or re-tracing
    (reference parity: the Paddle Inference saved model consumed by
    analysis_predictor.cc). If the model cannot be AOT-exported (e.g.
    input_spec missing), the weight/meta files still save and load()
    falls back to the live-layer path."""
    from ..nn.layer_base import Layer
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    state = {}
    if isinstance(layer, Layer):
        for k, v in layer.state_dict().items():
            arr = np.asarray(v._value)
            state[k] = arr.view(np.uint16) if str(v.dtype) == "bfloat16" else arr
    meta = {
        "class": type(layer).__name__,
        "input_spec": [
            {"shape": s.shape, "dtype": str(s.dtype), "name": s.name}
            for s in (input_spec or [])
        ],
        "bf16_keys": [k for k, v in (layer.state_dict().items()
                                     if isinstance(layer, Layer) else [])
                      if str(v.dtype) == "bfloat16"],
    }
    with open(path + ".pdiparams", "wb") as f:
        pickle.dump(state, f, protocol=4)
    with open(path + ".pdmodel", "wb") as f:
        pickle.dump(meta, f, protocol=4)
    if input_spec and isinstance(layer, Layer):
        try:
            _export_aot(layer, path, input_spec, meta)
        except Exception as e:
            import warnings
            warnings.warn(
                f"jit.save: AOT export failed ({type(e).__name__}: {e}); "
                "wrote weights+meta only — load() will need the model "
                "class in-process")
    # keep a live-layer registry so load() in the same process can rebuild
    _saved_layers[os.path.abspath(path)] = layer


_saved_layers = {}


class TranslatedLayer:
    """Parity shim for paddle.jit.load's return: callable inference layer."""

    def __init__(self, layer, meta):
        self._layer = layer
        self._meta = meta

    def __call__(self, *args, **kw):
        with no_grad():
            return self._layer(*args, **kw)

    def eval(self):
        if hasattr(self._layer, "eval"):
            self._layer.eval()
        return self

    def state_dict(self):
        return self._layer.state_dict()


class AOTLayer:
    """A deserialized jax.export artifact: callable inference layer needing
    NO model class and NO re-trace (parity: the loaded Paddle Inference
    program in analysis_predictor.cc). Weights come from .pdiparams."""

    def __init__(self, path, meta):
        from jax import export as jexport
        with open(path + ".pdexec", "rb") as f:
            blob = pickle.load(f)
        self._exp = jexport.deserialize(blob["stablehlo"])
        self._meta = meta
        from ..framework import dtype as dtypes
        with open(path + ".pdiparams", "rb") as f:
            state = pickle.load(f)
        bf16 = set(meta.get("bf16_keys", []))
        vals = {}
        for k, arr in state.items():
            if k in bf16:
                arr = arr.view(dtypes.bfloat16)
            vals[k] = jnp.asarray(arr)
        self._persisted = set(vals)
        # derived (non-persistable) buffers ship inside the artifact
        for k, (dt, arr) in blob.get("b_const", {}).items():
            if dt == "bfloat16":
                arr = arr.view(dtypes.bfloat16)
            vals[k] = jnp.asarray(arr)
        self._p = [vals[n] for n in blob["p_names"]]
        self._b = [vals[n] for n in blob["b_names"]]
        self._vals = vals

    def __call__(self, *args):
        xs = [a._value if isinstance(a, Tensor) else jnp.asarray(a)
              for a in args]
        outs = self._exp.call(self._p, self._b, *xs)
        outs = tuple(Tensor(o) for o in outs)
        return outs if len(outs) > 1 else outs[0]

    def eval(self):
        return self

    def state_dict(self):
        # mirror the live layer's state_dict: derived (non-persistable)
        # buffers stay internal, matching what .pdiparams holds
        return {k: Tensor(v) for k, v in self._vals.items()
                if k in self._persisted}


def load(path, **configs):
    """paddle.jit.load — prefers the serialized AOT artifact (.pdexec):
    loads and runs in a fresh process without the model class. Falls back
    to the same-process live-layer reload when no artifact exists."""
    ap = os.path.abspath(path)
    with open(path + ".pdmodel", "rb") as f:
        meta = pickle.load(f)
    if os.path.exists(path + ".pdexec"):
        return AOTLayer(path, meta)
    if ap in _saved_layers:
        layer = _saved_layers[ap]
        with open(path + ".pdiparams", "rb") as f:
            state = pickle.load(f)
        from ..framework import dtype as dtypes
        sd = {}
        for k, arr in state.items():
            if k in set(meta.get("bf16_keys", [])):
                arr = arr.view(dtypes.bfloat16)
            sd[k] = Tensor(jnp.asarray(arr))
        layer.set_state_dict(sd)
        return TranslatedLayer(layer, meta)
    raise RuntimeError(
        "paddle_tpu.jit.load: no AOT artifact (.pdexec) found and the "
        "layer class is not in-process; re-save with input_spec to "
        "produce a standalone artifact, or use paddle_tpu.inference."
        "create_predictor(config, model_factory=...)")

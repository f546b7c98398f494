"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capability surface, built on JAX/XLA/Pallas.

Top-level namespace parity: python/paddle/__init__.py. The import graph is
kept light: `import paddle_tpu as paddle` gives `paddle.Tensor`,
`paddle.to_tensor`, the op library, `paddle.nn`, `paddle.optimizer`,
`paddle.distributed` (Fleet equivalent), `paddle.jit`, `paddle.amp`,
`paddle.io`, `paddle.vision`, `paddle.inference`.
"""
from __future__ import annotations

import jax as _jax

# Paddle dtype parity needs int64/float64 tensors (paddle defaults python
# ints to int64); enable x64 before any array is created. Compute-path code
# explicitly uses float32/bfloat16, so the TPU hot path is unaffected.
_jax.config.update("jax_enable_x64", True)
# Paddle/cuBLAS semantics: float32 matmuls accumulate in float32. JAX's
# default lets the backend pick (bf16 passes on TPU); force f32 for parity —
# the bf16 hot path opts in explicitly via amp/bfloat16 params instead.
# NOTE: Pallas kernels must pin their own per-dot precision —
# kernels/_common.mxu_precision — because Mosaic rejects bf16 matmuls
# carrying the global fp32 contract precision ("Bad lhs type" on v5e).
_jax.config.update("jax_default_matmul_precision", "highest")

# Persistent XLA compile cache (parity role: Paddle Inference's engine/
# program caches + CINN's compilation cache). One place decides where it
# lives: JAX_COMPILATION_CACHE_DIR when the environment sets it (JAX
# reads it itself; nothing in this package, its tests or its benchmark
# then names another directory), else one fixed path inside the
# checkout. The path is part of the cache key, so it never moves.
import os as _os
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))
# JAX keeps only what took a second to compile. Half a second (unless the
# environment says otherwise) also keeps the many small programs that
# predictors and train steps rebuild under fresh jit wrappers, within a
# process and across them: the test suite runs several times slower
# without. (The wrong numerics that cache hits of small donated programs
# gave on jaxlib 0.4.37 do not occur on 0.9.0: docs/DEPLOYMENT.md.)
if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in _os.environ:
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

# JAX's trace, compile and cache events become the jit.* counters and the
# time-stamped compile log (observability.runtime) from the first program
# on, so a run can say what its set-up compiled and what compiled later.
# The garbage collector's pauses are stamped into a log of the same shape.
from .observability.runtime import (watch_compiles as _watch_compiles,
                                    watch_gc as _watch_gc)
_watch_compiles()
_watch_gc()

__version__ = "0.1.0"

from .framework import dtype as _dtype_mod
from .framework.dtype import (
    bool_ as bool,  # noqa: A001 — paddle exposes paddle.bool
    uint8, int8, int16, int32, int64, float16, bfloat16, float32, float64,
    complex64, complex128, float8_e4m3fn, float8_e5m2,
    set_default_dtype, get_default_dtype, finfo, iinfo,
)
from .framework.place import (
    CPUPlace, TPUPlace, XLAPlace, CUDAPlace, CUDAPinnedPlace, XPUPlace,
    set_device, get_device,
    is_compiled_with_cuda, is_compiled_with_xpu, is_compiled_with_tpu,
)
from .framework.random import (seed, get_rng_state, set_rng_state,
                               get_cuda_rng_state, set_cuda_rng_state)
from .framework.flags import set_flags, get_flags
from .framework import random as _random_mod

from .tensor import Tensor, Parameter, to_tensor
from .autograd.grad_mode import no_grad, enable_grad, is_grad_enabled, set_grad_enabled
from .autograd import grad
from . import autograd

# op library — star-exported at top level (paddle.add, paddle.matmul, ...)
from .ops import *  # noqa: F401,F403
from . import ops

from . import nn
from . import regularizer
from . import optimizer
from . import amp
from . import io
from . import metric
from .framework_io import save, load
from .nn.initializer import ParamAttr


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    """Standalone trainable Parameter (parity:
    python/paddle/tensor/creation.py create_parameter — LayerHelper path
    without requiring a Layer)."""
    from .nn.layer_base import Layer
    helper = Layer()
    p = helper.create_parameter(shape, attr=attr, dtype=dtype,
                                is_bias=is_bias,
                                default_initializer=default_initializer)
    if p is not None and name is not None:
        p.name = name
    return p

from . import jit
from . import static
from .static.api import enable_static, disable_static, in_dynamic_mode
from . import device
from . import vision
from . import inference
from . import incubate
from . import profiler
from .hapi import Model, summary
from .hapi.flops import flops
from . import hub
from . import text
from . import base
from . import fluid
from . import sysconfig
from . import geometric
from .hapi import callbacks

from . import distributed
from .distributed.parallel import DataParallel

from . import fft
from . import signal
from . import multiprocessing
from . import sparse
from . import distribution
from . import audio
from . import utils
from . import version
from . import onnx
from . import generation
from . import diffusion
from . import observability


def is_grad_enabled_():
    return is_grad_enabled()


def get_default_place():
    from .framework.place import _default_place
    return _default_place()


from .framework.place import is_compiled_with_rocm  # noqa: E402


def is_compiled_with_custom_device(device_type=None):
    from . import device as _device
    return bool(_device.get_all_custom_device_type())


def device_count():
    import jax as _jax
    return len(_jax.devices())


def disable_signal_handler():
    """Parity shim: paddle installs C++ signal handlers; here python's
    default handlers are already in charge, so this is a no-op."""


class LazyGuard:
    """Parity: paddle.LazyGuard — upstream defers parameter
    materialization. Initializers here are cheap jax ops, so the guard
    is a transparent context (parameters exist immediately, which is a
    superset of the lazy contract for user code)."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Parity: paddle.set_printoptions (python/paddle/tensor/to_string.py).
    Tensor repr here prints through numpy, so numpy's printoptions ARE the
    printoptions."""
    import numpy as _np
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


# paddle.dtype: dtypes in this framework ARE numpy dtype objects
import numpy as _np_mod  # noqa: E402
dtype = _np_mod.dtype


def in_static_mode():
    """Parity: paddle.in_static_mode (inverse of in_dynamic_mode)."""
    return not in_dynamic_mode()


def is_compiled_with_cinn():
    """Parity: CINN's role is subsumed by XLA here (SURVEY §2.1)."""
    return False


def batch(reader, batch_size, drop_last=False):
    """Parity: paddle.batch — legacy reader-composer (python/paddle/
    batch.py): wraps a sample reader into a batched reader."""
    def batched():
        buf = []
        for sample in reader():
            buf.append(sample)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched


from .amp import is_autocast_enabled, get_autocast_dtype  # noqa: E402
amp_guard = amp.amp_guard

"""Global stateful RNG over JAX functional PRNG.

Reference parity: paddle/phi/core/generator.cc (Generator with per-device
state), python/paddle/framework/random.py (paddle.seed, get/set_rng_state)
and fleet's RNG tracker (fleet/meta_parallel/parallel_layers/random.py:
get_rng_state_tracker) used by recompute and TP dropout.

Design: a single global key; every random op *splits* the key (new state is
rebound), giving Paddle's stateful-seed semantics on top of jax.random.
Under `jax.jit` tracing the split happens at trace time, so a traced function
captures a fixed key — matching Paddle's static-graph seed capture. For
per-axis determinism (TP local vs global dropout) the RNGStateTracker keeps
named independent key streams.
"""
from __future__ import annotations

import contextlib

import numpy as np
import jax


class _GlobalGenerator:
    """Key creation is LAZY: materializing a jax PRNG key initializes the
    XLA backend, and doing that at `import paddle_tpu` time makes import
    block on TPU client bring-up."""

    def __init__(self, seed: int = 0):
        self._lazy_key = None
        self._seed = seed
        self._host_draws = 0

    @property
    def _key(self):
        if self._lazy_key is None:
            self._lazy_key = jax.random.key(self._seed)
        return self._lazy_key

    @_key.setter
    def _key(self, value):
        self._lazy_key = value

    def manual_seed(self, seed: int):
        self._seed = int(seed)
        self._lazy_key = None
        self._host_draws = 0
        return self

    def split(self):
        """Return a fresh subkey; advances the global state."""
        self._key, sub = jax.random.split(self._key)
        return sub

    def host_rng(self) -> np.random.Generator:
        """A deterministic host-side (numpy) stream for FLAGS_host_init:
        each draw gets a fresh Philox keyed on (seed, draw counter), so
        same-seed processes produce identical parameters without a single
        device roundtrip. Independent of the jax.random key state."""
        rng = np.random.Generator(
            np.random.Philox(key=[self._seed & 0xFFFFFFFFFFFFFFFF,
                                  self._host_draws]))
        self._host_draws += 1
        return rng

    def get_state(self):
        return jax.random.key_data(self._key)

    def set_state(self, state):
        self._key = jax.random.wrap_key_data(np.asarray(state))


_generator = _GlobalGenerator(0)


def seed(s: int):
    """paddle.seed"""
    _generator.manual_seed(s)
    return _generator


def default_generator() -> _GlobalGenerator:
    return _generator


def next_key():
    return _generator.split()


def get_rng_state():
    return [_generator.get_state()]


def set_rng_state(state):
    _generator.set_state(state[0] if isinstance(state, (list, tuple)) else state)


class RNGStatesTracker:
    """Named independent RNG streams (parity: fleet parallel_layers/random.py).

    Used so that e.g. TP-local dropout differs across model-parallel ranks
    while global dropout matches.
    """

    def __init__(self):
        self._states = {}

    def reset(self):
        self._states = {}

    def add(self, name: str, seed_: int):
        if name in self._states:
            raise ValueError(f"rng state {name} already exists")
        self._states[name] = _GlobalGenerator(seed_)

    @contextlib.contextmanager
    def rng_state(self, name: str = "global_seed"):
        global _generator
        if name not in self._states:
            raise ValueError(f"rng state {name} not added")
        prev = _generator
        _generator = self._states[name]
        try:
            yield
        finally:
            _generator = prev

    def get_states_tracker(self):
        return {k: g.get_state() for k, g in self._states.items()}

    def set_states_tracker(self, states):
        for k, s in states.items():
            if k not in self._states:
                self._states[k] = _GlobalGenerator(0)
            self._states[k].set_state(s)


_rng_tracker = RNGStatesTracker()


def get_rng_state_tracker() -> RNGStatesTracker:
    return _rng_tracker


# CUDA-named aliases (parity: paddle.get_cuda_rng_state — accelerator
# RNG state; on TPU the same threefry generator drives everything)
def get_cuda_rng_state():
    return get_rng_state()


def set_cuda_rng_state(state):
    set_rng_state(state)

"""Seeded RNG — counter-based PRNG with paddle's global-seed surface.

Reference: ``phi::Generator`` (paddle/phi/core/generator.h) + ``paddle.seed``
(python/paddle/framework/random.py). TPU-native design: jax's counter-based
threefry keys; the global generator folds a monotonically increasing counter into
the seeded root key, so eager calls are deterministic given ``paddle.seed(n)``.

Under ``jax.jit`` tracing (to_static / compiled train steps), eager stateful RNG
would bake randomness into the compiled program. :func:`trace_key_scope` lets the
compile layer inject a per-step key tensor; random ops then derive per-call-site
keys by fold_in of a trace-local counter — deterministic per trace, fresh per step.
This mirrors the TP-aware ``RNGStatesTracker`` (fleet/layers/mpu/random.py:34) needs.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np


class Generator:
    def __init__(self, seed: int = 0):
        self._seed = seed
        self._counter = 0

    def manual_seed(self, seed: int):
        self._seed = int(seed)
        self._counter = 0
        return self

    @property
    def initial_seed(self):
        return self._seed

    def next_key(self):
        key = jax.random.key(self._seed)
        key = jax.random.fold_in(key, self._counter)
        self._counter += 1
        return key

    def get_state(self):
        return (self._seed, self._counter)

    def set_state(self, state):
        self._seed, self._counter = state


class _TraceRNG(threading.local):
    def __init__(self):
        self.key = None
        self.counter = 0


_default_generator = Generator(0)
_trace_rng = _TraceRNG()


def default_generator() -> Generator:
    return _default_generator


def seed(value: int) -> Generator:
    """paddle.seed"""
    return _default_generator.manual_seed(value)


def get_rng_state():
    return _default_generator.get_state()


def set_rng_state(state):
    _default_generator.set_state(state)


@contextlib.contextmanager
def trace_key_scope(key):
    """Route random ops to fold_in(key, callsite_counter) — used when staging
    eager code under jax.jit so randomness stays an input, not a constant."""
    prev_key, prev_counter = _trace_rng.key, _trace_rng.counter
    _trace_rng.key = key
    _trace_rng.counter = 0
    try:
        yield
    finally:
        _trace_rng.key, _trace_rng.counter = prev_key, prev_counter


def next_key():
    """Key for one random op call (eager or traced)."""
    if _trace_rng.key is not None:
        k = jax.random.fold_in(_trace_rng.key, _trace_rng.counter)
        _trace_rng.counter += 1
        return k
    return _default_generator.next_key()


def next_key_spec():
    """HOST-side step-key descriptor: a ``np.uint32[3]`` ``[seed_hi,
    seed_lo, counter]`` array, advancing the global generator exactly like
    :func:`next_key`.

    The eager ``next_key()`` issues two device ops per call
    (``jax.random.key`` + ``fold_in``) — two launches per step that the
    compiled program does not need. A compiled train step instead takes this numpy
    spec as a plain input and derives the identical key IN-program via
    :func:`derive_key`, so a step consumes zero eager dispatches for RNG.

    The seed ships as the 64-bit two's-complement value split hi/lo (under
    the default threefry impl these ARE the key words), so derivation is
    bit-identical to the eager key for ANY integer seed, negative
    included. Counters wrap at 2**32 (4B steps).
    """
    gen = _default_generator
    s64 = int(gen._seed) & 0xFFFFFFFFFFFFFFFF
    spec = np.asarray([s64 >> 32, s64 & 0xFFFFFFFF,
                       gen._counter % (2 ** 32)], np.uint32)
    gen._counter += 1
    return spec  # numpy-only: zero device ops on the per-step path


def derive_key(spec):
    """In-trace twin of ``Generator.next_key``: rebuild the key from the
    spec's seed words and fold in the step counter. Under the default
    threefry impl the two words ARE the key data (``wrap_key_data`` — the
    exact inverse of ``key(seed)``); under another jax_default_prng_impl
    (e.g. ``rbg``, whose key data is uint32[4]) the 64-bit seed is
    reassembled and fed to ``jax.random.key`` so the derivation stays
    impl-generic. Bit-identical to the eager key either way."""
    impl = getattr(jax.config, "jax_default_prng_impl", "threefry2x32")
    if impl == "threefry2x32":
        base = jax.random.wrap_key_data(spec[:2])
    else:  # impl-generic: key() accepts a (traced) integer seed
        seed = (spec[0].astype(jnp.int64) << 32) | spec[1].astype(jnp.int64)
        base = jax.random.key(seed)
    return jax.random.fold_in(base, spec[2])

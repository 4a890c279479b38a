"""Global RNG state with paddle-parity stateful surface over JAX PRNG keys.

Ref surface: paddle.seed, paddle.get_rng_state/set_rng_state (python/paddle/
framework/random.py upstream layout). Mechanism is TPU-native: a counter-based
threefry key, advanced by fold_in per draw — deterministic, checkpointable,
and per-mesh-axis foldable (the TP RNGStatesTracker parity lives in
paddle_tpu.distributed.random, built on the same fold_in primitive).

Inside a traced function (jit), eager draws would bake constants; traced code
paths (Trainer, dropout under to_static) must push an explicit traced key via
:func:`rng_key_guard`, which takes precedence over the global generator.
"""

from __future__ import annotations

import threading
from typing import List, Optional

import jax
import jax.numpy as jnp

__all__ = ["Generator", "seed", "default_generator", "next_key",
           "get_rng_state", "set_rng_state", "rng_key_guard", "fold_in_axis"]


class Generator:
    def __init__(self, seed_: int = 0):
        self.manual_seed(seed_)

    def manual_seed(self, s: int) -> "Generator":
        self._seed = int(s)
        self._counter = 0
        # the key is built on first draw: making one here would
        # initialise the XLA backend at package import, and a launcher
        # parent that imports the package must stay off the device
        self._key_cache = None
        return self

    @property
    def _key(self):
        if self._key_cache is None:
            self._key_cache = jax.random.key(self._seed)
        return self._key_cache

    def next_key(self):
        self._counter += 1
        return jax.random.fold_in(self._key, self._counter)

    def get_state(self):
        return (self._seed, self._counter)

    def set_state(self, state) -> None:
        self._seed, self._counter = int(state[0]), int(state[1])
        self._key_cache = None


default_generator = Generator(0)


class _TraceState(threading.local):
    def __init__(self):
        self.key_stack: List = []
        self.trace_counter = 0


_trace = _TraceState()


class rng_key_guard:
    """Push an explicit (possibly traced) base key; draws inside the context
    fold a local counter into it instead of touching global state."""

    def __init__(self, key):
        if isinstance(key, int):
            key = jax.random.key(key)
        self._key = key

    def __enter__(self):
        _trace.key_stack.append([self._key, 0])
        return self

    def __exit__(self, *exc):
        _trace.key_stack.pop()
        return False


def next_key():
    if _trace.key_stack:
        entry = _trace.key_stack[-1]
        entry[1] += 1
        return jax.random.fold_in(entry[0], entry[1])
    return default_generator.next_key()


def in_rng_guard() -> bool:
    return bool(_trace.key_stack)


def seed(s: int) -> Generator:
    """paddle.seed parity: reseed the global generator."""
    return default_generator.manual_seed(s)


def get_rng_state():
    return [default_generator.get_state()]


def set_rng_state(state) -> None:
    default_generator.set_state(state[0])


def fold_in_axis(key, axis_index):
    """Fold a mesh-axis index into a key — the TPU-native mechanism behind
    deterministic per-rank dropout (ref parity: fleet RNGStatesTracker,
    meta_parallel/random.py `get_rng_state_tracker`)."""
    return jax.random.fold_in(key, axis_index)

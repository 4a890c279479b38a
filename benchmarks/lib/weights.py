"""Weights from the seed, made on the device in ONE jitted call, in the
type they are served in."""

from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"),
                              seed >> 31)


def make_weights(shapes: Sequence[Tuple[str, Tuple[int, ...]]], seed: int,
                 dtype, embed_std: float = 0.02,
                 embed_names: Sequence[str] = ("embed_tokens",)
                 ) -> Dict[str, "jax.Array"]:
    """One array per (name, shape): vectors are ones (norm gains),
    matrices N(0, 2 / (fan_in + fan_out)) as the program's Xavier
    initialiser draws them, embeddings N(0, embed_std)."""
    import jax
    import jax.numpy as jnp

    def build(key):
        out = {}
        for i, (name, shape) in enumerate(shapes):
            if len(shape) < 2:
                out[name] = jnp.ones(shape, dtype)
                continue
            std = embed_std if any(e in name for e in embed_names) \
                else math.sqrt(2.0 / (shape[0] + shape[1]))
            k = jax.random.fold_in(key, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * std).astype(dtype)
        return out

    return jax.jit(build)(seed_key(seed))

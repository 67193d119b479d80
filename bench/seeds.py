"""Seeds: one ``--seed`` (any whole number, also past 32 bits) gives every
random stream of a run, each under a name of its own."""

from __future__ import annotations

import zlib

import numpy as np

_MASK = 0xFFFFFFFF


def numpy_rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([seed & _MASK, (seed >> 32) & _MASK,
                                  zlib.crc32(stream.encode())])


def jax_key(seed: int, stream: str):
    import jax

    key = jax.random.PRNGKey(np.uint32(seed & _MASK))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & _MASK))
    return jax.random.fold_in(key, np.uint32(zlib.crc32(stream.encode())))

"""Random weights from the seed, made by the benchmark in one jitted call.

The program decides the layout of its parameters (``shapes``, from
``jax.eval_shape`` of its own initializer, so nothing is made there); the
benchmark decides every value, by the leaf's name: norms 1, the embedding
N(0, 0.02^2), every other matrix N(0, 1/fan_in), truncated at 2 sigma, in
float32.  Rows of the embedding and columns of the head past the
configuration's vocabulary (the program's padding) are zero.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def _leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _init(key, name: str, shape, vocab: int):
    if name.rsplit("/", 1)[-1].startswith("norm") or name.endswith("_norm"):
        return jnp.ones(shape, jnp.float32)
    std = 0.02 if name == "embed" else shape[-2] ** -0.5
    w = std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    if name == "embed":
        w = w.at[vocab:].set(0.0)
    if name == "head":
        w = w.at[:, vocab:].set(0.0)
    return w


def build(shapes, key, vocab: int):
    """Weights shaped like ``shapes`` (a tree of ``ShapeDtypeStruct``);
    traceable, for use inside a jitted call."""
    leaves, tdef = jax.tree_util.tree_flatten_with_path(shapes)
    vals = []
    for path, leaf in leaves:
        name = _leaf_name(path)
        kk = jax.random.fold_in(key, zlib.crc32(name.encode()))
        vals.append(_init(kk, name, leaf.shape, vocab))
    return jax.tree_util.tree_unflatten(tdef, vals)


def make(shapes, key, vocab: int, shardings=None):
    """:func:`build` in one jitted call, laid out by ``shardings`` as the
    weights are made."""
    return jax.jit(lambda k: build(shapes, k, vocab),
                   out_shardings=shardings)(key)

"""Mesh and varying-manual-axes helpers over JAX's explicit-sharding API.

* :func:`make_mesh` — ``jax.make_mesh`` with every axis ``AxisType.Auto``.
* :func:`vma_of` / :func:`pvary_missing` — read and widen an array's
  varying-manual-axes set (``jax.typeof(x).vma``, ``lax.pcast``), which
  ``shard_map(check_vma=True)`` requires of loop carries and kernel outputs.
"""

from __future__ import annotations

import jax
from jax import lax
from jax.sharding import AxisType


def make_mesh(shape, names):
    """``jax.make_mesh`` with Auto axis types."""
    shape, names = tuple(shape), tuple(names)
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(shape))


def vma_of(x) -> frozenset:
    """Varying-manual-axes of ``x`` (empty outside ``shard_map``)."""
    return getattr(jax.typeof(x), "vma", frozenset())


def pvary_missing(v, names):
    """Cast ``v`` varying over every axis in ``names`` it is not already
    varying over."""
    missing = tuple(n for n in names if n not in vma_of(v))
    return lax.pcast(v, missing, to="varying") if missing else v

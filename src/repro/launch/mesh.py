"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single-pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model) — the "pod" axis
is the DCN/inter-pod dimension; SMI's routed transport treats it as one more
torus dimension with its own link bandwidth.
"""

from __future__ import annotations

from ..compat import make_mesh as _compat_make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _compat_make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/benchmarks/elastic restarts."""
    return _compat_make_mesh(shape, axes)


def grid_for(n: int) -> tuple[int, int]:
    """The squarest (rows, cols) grid of ``n`` devices, rows <= cols:
    1 -> (1, 1), 4 -> (2, 2), 8 -> (2, 4)."""
    rows = max(d for d in range(1, int(n ** 0.5) + 1) if n % d == 0)
    return rows, n // rows


def batch_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))

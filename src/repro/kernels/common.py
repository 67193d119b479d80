"""Shared kernel utilities: padding, backend dispatch.

Kernels TARGET TPU (MXU/VMEM tiling via BlockSpec); on this CPU container
they are validated with ``interpret=True`` against the pure-jnp ``ref.py``
oracles.  ``use_pallas(None)`` auto-selects: real kernels on TPU backends,
jnp reference elsewhere (models stay fast on CPU; tests force interpret)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_use_pallas(use_pallas: bool | None) -> bool:
    return on_tpu() if use_pallas is None else use_pallas


def pad_to(x: jax.Array, multiple: int, axis: int):
    """Zero-pad ``axis`` up to a multiple; returns (padded, original_size)."""
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def pallas_call(kernel, *, out_shape, interpret: bool = False,
                in_specs=None, out_specs=None, input_output_aliases=None,
                **kw):
    """``pl.pallas_call`` that also runs inside ``shard_map(check_vma=True)``.

    Every output varies over the mesh axes any operand varies over (a
    kernel's output ``ShapeDtypeStruct`` needs that ``vma`` there).

    The Pallas interpreter evaluates a kernel traced without ``vma``, which
    trips the varying-axes checks in two ways.  It starts an output that is
    not aliased to an input as an invariant zero buffer, which then fails
    the check of its own grid loop; so in interpret mode each such output
    is seeded from an extra varying zero input aliased onto it, which the
    kernel never sees.  And a primitive that mixes a block with a constant
    of the kernel fails its own check; a kernel without a grid is therefore
    interpreted by running its body on ``jax.new_ref`` refs instead, traced
    like any other code.  So off the TPU a gridless kernel under
    ``shard_map`` (the router tick) never reaches the Pallas interpreter:
    its CPU tests check the kernel body as plain JAX, and only a TPU run
    checks its ``pallas_call``.  ``tests/test_kernels.py`` fails once the
    interpreter carries ``vma`` itself, so both workarounds can go.
    """
    from jax.experimental import pallas as pl

    from ..compat import vma_of

    multi = isinstance(out_shape, (list, tuple))
    outs = list(out_shape) if multi else [out_shape]
    aliases = dict(input_output_aliases or {})
    specs = {} if in_specs is None else {"in_specs": list(in_specs)}
    if out_specs is not None:
        specs["out_specs"] = out_specs

    def call(*args):
        vma = frozenset().union(*(vma_of(a) for a in args))
        structs = [jax.ShapeDtypeStruct(o.shape, o.dtype, vma=vma)
                   for o in outs]
        shape_arg = structs if multi else structs[0]
        if not (interpret and vma):
            return pl.pallas_call(
                kernel, out_shape=shape_arg, input_output_aliases=aliases,
                interpret=interpret, **specs, **kw)(*args)
        if "grid" not in kw:
            outs_v = _run_on_refs(kernel, args, structs, aliases)
            return outs_v if multi else outs_v[0]
        free = [i for i in range(len(outs)) if i not in aliases.values()]
        seeds = [_varying_zeros(structs[i]) for i in free]
        n_in = len(args)
        seed_aliases = {**aliases, **{n_in + j: i for j, i in enumerate(free)}}
        seed_specs = dict(specs)
        if in_specs is not None:
            spec_of = (lambda i: out_specs[i]) if multi else (lambda i: out_specs)
            seed_specs["in_specs"] = specs["in_specs"] + [spec_of(i) for i in free]

        def seeded(*refs):
            return kernel(*refs[:n_in], *refs[n_in + len(free):])

        return pl.pallas_call(
            seeded, out_shape=shape_arg, input_output_aliases=seed_aliases,
            interpret=True, **seed_specs, **kw)(*args, *seeds)

    return call


def _varying_zeros(struct):
    from ..compat import pvary_missing

    return pvary_missing(jnp.zeros(struct.shape, struct.dtype), tuple(struct.vma))


def _run_on_refs(kernel, args, structs, aliases):
    """Interpret a gridless kernel: each operand and each output is one
    whole-array ref; an output aliased to an operand starts from its value."""
    src = {o: i for i, o in aliases.items()}
    in_refs = [jax.new_ref(a) for a in args]
    out_refs = [jax.new_ref(args[src[o]] if o in src else _varying_zeros(s))
                for o, s in enumerate(structs)]
    kernel(*in_refs, *out_refs)
    return [r[...] for r in out_refs]


def match_vma(x, ref):
    """Promote ``x``'s varying-manual-axes to match ``ref`` (no-op outside
    shard_map).  Needed for scan carries created inside shard_map bodies."""
    from ..compat import pvary_missing, vma_of

    return pvary_missing(x, tuple(vma_of(ref)))

"""Compile the main-path kernels for a described (not attached) TPU v5e.

Nothing runs: each test lowers and compiles at real widths for a ``v5e:2x2``
topology, so Mosaic refuses here what it would refuse on the chip (tiling,
VMEM, unsupported ops), and asserts the kernel is in the compiled program
(``tpu_custom_call``) rather than an interpreter or a reference.  The
topology is described inside a fixture: the TPU library may be loaded by
one process at a time, and only the worker running this file loads it.
The tests skip only where no TPU library is installed; a library that is
installed but cannot describe the topology fails them.
"""

from __future__ import annotations

import importlib.util
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler installed")
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled.as_text()


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_yi6b_widths(one_chip):
    from repro.kernels import flash_attention

    q = _struct((1, 2048, 32, 128), jnp.bfloat16, one_chip)
    kv = _struct((1, 2048, 4, 128), jnp.bfloat16, one_chip)
    txt = _compile(partial(flash_attention, causal=True, use_pallas=True),
                   q, kv, kv)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("n", [4096, 8192])
def test_stencil_fits_vmem(n, one_chip):
    from repro.kernels import stencil_step

    txt = _compile(partial(stencil_step, use_pallas=True),
                   _struct((n, n), jnp.float32, one_chip))
    assert "tpu_custom_call" in txt


def test_fused_accumulate(one_chip):
    from repro.transport.fused import fused_accumulate

    a = _struct((1 << 20,), jnp.float32, one_chip)
    assert "tpu_custom_call" in _compile(fused_accumulate, a, a)


def test_ssd_mamba2_widths(one_chip):
    from repro.kernels import ssd_scan

    BH, S, Dh, Dst = 80, 2048, 64, 128  # mamba2-2.7b: 80 heads, state 128
    f32 = jnp.float32
    txt = _compile(
        partial(ssd_scan, use_pallas=True),
        _struct((BH, S, Dh), f32, one_chip), _struct((BH, S), f32, one_chip),
        _struct((BH, S, Dst), f32, one_chip),
        _struct((BH, S, Dst), f32, one_chip), _struct((BH, 1), f32, one_chip))
    assert "tpu_custom_call" in txt


def test_ssd_mamba2_widths_highest_precision(one_chip):
    """The kernel's matmuls take f32 MXU passes when traced at "highest",
    as ``chip_smoke.py`` checks it against the sequential reference."""
    from repro.kernels import ssd_scan

    BH, S, Dh, Dst = 80, 2048, 64, 128
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        txt = _compile(
            partial(ssd_scan, use_pallas=True),
            _struct((BH, S, Dh), f32, one_chip),
            _struct((BH, S), f32, one_chip),
            _struct((BH, S, Dst), f32, one_chip),
            _struct((BH, S, Dst), f32, one_chip),
            _struct((BH, 1), f32, one_chip))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("impl", ["vector", "pallas"])
def test_router_tick_on_2x2_mesh(topo, impl, monkeypatch):
    """The packet backend's router compiles for four chips: one packed
    all-to-all per tick, and under impl="pallas" the tick as a Mosaic
    kernel (the TPU branch of ``run_router``, which this CPU process would
    not take on its own)."""
    from repro.core import Communicator
    from repro.core.router import RouterConfig, run_router
    from repro.kernels import common

    monkeypatch.setattr(common, "on_tpu", lambda: True)

    dims = (2, 2)
    mesh = Mesh(np.array(topo.devices).reshape(dims), ("x", "y"))
    comm = Communicator.create(("x", "y"), dims)
    cfg = RouterConfig(dims=dims)
    n = comm.size
    spec = P(("x", "y"))

    def body(tbl, pay, dst, ln):
        out = run_router(cfg, comm, tbl, pay[0], dst[0], ln[0], 16,
                         impl=impl)
        return tuple(o[None] for o in out)

    f = jax.shard_map(body, mesh=mesh, in_specs=(P(),) + (spec,) * 3,
                      out_specs=(spec,) * 4)
    sh = lambda s: NamedSharding(mesh, s)
    txt = _compile(
        f, _struct((n, n), jnp.int32, sh(P())),
        _struct((n, cfg.n_ports, cfg.fifo_cap, cfg.pkt_elems), jnp.float32,
                sh(spec)),
        _struct((n, cfg.n_ports, cfg.fifo_cap), jnp.int32, sh(spec)),
        _struct((n, cfg.n_ports), jnp.int32, sh(spec)))
    assert "all-to-all" in txt
    assert ("tpu_custom_call" in txt) == (impl == "pallas")

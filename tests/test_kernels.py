"""Per-kernel validation: interpret=True Pallas vs pure-jnp oracles,
sweeping shapes and dtypes (deliverable c)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import (
    attention_ref,
    flash_attention,
    matmul,
    matmul_ref,
    ssd_decode_step,
    ssd_ref,
    ssd_scan,
    stencil_ref,
    stencil_step,
)
from repro.kernels.ssd.ops import _ssd_chunked_jnp
from repro.kernels.stencil.kernel import block_rows

RNG = np.random.RandomState


# ---------------------------------------------------------------- matmul


@pytest.mark.parametrize("M,K,N", [
    (128, 128, 128),
    (256, 384, 128),
    (100, 70, 50),      # ragged -> padding path
    (8, 512, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes_dtypes(M, K, N, dtype):
    rng = RNG(0)
    x = jnp.asarray(rng.randn(M, K), dtype)
    w = jnp.asarray(rng.randn(K, N), dtype)
    got = matmul(x, w, interpret=True)
    want = matmul_ref(x, w)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@settings(max_examples=10, deadline=None)
@given(
    m=st.integers(1, 3), k=st.integers(1, 3), n=st.integers(1, 3),
    seed=st.integers(0, 99),
)
def test_matmul_property_blocked(m, k, n, seed):
    rng = RNG(seed)
    M, K, N = 64 * m, 64 * k, 64 * n
    x = jnp.asarray(rng.randn(M, K), jnp.float32)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)
    got = matmul(x, w, block_m=64, block_n=64, block_k=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(matmul_ref(x, w)), rtol=3e-5, atol=3e-5
    )


# ------------------------------------------------------- flash attention


@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (1, 128, 2, 2, 64),     # MHA
    (2, 256, 4, 2, 64),     # GQA
    (1, 200, 4, 1, 32),     # MQA + ragged seq (padding path)
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_ref(B, S, H, Hkv, D, causal):
    rng = RNG(1)
    q = jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hkv, D) * 0.3, jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hkv, D) * 0.3, jnp.float32)
    got = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=64, block_k=64)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_local_window():
    rng = RNG(2)
    B, S, H, D, W = 1, 256, 2, 32, 64
    q = jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=W, interpret=True,
                          block_q=64, block_k=64)
    want = attention_ref(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_grad_matches_ref():
    """The kernel is forward-only; its custom VJP is the reference's."""
    rng = RNG(5)
    B, S, H, Hkv, D = 1, 128, 4, 2, 32
    q, k, v = (jnp.asarray(rng.randn(B, S, h, D) * 0.3, jnp.float32)
               for h in (H, Hkv, Hkv))

    def loss(attn, q, k, v):
        return jnp.sum(attn(q, k, v) ** 2)

    kern = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                           interpret=True, block_q=64,
                                           block_k=64)
    ref = lambda q, k, v: attention_ref(q, k, v, causal=True)
    got = jax.grad(partial(loss, kern), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(partial(loss, ref), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    rng = RNG(3)
    B, S, H, D = 1, 128, 2, 64
    q = jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=64, block_k=64)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


# --------------------------------------------------------------- stencil


@pytest.mark.parametrize("M,N", [(128, 128), (256, 128), (100, 130)])
def test_stencil_vs_ref(M, N):
    rng = RNG(4)
    x = jnp.asarray(rng.randn(M, N), jnp.float32)
    got = stencil_step(x, interpret=True, block_m=64)
    want = stencil_ref(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("M,N", [(200, 1024), (64, 8192)])
def test_stencil_block_from_width_vs_ref(M, N):
    """Default block: sized from the row width, so the grid has several
    row blocks whose 8-row halo tiles must meet exactly."""
    x = jnp.asarray(RNG(6).randn(M, N), jnp.float32)
    assert block_rows(M, N) < M
    np.testing.assert_array_equal(np.asarray(stencil_step(x, interpret=True)),
                                  np.asarray(stencil_ref(x)))


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 99), m=st.sampled_from([64, 96, 128]))
def test_stencil_property_mean_preserving_bound(seed, m):
    """Property: max|stencil(x)| <= max|x| (averaging operator)."""
    rng = RNG(seed)
    x = jnp.asarray(rng.randn(m, 128), jnp.float32)
    y = stencil_step(x, interpret=True, block_m=64)
    assert np.abs(np.asarray(y)).max() <= np.abs(np.asarray(x)).max() + 1e-6


# ------------------------------------------------------------------- ssd


@pytest.mark.parametrize("S,chunk", [(128, 64), (256, 128), (200, 64)])
def test_ssd_kernel_vs_sequential_ref(S, chunk):
    rng = RNG(5)
    BH, Dh, Dst = 4, 16, 8
    x = jnp.asarray(rng.randn(BH, S, Dh) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.rand(BH, S) * 0.5 + 0.05, jnp.float32)
    B = jnp.asarray(rng.randn(BH, S, Dst) * 0.5, jnp.float32)
    C = jnp.asarray(rng.randn(BH, S, Dst) * 0.5, jnp.float32)
    A = jnp.asarray(-np.exp(rng.randn(BH, 1) * 0.3), jnp.float32)  # negative

    got = ssd_scan(x, dt, B, C, A, chunk=chunk, interpret=True)
    want = ssd_ref(x, dt, B, C, A)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ssd_jnp_dispatch_matches_kernel():
    """The CPU dispatch path (chunked jnp) must equal the kernel's math."""
    rng = RNG(6)
    BH, S, Dh, Dst = 2, 192, 8, 4
    x = jnp.asarray(rng.randn(BH, S, Dh) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.rand(BH, S) * 0.5 + 0.05, jnp.float32)
    B = jnp.asarray(rng.randn(BH, S, Dst) * 0.5, jnp.float32)
    C = jnp.asarray(rng.randn(BH, S, Dst) * 0.5, jnp.float32)
    A = jnp.asarray(-np.ones((BH, 1)), jnp.float32)
    a = ssd_scan(x, dt, B, C, A, chunk=64, interpret=True)
    b = _ssd_chunked_jnp(x, dt, B, C, A, chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


def test_ssd_decode_matches_scan_tail():
    """Decoding token-by-token reproduces the scan output (state carry)."""
    rng = RNG(7)
    BH, S, Dh, Dst = 2, 32, 8, 4
    x = jnp.asarray(rng.randn(BH, S, Dh) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.rand(BH, S) * 0.5 + 0.05, jnp.float32)
    B = jnp.asarray(rng.randn(BH, S, Dst) * 0.5, jnp.float32)
    C = jnp.asarray(rng.randn(BH, S, Dst) * 0.5, jnp.float32)
    A = jnp.asarray(-np.ones((BH, 1)), jnp.float32)

    want = ssd_ref(x, dt, B, C, A)
    h = jnp.zeros((BH, Dst, Dh), jnp.float32)
    ys = []
    for t in range(S):
        h, y = ssd_decode_step(h, x[:, t], dt[:, t], B[:, t], C[:, t], A)
        ys.append(y)
    got = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------- chunked attention ref


@pytest.mark.parametrize("Sq,Skv,window", [
    (128, 128, None), (128, 128, 32), (64, 192, None),  # decode-ish right-align
])
def test_attention_chunked_matches_dense(Sq, Skv, window):
    from repro.kernels import attention_chunked_ref

    rng = RNG(8)
    B, H, Hkv, D = 2, 4, 2, 16
    q = jnp.asarray(rng.randn(B, Sq, H, D) * 0.3, jnp.float32)
    k = jnp.asarray(rng.randn(B, Skv, Hkv, D) * 0.3, jnp.float32)
    v = jnp.asarray(rng.randn(B, Skv, Hkv, D) * 0.3, jnp.float32)
    got = attention_chunked_ref(q, k, v, causal=True, window=window, block_k=32)
    want = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------ interpreter under check_vma


def test_interpreter_still_needs_vma_workaround():
    """The Pallas interpreter traces a kernel without ``vma``, so a
    gridless kernel mixing a block with a constant fails under
    ``shard_map(check_vma=True)``; ``kernels.common.pallas_call`` runs it
    on refs instead.  When the plain call stops failing, the workarounds
    in ``kernels.common.pallas_call`` are dead code: delete them."""
    from jax.experimental import pallas as pl
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from repro.compat import vma_of
    from repro.kernels.common import pallas_call

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    def plain(x):
        out = jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma_of(x))
        return pl.pallas_call(kern, out_shape=out, interpret=True)(x)

    def wrapped(x):
        out = jax.ShapeDtypeStruct(x.shape, x.dtype)
        return pallas_call(kern, out_shape=out, interpret=True)(x)

    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    smap = lambda f: jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                                           out_specs=P("x")))
    x = jnp.arange(16 * 128, dtype=jnp.float32).reshape(16, 128)
    with pytest.raises(ValueError, match="varying manual axes"):
        smap(plain)(x)
    np.testing.assert_array_equal(np.asarray(smap(wrapped)(x)),
                                  np.asarray(x) + 1.0)

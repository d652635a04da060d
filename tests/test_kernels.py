"""Pallas kernels vs ref.py oracles — shape/dtype sweeps in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.im2col_gemm.im2col_gemm import conv_im2col, conv_im2col_batch
from repro.kernels.im2col_gemm.ref import conv_ref
from repro.kernels.matmul.matmul import matmul, matmul_batch
from repro.kernels.matmul.ops import VARIANTS as MM_VARIANTS
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.winograd.ops import winograd_conv_batch_op, winograd_conv_op
from repro.kernels.winograd.ref import conv3x3_ref, point_gemm_ref
from repro.kernels.winograd.winograd import (winograd_point_gemm,
                                             winograd_point_gemm_batch)

_TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-4),
        jnp.bfloat16: dict(rtol=5e-2, atol=5e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,blocks", [
    ((256, 256, 256), (128, 128, 128)),
    ((300, 200, 150), (128, 128, 128)),     # non-divisible edges
    ((64, 64, 64), (128, 128, 128)),        # blocks larger than array
    ((100, 77, 33), (32, 32, 32)),
])
def test_matmul_kernel(shape, blocks, dtype, rng):
    m, k, n = shape
    bm, bk, bn = blocks
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    y = jnp.asarray(rng.standard_normal((k, n)), dtype)
    got = matmul(x, y, bm=bm, bk=bk, bn=bn, interpret=True)
    ref = matmul_ref(x, y)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), **_TOL[dtype])


@pytest.mark.parametrize("variant", sorted(MM_VARIANTS))
def test_matmul_all_variants(variant, rng):
    from repro.kernels.matmul.ops import matmul_op
    x = jnp.asarray(rng.standard_normal((160, 96)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((96, 200)), jnp.float32)
    got = matmul_op(x, y, variant=variant, interpret=True)
    np.testing.assert_allclose(got, matmul_ref(x, y), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("cfg", [(4, 256, 64, 128, 128), (2, 256, 32, 64, 64),
                                 (3, 512, 64, 128, 256)])
def test_flash_attention_kernel(cfg, causal, rng):
    bh, s, d, bq, bkv = cfg
    q, k, v = (jnp.asarray(rng.standard_normal((bh, s, d)), jnp.float32)
               for _ in range(3))
    got = flash_attention(q, k, v, causal=causal, bq=bq, bkv=bkv, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_flash_attention_gqa_wrapper(rng):
    B, S, Hq, Hkv, d = 2, 256, 8, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, Hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, d)), jnp.float32)
    got = flash_attention_op(q, k, v, interpret=True)
    kr = jnp.repeat(k, Hq // Hkv, 2)
    vr = jnp.repeat(v, Hq // Hkv, 2)
    ref = attention_ref(q.transpose(0, 2, 1, 3).reshape(B * Hq, S, d),
                        kr.transpose(0, 2, 1, 3).reshape(B * Hq, S, d),
                        vr.transpose(0, 2, 1, 3).reshape(B * Hq, S, d))
    ref = ref.reshape(B, Hq, S, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cfg", [(8, 16, 16, 3, 1), (4, 19, 8, 3, 2),
                                 (3, 14, 32, 5, 1), (8, 9, 8, 1, 1),
                                 (5, 12, 20, 3, 1)])
def test_im2col_gemm_kernel(cfg, rng):
    C, H, K, f, s = cfg
    x = jnp.asarray(rng.standard_normal((C, H, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, C, f, f)), jnp.float32)
    got = conv_im2col(x, w, s, bk=16, interpret=True)
    np.testing.assert_allclose(got, conv_ref(x, w, s), rtol=1e-4, atol=2e-4)


def test_winograd_point_gemm(rng):
    u = jnp.asarray(rng.standard_normal((16, 60, 48)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((16, 48, 75)), jnp.float32)
    got = winograd_point_gemm(u, v, bk=32, bt=32, bc=32, interpret=True)
    np.testing.assert_allclose(got, point_gemm_ref(u, v), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cfg", [(4, 16, 8), (3, 15, 16), (6, 21, 10)])
def test_winograd_full_conv(cfg, rng):
    C, H, K = cfg
    x = jnp.asarray(rng.standard_normal((C, H, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, C, 3, 3)), jnp.float32)
    got = winograd_conv_op(x, w, interpret=True)
    np.testing.assert_allclose(got, conv3x3_ref(x, w), rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Batch-grid variants (explicit batch dimension in the kernel grid)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,blocks", [
    ((2, 100, 77, 53), (32, 32, 32)),
    ((3, 64, 64, 64), (128, 128, 128)),     # blocks larger than array
    ((1, 130, 70, 140), (64, 64, 64)),      # non-divisible edges
])
def test_matmul_batch_kernel(shape, blocks, rng):
    B, m, k, n = shape
    bm, bk, bn = blocks
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((B, k, n)), jnp.float32)
    got = matmul_batch(x, y, bm=bm, bk=bk, bn=bn, interpret=True)
    ref = jnp.stack([matmul_ref(x, y[b]) for b in range(B)])
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cfg", [(2, 4, 16, 8, 3, 1), (3, 4, 19, 8, 3, 2),
                                 (2, 3, 14, 32, 5, 1), (2, 8, 9, 8, 1, 1)])
def test_im2col_gemm_batch_kernel(cfg, rng):
    N, C, H, K, f, s = cfg
    x = jnp.asarray(rng.standard_normal((N, C, H, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, C, f, f)), jnp.float32)
    got = conv_im2col_batch(x, w, s, bk=16, interpret=True)
    ref = jnp.stack([conv_ref(x[b], w, s) for b in range(N)])
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=2e-4)


def test_winograd_point_gemm_batch(rng):
    u = jnp.asarray(rng.standard_normal((16, 60, 48)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 16, 48, 75)), jnp.float32)
    got = winograd_point_gemm_batch(u, v, bk=32, bt=32, bc=32, interpret=True)
    ref = jnp.stack([point_gemm_ref(u, v[b]) for b in range(2)])
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_winograd_full_conv_batch(rng):
    x = jnp.asarray(rng.standard_normal((2, 4, 16, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((8, 4, 3, 3)), jnp.float32)
    got = winograd_conv_batch_op(x, w, interpret=True)
    ref = jnp.stack([conv3x3_ref(x[b], w) for b in range(2)])
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-3)

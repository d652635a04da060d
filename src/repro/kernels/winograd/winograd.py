"""Winograd F(2x2, 3x3) convolution: Pallas batched point-GEMM.

Winograd's hot spot is the batched per-tile-point GEMM
``M[p] = U[p] @ V[p]`` for the 16 transform points p — on TPU this is 16
MXU GEMMs of shape (K, C) x (C, T). The input/output transforms are cheap
bandwidth-bound 4x4 stencils handled by XLA (ops.py); the kernel owns the
compute-bound stage, tiling (K, T) per point with the C reduction innermost.

``winograd_point_gemm_batch`` adds the request batch as an explicit leading
grid dimension over a shared transformed-weight tensor U — the compiled
serving plan's shape, where only V (the input transform) carries the batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _point_gemm_kernel(u_ref, v_ref, o_ref, acc_ref, *, n_c: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(u_ref[0], v_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == n_c - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def winograd_point_gemm(u: jnp.ndarray, v: jnp.ndarray, *, bk: int = 128,
                        bt: int = 128, bc: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """u: (P, K, C); v: (P, C, T) -> (P, K, T) — P parallel GEMMs
    (P = (m+r-1)^2 = 16 for F(2x2,3x3))."""
    P, K, C = u.shape
    T = v.shape[2]
    bk, bt, bc = min(bk, K), min(bt, T), min(bc, C)
    # pad to block multiples (partial tiles are undefined on TPU)
    Kp, Tp, Cp = -(-K // bk) * bk, -(-T // bt) * bt, -(-C // bc) * bc
    if (Kp, Cp) != (K, C):
        u = jnp.pad(u, ((0, 0), (0, Kp - K), (0, Cp - C)))
    if (Cp, Tp) != (C, T):
        v = jnp.pad(v, ((0, 0), (0, Cp - C), (0, Tp - T)))
    grid = (P, Kp // bk, Tp // bt, Cp // bc)
    out = pl.pallas_call(
        functools.partial(_point_gemm_kernel, n_c=grid[3]),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bk, bc), lambda p, i, j, c: (p, i, c)),
                  pl.BlockSpec((1, bc, bt), lambda p, i, j, c: (p, c, j))],
        out_specs=pl.BlockSpec((1, bk, bt), lambda p, i, j, c: (p, i, j)),
        out_shape=jax.ShapeDtypeStruct((P, Kp, Tp), u.dtype),
        scratch_shapes=[pltpu.VMEM((bk, bt), jnp.float32)],
        interpret=interpret,
    )(u, v)
    return out[:, :K, :T]


def _point_gemm_batch_kernel(u_ref, v_ref, o_ref, acc_ref, *, n_c: int):
    @pl.when(pl.program_id(4) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(u_ref[0], v_ref[0, 0],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(4) == n_c - 1)
    def _store():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


def winograd_point_gemm_batch(u: jnp.ndarray, v: jnp.ndarray, *, bk: int = 128,
                              bt: int = 128, bc: int = 128,
                              interpret: bool = False) -> jnp.ndarray:
    """u: (P, K, C) shared weights; v: (N, P, C, T) batched input transform
    -> (N, P, K, T). Grid (N, P, K tiles, T tiles, C tiles) — the batch is
    an explicit grid dimension, U blocks are revisited per image."""
    P, K, C = u.shape
    N, P2, C2, T = v.shape
    assert (P, C) == (P2, C2), (u.shape, v.shape)
    bk, bt, bc = min(bk, K), min(bt, T), min(bc, C)
    Kp, Tp, Cp = -(-K // bk) * bk, -(-T // bt) * bt, -(-C // bc) * bc
    if (Kp, Cp) != (K, C):
        with jax.named_scope("wpack"):
            u = jnp.pad(u, ((0, 0), (0, Kp - K), (0, Cp - C)))
    if (Cp, Tp) != (C, T):
        with jax.named_scope("pack"):
            v = jnp.pad(v, ((0, 0), (0, 0), (0, Cp - C), (0, Tp - T)))
    grid = (N, P, Kp // bk, Tp // bt, Cp // bc)
    out = pl.pallas_call(
        functools.partial(_point_gemm_batch_kernel, n_c=grid[4]),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bk, bc), lambda n, p, i, j, c: (p, i, c)),
                  pl.BlockSpec((1, 1, bc, bt), lambda n, p, i, j, c: (n, p, c, j))],
        out_specs=pl.BlockSpec((1, 1, bk, bt), lambda n, p, i, j, c: (n, p, i, j)),
        out_shape=jax.ShapeDtypeStruct((N, P, Kp, Tp), u.dtype),
        scratch_shapes=[pltpu.VMEM((bk, bt), jnp.float32)],
        interpret=interpret,
    )(u, v)
    with jax.named_scope("pack"):
        return out[:, :, :K, :T]

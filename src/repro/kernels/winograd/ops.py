"""Full Winograd conv: jnp transforms around the Pallas point-GEMM (the
compute-bound stage), generic over F(mxm, 3x3) via the shared transform
sets in ``primitives.conv``.

Epilogues (DESIGN.md §13): bias / residual / ReLU are applied right after
the inverse transform, inside the same jitted function — they cannot move
into the point-GEMM kernel (the transform is linear, ReLU is not; the
kernel's output lives in the transform domain), but fusing them here still
removes the separate elementwise pass over the activation at the plan
level.
"""
from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.common import default_interpret
from repro.kernels.winograd.winograd import (winograd_point_gemm,
                                             winograd_point_gemm_batch)
from repro.primitives.conv import _WINO_SETS

VARIANTS = {"wino-128x128": (128, 128), "wino-256x128": (256, 128),
            "wino-128x256": (128, 256)}


def _epilogue(y, bias, residual, relu: bool, channel_axis: int):
    if bias is not None:
        shape = [1] * y.ndim
        shape[channel_axis] = bias.shape[0]
        y = y + bias.astype(y.dtype).reshape(shape)
    if residual is not None:
        y = y + residual.astype(y.dtype)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y


@partial(jax.jit, static_argnames=("m", "bk", "bt", "bc", "relu", "interpret"))
def winograd_conv(x: jnp.ndarray, w: jnp.ndarray, *, m: int = 2,
                  bk: int = 128, bt: int = 128, bc: int = 128,
                  bias=None, residual=None, relu: bool = False,
                  interpret: bool | None = None) -> jnp.ndarray:
    """x: (C, H, W); w: (K, C, 3, 3) -> (K, H-2, W-2). Stride 1, F(mxm,3x3)."""
    AT, G, BT = (jnp.asarray(a, jnp.float32) for a in _WINO_SETS[(m, 3)])
    C, H, W = x.shape
    K = w.shape[0]
    n = m + 2
    oh, ow = H - 2, W - 2
    th, tw = -(-oh // m), -(-ow // m)
    ph, pw = (th - 1) * m + n, (tw - 1) * m + n
    xp = jnp.pad(x, ((0, 0), (0, ph - H), (0, pw - W)))
    rows = []
    for a in range(n):
        cols = [xp[:, a:a + (th - 1) * m + 1:m, b:b + (tw - 1) * m + 1:m]
                for b in range(n)]
        rows.append(jnp.stack(cols, -1))
    tiles = jnp.stack(rows, -2)                               # (C, th, tw, n, n)
    V = jnp.einsum("ap,cijpq,qb->abcij", BT, tiles.astype(jnp.float32), BT.T)
    V = V.reshape(n * n, C, th * tw)                          # (n², C, T)
    U = jnp.einsum("ar,kcrs,sb->abkc", G, w.astype(jnp.float32), G.T)
    U = U.reshape(n * n, K, C)

    interp = default_interpret() if interpret is None else interpret
    M = winograd_point_gemm(U, V.astype(U.dtype), bk=bk, bt=bt, bc=bc,
                            interpret=interp)                 # (n², K, T)
    M = M.reshape(n, n, K, th, tw)
    Y = jnp.einsum("ap,pqkij,qm->kiajm", AT, M, AT.T)         # (K, th, m, tw, m)
    y = Y.reshape(K, th * m, tw * m)[:, :oh, :ow]
    y = _epilogue(y, bias, residual, relu, channel_axis=0)
    return y.astype(x.dtype)


@partial(jax.jit, static_argnames=("m", "bk", "bt", "bc", "relu", "interpret",
                                   "pad"))
def winograd_conv_batch(x: jnp.ndarray, w: jnp.ndarray, *, m: int = 2,
                        bk: int = 128, bt: int = 128, bc: int = 128,
                        bias=None, residual=None, relu: bool = False,
                        interpret: bool | None = None,
                        pad: int = 0) -> jnp.ndarray:
    """x: (N, C, H, W); w: (K, C, 3, 3) -> (N, K, H+2·pad-2, W+2·pad-2).
    Stride 1, ``x`` zero-padded by ``pad`` on every side: that padding is
    folded into the pad to whole tiles, so a padded call makes one pad op
    (scope ``pack/pad``). Batched transforms around the batch-grid Pallas
    point-GEMM: U is transformed once and shared, only V carries the
    batch. The input and output transforms run under the scope ``pack``,
    the weight transform under ``wpack`` (``plan._emit``'s roles)."""
    AT, G, BT = (jnp.asarray(a, jnp.float32) for a in _WINO_SETS[(m, 3)])
    N, C, H, W = x.shape
    K = w.shape[0]
    n = m + 2
    oh, ow = H + 2 * pad - 2, W + 2 * pad - 2
    th, tw = -(-oh // m), -(-ow // m)
    ph, pw = (th - 1) * m + n, (tw - 1) * m + n
    with jax.named_scope("pack"):                 # input transform
        with jax.named_scope("pad") if pad else contextlib.nullcontext():
            xp = jnp.pad(x, ((0, 0), (0, 0), (pad, ph - H - pad),
                             (pad, pw - W - pad)))
        rows = []
        for a in range(n):
            cols = [xp[:, :, a:a + (th - 1) * m + 1:m,
                       b:b + (tw - 1) * m + 1:m] for b in range(n)]
            rows.append(jnp.stack(cols, -1))
        tiles = jnp.stack(rows, -2)                   # (N, C, th, tw, n, n)
        V = jnp.einsum("ap,ncijpq,qb->nabcij", BT,
                       tiles.astype(jnp.float32), BT.T)
        V = V.reshape(N, n * n, C, th * tw)           # (N, n², C, T)
    with jax.named_scope("wpack"):                # weight transform
        U = jnp.einsum("ar,kcrs,sb->abkc", G, w.astype(jnp.float32), G.T)
        U = U.reshape(n * n, K, C)

    interp = default_interpret() if interpret is None else interpret
    M = winograd_point_gemm_batch(U, V.astype(U.dtype), bk=bk, bt=bt, bc=bc,
                                  interpret=interp)   # (N, n², K, T)
    with jax.named_scope("pack"):                 # output transform
        M = M.reshape(N, n, n, K, th, tw)
        Y = jnp.einsum("ap,npqkij,qm->nkiajm", AT, M, AT.T)  # (N,K,th,m,tw,m)
        y = Y.reshape(N, K, th * m, tw * m)[:, :, :oh, :ow]
    y = _epilogue(y, bias, residual, relu, channel_axis=1)
    return y.astype(x.dtype)


def winograd_conv_op(x: jnp.ndarray, w: jnp.ndarray,
                     variant: str = "wino-128x128",
                     interpret: bool | None = None) -> jnp.ndarray:
    """x: (C, H, W); w: (K, C, 3, 3) -> (K, H-2, W-2). Stride 1, F(2x2,3x3)."""
    bk, bt = VARIANTS[variant]
    return winograd_conv(x, w, m=2, bk=bk, bt=bt, interpret=interpret)


def winograd_conv_batch_op(x: jnp.ndarray, w: jnp.ndarray,
                           variant: str = "wino-128x128",
                           interpret: bool | None = None) -> jnp.ndarray:
    """x: (N, C, H, W); w: (K, C, 3, 3) -> (N, K, H-2, W-2). Stride 1."""
    bk, bt = VARIANTS[variant]
    return winograd_conv_batch(x, w, m=2, bk=bk, bt=bt, interpret=interpret)

"""Fused im2col + GEMM convolution Pallas kernel.

The paper's dominant primitive family (im2col) materialises the (c*f*f, P)
patch matrix in HBM. On TPU the lowering belongs in VMEM: this kernel
builds each output row's patch block on-chip and feeds the MXU directly —
the HBM-level patch matrix never exists (the TPU adaptation of the family,
DESIGN.md §2.3).

Layout. Each grid step sees one whole image, so overlapping windows need no
overlapping blocks. The wrapper splits the image into its ``s × s`` stride
phases, ``xph[p, q, r, c, j] = x[c, r*s + p, j*s + q]``: kernel tap
``(a, b)`` of output row ``i`` then reads phase ``(a % s, b % s)`` at row
``i + a // s`` and columns ``b // s .. b // s + ow`` — a dynamic index on
a leading axis plus a static, unit-stride lane slice, which Mosaic lowers
(strided lane slices and ``(C, 1, W)`` row blocks it refuses). Rows sit on
a leading axis and the ``(C, W)`` plane is the tiled one; channels pad to a
multiple of 8 so the per-tap patch pieces stack along sublanes aligned.

Grid: (N, K blocks, output rows). Weights arrive as (K, f*f*Cp) in
(a, b, c) order, matching the patch stack.

Epilogues (DESIGN.md §13): optional bias (per output channel), residual
(output-shaped) and ReLU finish the output tile in VMEM before its single
HBM writeback. In interpret mode the epilogue runs once at the wrapper
level (identical numerics, no per-grid-step interpreter overhead);
``fuse_store`` forces the in-kernel path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped-VMEM ceiling for one call: the whole-image block is double-buffered
# and the largest resnet50 planes need more than Mosaic's 16 MiB default.
# v5e has 128 MiB of VMEM per core.
_VMEM_LIMIT = 100 * 2 ** 20


def _finish(y, bias, res, relu: bool, channel_axis: int):
    if bias is not None:
        shape = [1] * y.ndim
        shape[channel_axis] = bias.shape[0]
        y = y + bias.astype(y.dtype).reshape(shape)
    if res is not None:
        y = y + res.astype(y.dtype)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y


def _conv_kernel(*refs, stride: int, f: int, ow: int, has_bias: bool,
                 has_res: bool, relu: bool):
    it = iter(refs)
    x_ref = next(it)             # (1, s, s, Hs, Cp, Ws): one image's phases
    w_ref = next(it)             # (bk, f*f*Cp)
    b_ref = next(it) if has_bias else None
    r_ref = next(it) if has_res else None
    o_ref = next(it)             # (1, 1, bk, ow)
    i = pl.program_id(2)
    taps = []
    for a in range(f):
        for b in range(f):
            row = x_ref[0, a % stride, b % stride, i + a // stride]  # (Cp, Ws)
            taps.append(row[:, b // stride:b // stride + ow])
    pat = jnp.concatenate(taps, axis=0)                  # (f*f*Cp, ow)
    acc = jnp.dot(w_ref[...], pat, preferred_element_type=jnp.float32)
    if has_bias:
        acc = acc + b_ref[0].astype(jnp.float32)[:, None]
    if has_res:
        acc = acc + r_ref[0, 0].astype(jnp.float32)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    o_ref[0, 0] = acc.astype(o_ref.dtype)


def conv_im2col_batch(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1, *,
                      bk: int = 128, bias: jnp.ndarray | None = None,
                      residual: jnp.ndarray | None = None, relu: bool = False,
                      interpret: bool = False,
                      fuse_store: bool | None = None) -> jnp.ndarray:
    """x: (N, C, H, W); w: (K, C, f, f) -> (N, K, oh, ow), valid padding.
    Batch is the leading grid dimension: grid (N, K blocks, output rows).
    ``bias`` is (K,), ``residual`` is (N, K, oh, ow)."""
    K, _, f, _ = w.shape
    s = stride
    if f == 1 and s > 1:             # a strided 1x1 reads phase (0, 0) only
        x, s = x[..., ::s, ::s], 1
    N, C, H, W = x.shape
    oh = (H - f) // s + 1
    ow = (W - f) // s + 1
    fuse = (not interpret) if fuse_store is None else fuse_store
    Cp = -(-C // 8) * 8
    Hs, Ws = -(-H // s), -(-W // s)
    xp = jnp.pad(x, ((0, 0), (0, Cp - C), (0, Hs * s - H), (0, Ws * s - W)))
    xph = xp.reshape(N, Cp, Hs, s, Ws, s).transpose(0, 3, 5, 2, 1, 4)
    bk = min(bk, K)
    Kp = -(-K // bk) * bk
    # partial K tiles are undefined on TPU: zero rows are sliced away below
    wm = jnp.pad(w, ((0, Kp - K), (0, Cp - C), (0, 0), (0, 0)))
    wm = wm.transpose(0, 2, 3, 1).reshape(Kp, f * f * Cp)
    grid = (N, Kp // bk, oh)
    has_bias = fuse and bias is not None
    has_res = fuse and residual is not None

    ins = [xph, wm]
    in_specs = [pl.BlockSpec((1, s, s, Hs, Cp, Ws),
                             lambda n, kb, i: (n, 0, 0, 0, 0, 0)),
                pl.BlockSpec((bk, f * f * Cp), lambda n, kb, i: (kb, 0))]
    if has_bias:
        ins.append(jnp.pad(bias, (0, Kp - K))[None, :])
        in_specs.append(pl.BlockSpec((1, bk), lambda n, kb, i: (0, kb)))
    if has_res:
        r = residual.transpose(0, 2, 1, 3)           # (N, oh, K, ow)
        ins.append(jnp.pad(r, ((0, 0), (0, 0), (0, Kp - K), (0, 0))))
        in_specs.append(pl.BlockSpec((1, 1, bk, ow),
                                     lambda n, kb, i: (n, i, kb, 0)))
    out = pl.pallas_call(
        functools.partial(_conv_kernel, stride=s, f=f, ow=ow,
                          has_bias=has_bias, has_res=has_res,
                          relu=fuse and relu),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bk, ow), lambda n, kb, i: (n, i, kb, 0)),
        out_shape=jax.ShapeDtypeStruct((N, oh, Kp, ow), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*ins)
    out = out.transpose(0, 2, 1, 3)[:, :K]
    if not fuse:
        out = _finish(out, bias, residual, relu, channel_axis=1)
    return out


def conv_im2col(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1, *,
                bk: int = 128, bias: jnp.ndarray | None = None,
                residual: jnp.ndarray | None = None, relu: bool = False,
                interpret: bool = False,
                fuse_store: bool | None = None) -> jnp.ndarray:
    """x: (C, H, W); w: (K, C, f, f) -> (K, oh, ow), valid padding.
    ``bias`` is (K,), ``residual`` is (K, oh, ow). One image through
    ``conv_im2col_batch``."""
    return conv_im2col_batch(
        x[None], w, stride, bk=bk, bias=bias,
        residual=None if residual is None else residual[None], relu=relu,
        interpret=interpret, fuse_store=fuse_store)[0]

"""Tiled MXU matmul Pallas kernel with configurable block shapes.

The (bm, bk, bn) block configuration is the TPU analogue of the paper's
"primitive variants" (DESIGN.md §2.2): each config is a selectable
implementation whose cost the performance model predicts, and the autotune
pipeline PBQP-selects per matmul site. Blocks tile VMEM; the inner jnp.dot
maps onto the 128x128 MXU, so hardware-aligned configs keep bm/bk/bn at
multiples of 128.

Grid is (M/bm, N/bn, K/bk) with the K dimension innermost (sequential on
TPU), accumulating into an f32 VMEM scratch tile. ``matmul_batch`` runs one
such GEMM per image, w (M, K) @ x (B, K, T), with the batch as the
outermost grid axis and the weights shared: it reads and writes a conv's
(N, C, H*W) activations as they are, with no transpose or pad around it.

Epilogues (DESIGN.md §13): an optional bias (per output row), residual
(same shape as the output) and ReLU can be fused into the kernel's store
step — the output tile is finished in VMEM before the single HBM writeback,
so the unfused read-modify-write round trip over the activation never
happens. In interpret mode the epilogue is applied once at the wrapper
level instead (same jit, identical numerics): the interpreter executes the
kernel body per grid step, so per-tile epilogue ops would multiply
interpreter overhead while saving no memory traffic. ``fuse_store`` forces
the in-kernel path (tests exercise it under interpret).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _finish(acc, bias_blk, res_blk, relu: bool):
    """Shared epilogue: bias -> residual -> ReLU on an f32 (bm, bn) tile."""
    if bias_blk is not None:
        acc = acc + bias_blk.astype(jnp.float32)[:, None]
    if res_blk is not None:
        acc = acc + res_blk.astype(jnp.float32)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    return acc


def _matmul_kernel(*refs, n_k: int, has_bias: bool, has_res: bool, relu: bool):
    it = iter(refs)
    x_ref, y_ref = next(it), next(it)
    b_ref = next(it) if has_bias else None
    r_ref = next(it) if has_res else None
    o_ref, acc_ref = next(it), next(it)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], y_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _store():
        acc = _finish(acc_ref[...], b_ref[0] if has_bias else None,
                      r_ref[...] if has_res else None, relu)
        o_ref[...] = acc.astype(o_ref.dtype)


def _matmul_batch_kernel(*refs, n_k: int, k_rem: int, has_bias: bool,
                         has_res: bool, relu: bool):
    it = iter(refs)
    w_ref, x_ref = next(it), next(it)
    b_ref = next(it) if has_bias else None
    r_ref = next(it) if has_res else None
    o_ref, acc_ref = next(it), next(it)
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(w, x):
        acc_ref[...] += jnp.dot(w, x, preferred_element_type=jnp.float32)

    if k_rem:
        # the last K block overhangs K: its columns of w and rows of x past
        # the end are undefined (NaN in interpret mode), and garbage x 0 can
        # be NaN, so both operands are zeroed there
        @pl.when(kk < n_k - 1)
        def _full():
            accumulate(w_ref[...], x_ref[0])

        @pl.when(kk == n_k - 1)
        def _tail():
            w, x = w_ref[...], x_ref[0]
            w = jnp.where(lax.broadcasted_iota(jnp.int32, w.shape, 1) < k_rem,
                          w, jnp.zeros_like(w))
            x = jnp.where(lax.broadcasted_iota(jnp.int32, x.shape, 0) < k_rem,
                          x, jnp.zeros_like(x))
            accumulate(w, x)
    else:
        accumulate(w_ref[...], x_ref[0])

    @pl.when(kk == n_k - 1)
    def _store():
        acc = _finish(acc_ref[...], b_ref[0] if has_bias else None,
                      r_ref[0] if has_res else None, relu)
        o_ref[0] = acc.astype(o_ref.dtype)


def matmul_batch(w: jnp.ndarray, x: jnp.ndarray, *, bm: int = 128,
                 bk: int = 128, bn: int = 128, out_dtype=None,
                 bias: jnp.ndarray | None = None,
                 residual: jnp.ndarray | None = None, relu: bool = False,
                 interpret: bool = False,
                 fuse_store: bool | None = None) -> jnp.ndarray:
    """Shared-weight batched GEMM: w (M, K) @ x (B, K, T) -> (B, M, T), one
    GEMM per image. ``bias`` is (M,), ``residual`` is (B, M, T).

    The grid is (B, M/bm, T/bn, K/bk) with K innermost, so each output tile
    sums its K blocks in the same order as ``matmul``. The weight block's
    index ignores the image: one weight matrix serves every image, with no
    copy per image. Nothing is padded: a partial M or T edge tile reads undefined rows or columns that
    reach only output elements past the edge, which are never written back;
    the K tail, which every output element sums, is zeroed in the kernel."""
    m, k = w.shape
    B, k2, t = x.shape
    assert k == k2, (w.shape, x.shape)
    out_dtype = out_dtype or w.dtype
    fuse = (not interpret) if fuse_store is None else fuse_store
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, t)
    grid = (B, pl.cdiv(m, bm), pl.cdiv(t, bn), pl.cdiv(k, bk))
    has_bias = fuse and bias is not None
    has_res = fuse and residual is not None
    ins = [w, x]
    in_specs = [pl.BlockSpec((bm, bk), lambda b, i, j, kk: (i, kk)),
                pl.BlockSpec((1, bk, bn), lambda b, i, j, kk: (b, kk, j))]
    if has_bias:
        ins.append(bias[None, :])
        in_specs.append(pl.BlockSpec((1, bm), lambda b, i, j, kk: (0, i)))
    if has_res:
        ins.append(residual)
        in_specs.append(pl.BlockSpec((1, bm, bn), lambda b, i, j, kk: (b, i, j)))
    out = pl.pallas_call(
        functools.partial(_matmul_batch_kernel, n_k=grid[3], k_rem=k % bk,
                          has_bias=has_bias, has_res=has_res,
                          relu=fuse and relu),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j, kk: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, m, t), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*ins)
    if not fuse:
        out = _finish(out, bias, residual, relu).astype(out_dtype)
    return out


def matmul(x: jnp.ndarray, y: jnp.ndarray, *, bm: int = 128, bk: int = 128,
           bn: int = 128, out_dtype=None, bias: jnp.ndarray | None = None,
           residual: jnp.ndarray | None = None, relu: bool = False,
           interpret: bool = False, fuse_store: bool | None = None,
           roles: tuple[str, str] = ("lhs", "rhs")) -> jnp.ndarray:
    """x: (M, K) @ y: (K, N) -> (M, N). Shapes need not divide blocks
    (Pallas masks edge tiles; zero-fill is exact for the K reduction).
    ``bias`` is (M,), ``residual`` is (M, N).

    ``roles`` names the ``jax.named_scope`` of each side's preparation, as
    the caller knows it: the first covers ``x`` and ``bias`` (the M side),
    the second ``y``, ``residual`` and the output slice (the N side)."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    out_dtype = out_dtype or x.dtype
    fuse = (not interpret) if fuse_store is None else fuse_store
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    # pad to block multiples. A partial edge tile reads undefined elements
    # on TPU (NaN in interpret mode); on M and N they reach only output
    # elements past the edge, but every output element sums the K tail, so
    # that is where it matters. Zero padding makes the K reduction exact, and
    # the M/N padding is sliced away. ``matmul_batch`` pads nothing: it masks
    # the K tail in the kernel instead.
    mp, kp, np_ = -(-m // bm) * bm, -(-k // bk) * bk, -(-n // bn) * bn
    m_side, n_side = roles
    if (mp, kp) != (m, k):
        with jax.named_scope(m_side):
            x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        with jax.named_scope(n_side):
            y = jnp.pad(y, ((0, kp - k), (0, np_ - n)))
    grid = (mp // bm, np_ // bn, kp // bk)
    has_bias = fuse and bias is not None
    has_res = fuse and residual is not None
    ins = [x, y]
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))]
    if has_bias:
        with jax.named_scope(m_side):
            ins.append(jnp.pad(bias, (0, mp - m))[None, :] if mp != m
                       else bias[None, :])
        in_specs.append(pl.BlockSpec((1, bm), lambda i, j, kk: (0, i)))
    if has_res:
        r = residual
        if (mp, np_) != (m, n):
            with jax.named_scope(n_side):
                r = jnp.pad(r, ((0, mp - m), (0, np_ - n)))
        ins.append(r)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=grid[2], has_bias=has_bias,
                          has_res=has_res, relu=fuse and relu),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*ins)
    with jax.named_scope(n_side):
        out = out[:m, :n]
    if not fuse:
        out = _finish(out, bias, residual, relu).astype(out_dtype)
    return out

"""Tiled MXU matmul Pallas kernel with configurable block shapes.

The (bm, bk, bn) block configuration is the TPU analogue of the paper's
"primitive variants" (DESIGN.md §2.2): each config is a selectable
implementation whose cost the performance model predicts, and the autotune
pipeline PBQP-selects per matmul site. Blocks tile VMEM; the inner jnp.dot
maps onto the 128x128 MXU, so hardware-aligned configs keep bm/bk/bn at
multiples of 128.

Grid is (M/bm, N/bn, K/bk) with the K dimension innermost (sequential on
TPU), accumulating into an f32 VMEM scratch tile.

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


def _matmul_batch_kernel(*refs, n_k: int, has_bias: bool, has_res: bool,
                         relu: bool):
    it = iter(refs)
    x_ref, y_ref = next(it), next(it)
    b_ref = next(it) if has_bias else None
    r_ref = next(it) if has_res else None
    o_ref, acc_ref = next(it), next(it)

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[0], y_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == n_k - 1)
    def _store():
        acc = _finish(acc_ref[...], b_ref[0] if has_bias else None,
                      r_ref[0] if has_res else None, relu)
        o_ref[0] = acc.astype(o_ref.dtype)


def matmul_batch(x: jnp.ndarray, y: jnp.ndarray, *, bm: int = 128,
                 bk: int = 128, bn: int = 128, out_dtype=None,
                 bias: jnp.ndarray | None = None,
                 residual: jnp.ndarray | None = None, relu: bool = False,
                 interpret: bool = False,
                 fuse_store: bool | None = None) -> jnp.ndarray:
    """Batched GEMM x: (B, M, K) @ y: (B, K, N) -> (B, M, N) with the batch
    as an explicit leading grid dimension (one (M, N, K) tile walk per image;
    the plan executor's whole-batch GEMM shape). Same edge-tile padding rules
    as ``matmul``. ``bias`` is (M,), ``residual`` is (B, M, N)."""
    B, m, k = x.shape
    B2, k2, n = y.shape
    assert (B, k) == (B2, k2), (x.shape, y.shape)
    out_dtype = out_dtype or x.dtype
    fuse = (not interpret) if fuse_store is None else fuse_store
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    mp, kp, np_ = -(-m // bm) * bm, -(-k // bk) * bk, -(-n // bn) * bn
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, 0), (0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        y = jnp.pad(y, ((0, 0), (0, kp - k), (0, np_ - n)))
    grid = (B, mp // bm, np_ // bn, kp // bk)
    has_bias = fuse and bias is not None
    has_res = fuse and residual is not None
    ins = [x, y]
    in_specs = [pl.BlockSpec((1, bm, bk), lambda b, i, j, kk: (b, i, kk)),
                pl.BlockSpec((1, bk, bn), lambda b, i, j, kk: (b, kk, j))]
    if has_bias:
        ins.append(jnp.pad(bias, (0, mp - m))[None, :] if mp != m
                   else bias[None, :])
        in_specs.append(pl.BlockSpec((1, bm), lambda b, i, j, kk: (0, i)))
    if has_res:
        r = residual
        if (mp, np_) != (m, n):
            r = jnp.pad(r, ((0, 0), (0, mp - m), (0, np_ - n)))
        ins.append(r)
        in_specs.append(pl.BlockSpec((1, bm, bn), lambda b, i, j, kk: (b, i, j)))
    out = pl.pallas_call(
        functools.partial(_matmul_batch_kernel, n_k=grid[3], has_bias=has_bias,
                          has_res=has_res, relu=fuse and relu),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j, kk: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*ins)
    out = out[:, :m, :n]
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
    # pad to block multiples: partial edge tiles are undefined on TPU (and
    # NaN-poisoned in interpret mode); zero padding is exact for the K
    # reduction and sliced away on M/N.
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

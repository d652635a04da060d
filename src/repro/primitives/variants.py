"""Variant-aware conv execution: route a (base primitive, tile variant)
column through the matching Pallas kernel entry point (DESIGN.md §13).

Until PR 9 a tile column like ``im2col-copy-ab-ki@mm-256x128x128`` priced
differently in the perf model but executed through the base XLA impl — the
PBQP-selected tile never changed the emitted kernel. ``conv_variant_call``
closes that gap:

* ``mm-*``   — the base's GEMM stage runs through ``kernels/matmul`` with
  that (bm, bk, bn) block config. For im2col bases the patch matrix is
  lowered at the jnp level and the batch is folded into the GEMM N axis
  (one kernel launch, weights shared); for 1x1 the pointwise GEMM maps
  directly; for 2-D Winograd bases the blocks map onto the point-GEMM's
  (K, C, T) tiling.
* ``conv-bk*`` — the fused im2col+GEMM kernel (patches built in VMEM) with
  that K-block, batch as a leading grid dimension.
* ``wino-*`` — the Winograd point-GEMM with that (K, T) tiling.

Compatibility is enforced by ``conv.variant_compatible`` (consulted by
``is_runnable``/``tile_columns``), so selection can never produce a pair
this module rejects. All paths accept the fused elementwise epilogue
(bias -> residual -> ReLU); semantics are identical to the base impl plus
the epilogue ops — only the schedule differs (DESIGN.md §13.1).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.primitives.conv import (Primitive, _patches_copy_chw,
                                   _patches_scan_chw, _w_mat,
                                   variant_compatible)


def _gemm_chw(wm: jnp.ndarray, x2: jnp.ndarray, variant: str, bias, res,
              relu: bool, N: int, K: int, oh: int, ow: int) -> jnp.ndarray:
    """Shared mm-* tail: wm (K, R) @ x2 (R, N*oh*ow) through the tiled
    Pallas matmul, epilogue fused, result reshaped back to (N, K, oh, ow).
    The weights are the matmul's left operand (``wpack``), the activations
    its right (``pack``)."""
    from repro.kernels.matmul.ops import matmul_op
    res2 = None
    if res is not None:
        with jax.named_scope("pack"):
            res2 = res.transpose(1, 0, 2, 3).reshape(K, N * oh * ow)
    y2 = matmul_op(wm, x2, variant=variant, bias=bias, residual=res2,
                   relu=relu, roles=("wpack", "pack"))        # (K, N*oh*ow)
    with jax.named_scope("pack"):
        return y2.reshape(K, N, oh, ow).transpose(1, 0, 2, 3)


def conv_variant_call(prim: Primitive, variant: str, x: jnp.ndarray,
                      w: jnp.ndarray, stride: int, *,
                      bias: Optional[jnp.ndarray] = None,
                      residual: Optional[jnp.ndarray] = None,
                      relu: bool = False) -> jnp.ndarray:
    """Run chw conv ``prim`` under Pallas tile ``variant``.

    ``x`` is (C, H, W) or (N, C, H, W); ``w`` is (K, C, f, f). ``bias`` is
    (K,); ``residual`` must already be cropped to the conv's output shape.
    Numerics match ``prim.impl(x, w, stride)`` plus the epilogue ops.
    """
    if not variant_compatible(prim.name, variant):
        raise ValueError(f"variant {variant!r} cannot lower through "
                         f"{prim.name!r}")
    squeeze = x.ndim == 3
    if squeeze:
        x = x[None]
        if residual is not None:
            residual = residual[None]
    N, C, H, W = x.shape
    K, _, f, _ = w.shape

    if variant.startswith("conv-bk"):
        from repro.kernels.im2col_gemm.ops import conv_im2col_batch_op
        y = conv_im2col_batch_op(x, w, stride, variant=variant, bias=bias,
                                 residual=residual, relu=relu)
    elif variant.startswith("wino-"):
        from repro.kernels.winograd.ops import VARIANTS, winograd_conv_batch
        bk, bt = VARIANTS[variant]
        y = winograd_conv_batch(x, w, m=int(prim.traits["tile_m"]), bk=bk,
                                bt=bt, bias=bias, residual=residual,
                                relu=relu)
    elif variant.startswith("mm-"):
        if prim.family == "wino3":
            from repro.kernels.matmul.ops import VARIANTS
            from repro.kernels.winograd.ops import winograd_conv_batch
            bm, bk, bn = VARIANTS[variant]
            y = winograd_conv_batch(x, w, m=int(prim.traits["tile_m"]),
                                    bk=bm, bc=bk, bt=bn, bias=bias,
                                    residual=residual, relu=relu)
        elif prim.family == "c1x1":
            with jax.named_scope("pack"):
                xs = x[..., ::stride, ::stride]
                oh, ow = xs.shape[-2:]
                x2 = xs.reshape(N, C, oh * ow).transpose(1, 0, 2).reshape(
                    C, N * oh * ow)
            with jax.named_scope("wpack"):
                wm = w[:, :, 0, 0]
            y = _gemm_chw(wm, x2, variant, bias, residual, relu, N, K, oh, ow)
        else:                                     # im2 family, chw/ki
            patches = (_patches_scan_chw if prim.traits.get("trav") == "scan"
                       else _patches_copy_chw)
            oh = (H - f) // stride + 1
            ow = (W - f) // stride + 1
            with jax.named_scope("pack"):
                pat = patches(x, f, stride)       # (N, C*f*f, oh*ow)
                x2 = pat.transpose(1, 0, 2).reshape(C * f * f, N * oh * ow)
            with jax.named_scope("wpack"):
                wm = _w_mat(w)
            y = _gemm_chw(wm, x2, variant, bias, residual, relu, N, K, oh, ow)
    else:
        raise ValueError(f"unknown tile variant {variant!r}")
    return y[0] if squeeze else y

"""Variant-aware conv execution: route a (base primitive, tile variant)
column through the matching Pallas kernel entry point (DESIGN.md §13).

Until PR 9 a tile column like ``im2col-copy-ab-ki@mm-256x128x128`` priced
differently in the perf model but executed through the base XLA impl — the
PBQP-selected tile never changed the emitted kernel. ``conv_variant_call``
closes that gap:

* ``mm-*``   — the base's GEMM stage runs through ``kernels/matmul`` with
  that (bm, bk, bn) block config. For im2col bases the patch matrix is
  lowered at the jnp level, (N, C*f*f, oh*ow); for 1x1 the input is the
  pointwise GEMM's operand as it stands, (N, C, oh*ow). Where a batch of
  several images has a lane tile of pixels per image (oh*ow >= 128), the
  batch is a grid axis with the weights shared (``matmul_batch``): the
  kernel reads and writes the plan's (N, C, H, W) order and no activation
  is transposed, padded or sliced. Elsewhere the batch is folded into the
  GEMM N axis, (C*f*f, N*oh*ow) (``gemm_path``): a few-pixel image would
  leave most of each tile empty, and for one image the fold's transposes
  are free. For 2-D Winograd bases the blocks map onto the
  point-GEMM's (K, C, T) tiling.
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

from repro.primitives import layouts as L
from repro.primitives.conv import (Primitive, _patches_copy_chw,
                                   _patches_scan_chw, _w_mat,
                                   variant_compatible)


# Per-image GEMM needs at least one lane tile of pixels per image; below
# that (resnet50's last stage, 1-32 pixels) the batch is folded into N.
# One image folds too: (1, C, T) -> (C, T) moves no data, and on a v5e the
# 3-D operands of one image cost resnet50 0.30 ms more device time per
# dispatch (3.829 against 3.525 ms) in relayouts of the degenerate batch.
PER_IMAGE_MIN_PIXELS = 128


def pad_input(x: jnp.ndarray, pad: int, layout: str = "chw") -> jnp.ndarray:
    """``x`` zero-padded by ``pad`` on every side of its spatial axes, under
    the scope ``pad`` (callers hold it inside their ``pack``); ``x`` itself
    at ``pad`` 0, so an unpadded step emits nothing."""
    if not pad:
        return x
    lead = x.ndim - 3
    widths = [(0, 0)] * x.ndim
    for a in L.SPATIAL_AXES[layout]:
        widths[lead + a] = (pad, pad)
    with jax.named_scope("pad"):
        return jnp.pad(x, widths)


def gemm_path(prim: Primitive, variant: Optional[str], n: int,
              pixels: int) -> Optional[str]:
    """How ``conv_variant_call`` runs the GEMM of an ``mm-*`` column on a
    1x1 or im2col base over ``n`` images of ``pixels`` output pixels each:
    ``"per_image"`` (batch on a grid axis, weights shared) or ``"folded"``
    (batch folded into the GEMM N axis). None for every other column."""
    if (variant is None or not variant.startswith("mm-")
            or prim.family not in ("c1x1", "im2")):
        return None
    return ("per_image" if n > 1 and pixels >= PER_IMAGE_MIN_PIXELS
            else "folded")


def _gemm_chw(prim: Primitive, wm: jnp.ndarray, x3: jnp.ndarray,
              variant: str, bias, res, relu: bool, oh: int,
              ow: int) -> jnp.ndarray:
    """Shared mm-* tail: wm (K, R) @ x3 (N, R, oh*ow) through the tiled
    Pallas matmul, per image or with the batch folded into N as
    ``gemm_path`` says, epilogue fused, result reshaped back to (N, K, oh,
    ow). The weights are the matmul's left operand (``wpack``), the
    activations its right (``pack``)."""
    from repro.kernels.matmul.ops import matmul_op
    N, R, _ = x3.shape
    K = wm.shape[0]
    if gemm_path(prim, variant, N, oh * ow) == "per_image":
        res3 = None
        if res is not None:
            with jax.named_scope("pack"):
                res3 = res.reshape(N, K, oh * ow)
        y3 = matmul_op(wm, x3, variant=variant, bias=bias, residual=res3,
                       relu=relu, roles=("wpack", "pack"))    # (N, K, oh*ow)
        with jax.named_scope("pack"):
            return y3.reshape(N, K, oh, ow)
    res2 = None
    with jax.named_scope("pack"):
        x2 = x3.transpose(1, 0, 2).reshape(R, N * oh * ow)
        if res is not None:
            res2 = res.transpose(1, 0, 2, 3).reshape(K, N * oh * ow)
    y2 = matmul_op(wm, x2, variant=variant, bias=bias, residual=res2,
                   relu=relu, roles=("wpack", "pack"))        # (K, N*oh*ow)
    with jax.named_scope("pack"):
        return y2.reshape(K, N, oh, ow).transpose(1, 0, 2, 3)


def conv_variant_call(prim: Primitive, variant: str, x: jnp.ndarray,
                      w: jnp.ndarray, stride: int, *,
                      bias: Optional[jnp.ndarray] = None,
                      residual: Optional[jnp.ndarray] = None,
                      relu: bool = False, pad: int = 0) -> jnp.ndarray:
    """Run chw conv ``prim`` under Pallas tile ``variant``.

    ``x`` is (C, H, W) or (N, C, H, W); ``w`` is (K, C, f, f). ``bias`` is
    (K,); ``residual`` must already be cropped to the conv's output shape.
    Numerics match ``prim.impl(x_padded, w, stride)`` plus the epilogue
    ops, where ``x_padded`` is ``x`` zero-padded by ``pad`` on every side:
    the Winograd and fused im2col kernels fold that padding into the pad
    they already make (tiles, stride phases, channels), the ``mm-*`` 1x1
    and im2col lowerings pad first (``pad_input``).
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
    f = w.shape[-1]

    if variant.startswith("conv-bk"):
        from repro.kernels.im2col_gemm.ops import conv_im2col_batch_op
        y = conv_im2col_batch_op(x, w, stride, variant=variant, bias=bias,
                                 residual=residual, relu=relu, pad=pad)
    elif variant.startswith("wino-"):
        from repro.kernels.winograd.ops import VARIANTS, winograd_conv_batch
        bk, bt = VARIANTS[variant]
        y = winograd_conv_batch(x, w, m=int(prim.traits["tile_m"]), bk=bk,
                                bt=bt, bias=bias, residual=residual,
                                relu=relu, pad=pad)
    elif variant.startswith("mm-"):
        if prim.family == "wino3":
            from repro.kernels.matmul.ops import VARIANTS
            from repro.kernels.winograd.ops import winograd_conv_batch
            bm, bk, bn = VARIANTS[variant]
            y = winograd_conv_batch(x, w, m=int(prim.traits["tile_m"]),
                                    bk=bm, bc=bk, bt=bn, bias=bias,
                                    residual=residual, relu=relu, pad=pad)
        elif prim.family == "c1x1":
            with jax.named_scope("pack"):
                x = pad_input(x, pad)
                xs = x[..., ::stride, ::stride]
                oh, ow = xs.shape[-2:]
                x3 = xs.reshape(N, C, oh * ow)
            with jax.named_scope("wpack"):
                wm = w[:, :, 0, 0]
            y = _gemm_chw(prim, wm, x3, variant, bias, residual, relu, oh, ow)
        else:                                     # im2 family, chw/ki
            patches = (_patches_scan_chw if prim.traits.get("trav") == "scan"
                       else _patches_copy_chw)
            oh = (H + 2 * pad - f) // stride + 1
            ow = (W + 2 * pad - f) // stride + 1
            with jax.named_scope("pack"):
                x3 = patches(pad_input(x, pad), f, stride)  # (N, C*f*f, oh*ow)
            with jax.named_scope("wpack"):
                wm = _w_mat(w)
            y = _gemm_chw(prim, wm, x3, variant, bias, residual, relu, oh, ow)
    else:
        raise ValueError(f"unknown tile variant {variant!r}")
    return y[0] if squeeze else y

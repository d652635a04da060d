"""Jitted wrapper for the fused im2col+GEMM conv kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.common import default_interpret
from repro.kernels.im2col_gemm.im2col_gemm import conv_im2col, conv_im2col_batch

VARIANTS = {"conv-bk64": 64, "conv-bk128": 128, "conv-bk256": 256}


@partial(jax.jit, static_argnames=("stride", "variant", "interpret", "relu",
                                   "fuse_store"))
def conv_im2col_op(x, w, stride: int = 1, variant: str = "conv-bk128",
                   interpret: bool | None = None, bias=None, residual=None,
                   relu: bool = False, fuse_store: bool | None = None):
    interp = default_interpret() if interpret is None else interpret
    return conv_im2col(x, w, stride, bk=VARIANTS[variant], bias=bias,
                       residual=residual, relu=relu, interpret=interp,
                       fuse_store=fuse_store)


@partial(jax.jit, static_argnames=("stride", "variant", "interpret", "relu",
                                   "fuse_store", "pad"))
def conv_im2col_batch_op(x, w, stride: int = 1, variant: str = "conv-bk128",
                         interpret: bool | None = None, bias=None,
                         residual=None, relu: bool = False,
                         fuse_store: bool | None = None, pad: int = 0):
    """(N, C, H, W) batch through the fused conv — batch grid dimension;
    ``x`` zero-padded by ``pad`` on every side."""
    interp = default_interpret() if interpret is None else interpret
    return conv_im2col_batch(x, w, stride, bk=VARIANTS[variant], bias=bias,
                             residual=residual, relu=relu, interpret=interp,
                             fuse_store=fuse_store, pad=pad)

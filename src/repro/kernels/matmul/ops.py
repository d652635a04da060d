"""Jitted wrapper + variant registry for the tiled matmul kernel.

``VARIANTS`` is the kernel-config pool the autotune feature (repro.core.
autotune) selects from — the TPU analogue of the paper's primitive table.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax

from repro.kernels.common import default_interpret
from repro.kernels.matmul.matmul import matmul, matmul_batch

# (bm, bk, bn) pool: MXU-aligned tilings trading VMEM footprint for reuse.
VARIANTS: Dict[str, Tuple[int, int, int]] = {
    "mm-128x128x128": (128, 128, 128),
    "mm-256x128x128": (256, 128, 128),
    "mm-128x128x256": (128, 128, 256),
    "mm-256x128x256": (256, 128, 256),
    "mm-512x128x128": (512, 128, 128),
    "mm-128x256x128": (128, 256, 128),
    "mm-256x256x256": (256, 256, 256),
    "mm-512x256x256": (512, 256, 256),
}


@partial(jax.jit, static_argnames=("variant", "interpret", "relu", "fuse_store",
                                   "roles"))
def matmul_op(x, y, variant: str = "mm-128x128x128", interpret: bool | None = None,
              bias=None, residual=None, relu: bool = False,
              fuse_store: bool | None = None,
              roles: tuple[str, str] = ("lhs", "rhs")):
    """``matmul`` under ``variant``'s blocks; ``roles`` as ``matmul``'s.
    A 3-D ``y`` (B, K, T) runs ``matmul_batch``: one GEMM per image with
    ``x`` (M, K) shared, result (B, M, T)."""
    bm, bk, bn = VARIANTS[variant]
    interp = default_interpret() if interpret is None else interpret
    if y.ndim == 3:
        return matmul_batch(x, y, bm=bm, bk=bk, bn=bn, bias=bias,
                            residual=residual, relu=relu, interpret=interp,
                            fuse_store=fuse_store)
    return matmul(x, y, bm=bm, bk=bk, bn=bn, bias=bias, residual=residual,
                  relu=relu, interpret=interp, fuse_store=fuse_store,
                  roles=roles)


def vmem_bytes(variant: str, dtype_bytes: int = 2) -> int:
    """Working-set estimate per grid step — used as an autotune feature."""
    bm, bk, bn = VARIANTS[variant]
    return dtype_bytes * (bm * bk + bk * bn) + 4 * bm * bn

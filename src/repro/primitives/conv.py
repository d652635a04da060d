"""Convolution primitive families (paper §3.1, appendix Table 6).

Every primitive computes the same valid, un-padded 2-D cross-correlation

    y[k, i, j] = sum_{c, a, b} x[c, i*s + a, j*s + b] * w[k, c, a, b]

but differs in *how*: data restructuring (im2col/im2row lowering, MEC partial
lowering, kn2 shift-accumulate, Winograd transform), GEMM orientation
(`ab`/`atb`/... transpose variants), traversal (`copy` = slice-stacked
lowering, `scan` = gather-indexed lowering) and input/output data layout
(chw / hcw / hwc). Implementations take the image in the primitive's
``in_layout`` and produce its ``out_layout``; weights are always (k, c, f, f).

A zero-padded layer (``ConvLayer.p``) is this convolution of its padded
input: the plan and the interpreted executor pad before the impl
(``variants.pad_input``), and the Winograd and fused im2col kernels fold
the padding into their own pads; it is priced as the valid convolution of
the padded size (``ConvLayer.config``, DESIGN.md §10).

17 primitives are runnable JAX implementations (validated against
``reference_conv`` = ``lax.conv_general_dilated``); the remaining entries of
the paper's Table 6 (SIMD-width `-vec-N` and residual transpose variants —
CPU-register-level distinctions that JAX/XLA does not expose) exist as
*simulated-only* registry entries used by the profiler simulators
(DESIGN.md §2.3).

Every runnable implementation is rank-polymorphic over leading batch axes:
the layout describes the trailing three axes, so a (n, c, im, im) batch goes
through the same code path with the GEMM stages broadcasting over ``n`` —
the batched entry point the plan compiler (DESIGN.md §6) lowers to. Use
``batch_impl``/``run_primitive_batch`` for the batched API (vmap fallback
for any future impl whose traits set ``batch=False``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
from functools import lru_cache, partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.primitives import layouts as L


# ---------------------------------------------------------------------------
# Reference oracle
# ---------------------------------------------------------------------------

def reference_conv(x_chw: jnp.ndarray, w: jnp.ndarray, stride: int) -> jnp.ndarray:
    """Oracle: XLA's native convolution, NCHW single image."""
    y = jax.lax.conv_general_dilated(
        x_chw[None], w, window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return y[0]


def out_size(im: int, f: int, s: int) -> int:
    return (im - f) // s + 1


# ---------------------------------------------------------------------------
# Lowerings
#
# All lowerings operate on the trailing image axes; any leading axes are
# batch and broadcast straight through the GEMM stages.
# ---------------------------------------------------------------------------

def _t(x: jnp.ndarray, perm: Tuple[int, ...]) -> jnp.ndarray:
    """Transpose the trailing ``len(perm)`` axes, leading (batch) untouched."""
    lead = x.ndim - len(perm)
    return jnp.transpose(x, tuple(range(lead)) + tuple(lead + p for p in perm))


def _patches_copy_chw(x: jnp.ndarray, f: int, s: int) -> jnp.ndarray:
    """Slice-stacked ("copy") lowering: (..., c*f*f, oh*ow), (c, a, b) order."""
    c, h, w = x.shape[-3:]
    oh, ow = out_size(h, f, s), out_size(w, f, s)
    cols = []
    for a in range(f):
        for b in range(f):
            cols.append(x[..., a:a + (oh - 1) * s + 1:s, b:b + (ow - 1) * s + 1:s])
    pat = jnp.stack(cols, axis=-3)           # (..., c, f*f, oh, ow)
    return pat.reshape(*x.shape[:-3], c * f * f, oh * ow)


def _patches_scan_chw(x: jnp.ndarray, f: int, s: int) -> jnp.ndarray:
    """Gather-indexed ("scan") lowering — same result, different traversal."""
    c, h, w = x.shape[-3:]
    oh, ow = out_size(h, f, s), out_size(w, f, s)
    ih = (jnp.arange(oh) * s)[:, None] + jnp.arange(f)[None, :]   # (oh, f)
    iw = (jnp.arange(ow) * s)[:, None] + jnp.arange(f)[None, :]   # (ow, f)
    # gather -> (..., c, oh, f, ow, f)
    pat = jnp.take(jnp.take(x, ih, axis=-2), iw, axis=-1)
    pat = _t(pat, (0, 2, 4, 1, 3))           # (..., c, f, f, oh, ow)
    return pat.reshape(*x.shape[:-3], c * f * f, oh * ow)


def _w_mat(w: jnp.ndarray) -> jnp.ndarray:
    """(k, c*f*f) with (c, a, b) ordering — matches chw patch lowering."""
    k = w.shape[0]
    return w.reshape(k, -1)


def _w_mat_rows(w: jnp.ndarray) -> jnp.ndarray:
    """(k, f*f*c) with (a, b, c) ordering — matches hwc row lowering."""
    k = w.shape[0]
    return jnp.transpose(w, (0, 2, 3, 1)).reshape(k, -1)


def _patches_rows_hwc(x: jnp.ndarray, f: int, s: int, scan: bool) -> jnp.ndarray:
    """Row lowering from an hwc image: (..., oh*ow, f*f*c), (a, b, c) order."""
    h, w, c = x.shape[-3:]
    oh, ow = out_size(h, f, s), out_size(w, f, s)
    if scan:
        ih = (jnp.arange(oh) * s)[:, None] + jnp.arange(f)[None, :]
        iw = (jnp.arange(ow) * s)[:, None] + jnp.arange(f)[None, :]
        # gather -> (..., oh, f, ow, f, c)
        pat = jnp.take(jnp.take(x, ih, axis=-3), iw, axis=-2)
        pat = _t(pat, (0, 2, 1, 3, 4))              # (..., oh, ow, f, f, c)
    else:
        rows = []
        for a in range(f):
            for b in range(f):
                rows.append(x[..., a:a + (oh - 1) * s + 1:s, b:b + (ow - 1) * s + 1:s, :])
        pat = jnp.stack(rows, axis=-2)              # (..., oh, ow, f*f, c)
    return pat.reshape(*x.shape[:-3], oh * ow, f * f * c)


# ---------------------------------------------------------------------------
# im2col / im2row family
# ---------------------------------------------------------------------------

def im2col(x: jnp.ndarray, w: jnp.ndarray, s: int, *, scan: bool, out_ik: bool) -> jnp.ndarray:
    c, h, wd = x.shape[-3:]
    f = w.shape[2]
    oh, ow = out_size(h, f, s), out_size(wd, f, s)
    pat = (_patches_scan_chw if scan else _patches_copy_chw)(x, f, s)
    wm = _w_mat(w)
    lead = x.shape[:-3]
    if out_ik:
        y = jnp.swapaxes(pat, -1, -2) @ wm.T       # (..., P, k)  "atb-ik"
        return y.reshape(*lead, oh, ow, w.shape[0])        # hwc
    y = wm @ pat                                   # (..., k, P)  "ab-ki"
    return y.reshape(*lead, w.shape[0], oh, ow)            # chw


def im2row(x: jnp.ndarray, w: jnp.ndarray, s: int, *, scan: bool, out_ik: bool) -> jnp.ndarray:
    h, wd, c = x.shape[-3:]
    f = w.shape[2]
    oh, ow = out_size(h, f, s), out_size(wd, f, s)
    pat = _patches_rows_hwc(x, f, s, scan)
    wm = _w_mat_rows(w)
    lead = x.shape[:-3]
    if out_ik:
        y = pat @ wm.T                             # (..., P, k)
        return y.reshape(*lead, oh, ow, w.shape[0])        # hwc
    y = wm @ jnp.swapaxes(pat, -1, -2)             # (..., k, P)
    return y.reshape(*lead, w.shape[0], oh, ow)            # chw


# ---------------------------------------------------------------------------
# kn2 family (sum of f*f pointwise GEMMs, shift-accumulated; stride 1)
# ---------------------------------------------------------------------------

def kn2row(x: jnp.ndarray, w: jnp.ndarray, s: int, *, stacked: bool = False) -> jnp.ndarray:
    """chw -> chw. One (k,c)@(c,h*w) GEMM per kernel offset on the *full*
    image, then shifted accumulation of the valid region."""
    c, h, wd = x.shape[-3:]
    k, _, f, _ = w.shape
    oh, ow = out_size(h, f, s), out_size(wd, f, s)
    lead = x.shape[:-3]
    xf = x.reshape(*lead, c, h * wd)
    if stacked:  # "-as" variant: all offsets at once, one reduction
        g = jnp.transpose(w, (2, 3, 0, 1)).reshape(f * f * k, c)
        full = (g @ xf).reshape(*lead, f, f, k, h, wd)
        parts = [full[..., a, b, :, a:a + oh:1, b:b + ow:1]
                 for a in range(f) for b in range(f)]
        return jnp.sum(jnp.stack(parts), axis=0)
    acc = jnp.zeros((*lead, k, oh, ow), x.dtype)
    for a in range(f):
        for b in range(f):
            full = (w[:, :, a, b] @ xf).reshape(*lead, k, h, wd)
            acc = acc + full[..., a:a + oh, b:b + ow]
    return acc


def kn2col(x: jnp.ndarray, w: jnp.ndarray, s: int) -> jnp.ndarray:
    """hwc -> hwc. Image-major GEMM per offset."""
    h, wd, c = x.shape[-3:]
    k, _, f, _ = w.shape
    oh, ow = out_size(h, f, s), out_size(wd, f, s)
    lead = x.shape[:-3]
    xf = x.reshape(*lead, h * wd, c)
    acc = jnp.zeros((*lead, oh, ow, k), x.dtype)
    for a in range(f):
        for b in range(f):
            full = (xf @ w[:, :, a, b].T).reshape(*lead, h, wd, k)
            acc = acc + full[..., a:a + oh, b:b + ow, :]
    return acc


# ---------------------------------------------------------------------------
# Winograd family (stride 1)
# ---------------------------------------------------------------------------

# F(2x2, 3x3)
_BT_4 = np.array([[1, 0, -1, 0],
                  [0, 1, 1, 0],
                  [0, -1, 1, 0],
                  [0, 1, 0, -1]], np.float64)
_G_23 = np.array([[1, 0, 0],
                  [0.5, 0.5, 0.5],
                  [0.5, -0.5, 0.5],
                  [0, 0, 1]], np.float64)
_AT_2_3 = np.array([[1, 1, 1, 0],
                    [0, 1, -1, -1]], np.float64)

# n=6 point set {0, 1, -1, 2, -2, inf}
_BT_6 = np.array([[4, 0, -5, 0, 1, 0],
                  [0, -4, -4, 1, 1, 0],
                  [0, 4, -4, -1, 1, 0],
                  [0, -2, -1, 2, 1, 0],
                  [0, 2, -1, -2, 1, 0],
                  [0, 4, 0, -5, 0, 1]], np.float64)
_AT_4_3 = np.array([[1, 1, 1, 1, 1, 0],
                    [0, 1, -1, 2, -2, 0],
                    [0, 1, 1, 4, 4, 0],
                    [0, 1, -1, 8, -8, 1]], np.float64)
_AT_2_5 = np.array([[1, 1, 1, 1, 1, 0],
                    [0, 1, -1, 2, -2, 1]], np.float64)


def _derive_G(AT: np.ndarray, BT: np.ndarray, m: int, r: int) -> np.ndarray:
    """Solve for G from the Winograd identity AT @ diag(G g) @ BT == S(g)
    for kernel basis vectors — numerically robust, avoids transcription bugs
    in hand-copied G matrices. Residual is asserted tiny."""
    n = m + r - 1
    # column k of the linear map: vec(outer(AT[:, k], BT[k, :]))
    M = np.stack([np.outer(AT[:, k], BT[k, :]).ravel() for k in range(n)], axis=1)
    G = np.zeros((n, r))
    for i in range(r):
        S = np.zeros((m, n))
        for t in range(m):
            S[t, t + i] = 1.0
        sol, res, *_ = np.linalg.lstsq(M, S.ravel(), rcond=None)
        if not np.allclose(M @ sol, S.ravel(), atol=1e-9):
            raise RuntimeError("winograd G derivation failed")
        G[:, i] = sol
    return G


_G_43 = _derive_G(_AT_4_3, _BT_6, 4, 3)
_G_25 = _derive_G(_AT_2_5, _BT_6, 2, 5)

_WINO_SETS = {
    (2, 3): (_AT_2_3, _G_23, _BT_4),
    (4, 3): (_AT_4_3, _G_43, _BT_6),
    (2, 5): (_AT_2_5, _G_25, _BT_6),
}


def winograd2d(x: jnp.ndarray, w: jnp.ndarray, s: int, *, m: int, r: int) -> jnp.ndarray:
    """chw -> chw, F(mxm, rxr), stride 1."""
    assert s == 1
    AT, G, BT = (jnp.asarray(a, x.dtype) for a in _WINO_SETS[(m, r)])
    c, h, wd = x.shape[-3:]
    k, _, f, _ = w.shape
    n = m + r - 1
    oh, ow = h - r + 1, wd - r + 1
    th, tw = -(-oh // m), -(-ow // m)
    ph, pw = (th - 1) * m + n, (tw - 1) * m + n
    lead = x.shape[:-3]
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, ph - h), (0, pw - wd)])
    # overlapping n x n tiles at stride m: slice-stack over in-tile offsets
    rows = []
    for a in range(n):
        cols = []
        for b in range(n):
            cols.append(xp[..., a:a + (th - 1) * m + 1:m, b:b + (tw - 1) * m + 1:m])
        rows.append(jnp.stack(cols, -1))
    tiles = jnp.stack(rows, -2)                       # (..., c, th, tw, n, n)
    V = jnp.einsum("an,...cijnb,bm->...cijam", BT, tiles, BT.T)
    U = jnp.einsum("an,kcnb,bm->kcam", G, w, G.T)      # (k, c, n, n)
    M = jnp.einsum("kcab,...cijab->...kijab", U, V)    # (..., k, th, tw, n, n)
    Y = jnp.einsum("an,...kijnb,bm->...kijam", AT, M, AT.T)
    y = _t(Y, (0, 1, 3, 2, 4)).reshape(*lead, k, th * m, tw * m)
    return y[..., :oh, :ow]


def winograd1d(x: jnp.ndarray, w: jnp.ndarray, s: int, *, m: int, r: int) -> jnp.ndarray:
    """chw -> chw. 1-D F(m, r) along rows, direct sum over kernel rows
    (paper's 'winograd-2-3' / 'winograd-2-5' style)."""
    assert s == 1
    AT, G, BT = (jnp.asarray(a, x.dtype) for a in _WINO_SETS[(m, r)])
    c, h, wd = x.shape[-3:]
    k, _, f, _ = w.shape
    n = m + r - 1
    oh, ow = h - r + 1, wd - r + 1
    tw = -(-ow // m)
    pw = (tw - 1) * m + n
    lead = x.shape[:-3]
    acc = jnp.zeros((*lead, k, oh, ow), x.dtype)
    for a in range(r):  # kernel rows handled directly
        xrow = x[..., a:a + oh, :]                     # (..., c, oh, wd)
        xrow = jnp.pad(xrow, [(0, 0)] * (x.ndim - 1) + [(0, pw - wd)])
        segs = jnp.stack([xrow[..., b:b + (tw - 1) * m + 1:m] for b in range(n)], -1)
        V = segs @ BT.T                                # (..., c, oh, tw, n)
        U = jnp.einsum("nr,kcr->kcn", G, w[:, :, a, :])
        M = jnp.einsum("kcn,...citn->...kitn", U, V)
        Y = M @ AT.T                                   # (..., k, oh, tw, m)
        acc = acc + Y.reshape(*lead, k, oh, tw * m)[..., :ow]
    return acc


# ---------------------------------------------------------------------------
# conv-1x1 family
# ---------------------------------------------------------------------------

def conv1x1(x: jnp.ndarray, w: jnp.ndarray, s: int, *, ik: bool) -> jnp.ndarray:
    g = w[:, :, 0, 0]                                  # (k, c)
    if ik:   # hwc -> hwc
        xs = x[..., ::s, ::s, :]
        return xs @ g.T
    xs = x[..., ::s, ::s]                              # chw -> chw
    c, oh, ow = xs.shape[-3:]
    y = g @ xs.reshape(*xs.shape[:-2], oh * ow)
    return y.reshape(*xs.shape[:-3], g.shape[0], oh, ow)


# ---------------------------------------------------------------------------
# MEC family (memory-efficient convolution, Cho & Brandt)
# ---------------------------------------------------------------------------

def mec_col(x: jnp.ndarray, w: jnp.ndarray, s: int) -> jnp.ndarray:
    """chw -> chw. Lower along width only (L: ow strips of f columns), then
    f partitioned small GEMMs along the height."""
    c, h, wd = x.shape[-3:]
    k, _, f, _ = w.shape
    oh, ow = out_size(h, f, s), out_size(wd, f, s)
    strips = jnp.stack([x[..., j * s:j * s + f] for j in range(ow)], -4)  # (..., ow, c, h, f)
    parts = []
    for a in range(f):
        blk = strips[..., a:a + (oh - 1) * s + 1:s, :]    # (..., ow, c, oh, f)
        parts.append(jnp.einsum("...jcib,kcb->...kij", blk, w[:, :, a, :]))
    return jnp.sum(jnp.stack(parts), axis=0)              # (..., k, oh, ow)


def mec_row(x: jnp.ndarray, w: jnp.ndarray, s: int) -> jnp.ndarray:
    """hwc -> hwc. Lower along height; partitioned GEMMs along width."""
    h, wd, c = x.shape[-3:]
    k, _, f, _ = w.shape
    oh, ow = out_size(h, f, s), out_size(wd, f, s)
    strips = jnp.stack([x[..., i * s:i * s + f, :, :] for i in range(oh)], -4)  # (..., oh, f, wd, c)
    parts = []
    for b in range(f):
        blk = strips[..., b:b + (ow - 1) * s + 1:s, :]     # (..., oh, f, ow, c)
        parts.append(jnp.einsum("...iajc,kca->...ijk", blk, w[:, :, :, b]))
    return jnp.sum(jnp.stack(parts), axis=0)               # (..., oh, ow, k)


# ---------------------------------------------------------------------------
# direct family
# ---------------------------------------------------------------------------

def direct_sum2d(x: jnp.ndarray, w: jnp.ndarray, s: int) -> jnp.ndarray:
    """chw -> chw. Offset-sliced multiply-accumulate without a GEMM
    lowering — the 'six nested loops' structure, vectorised over pixels."""
    c, h, wd = x.shape[-3:]
    k, _, f, _ = w.shape
    oh, ow = out_size(h, f, s), out_size(wd, f, s)
    acc = jnp.zeros((*x.shape[:-3], k, oh, ow), x.dtype)
    for a in range(f):
        for b in range(f):
            sl = x[..., a:a + (oh - 1) * s + 1:s, b:b + (ow - 1) * s + 1:s]
            acc = acc + jnp.einsum("...cij,kc->...kij", sl, w[:, :, a, b])
    return acc


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Primitive:
    name: str
    family: str                       # direct | im2 | kn2 | wino3 | wino5 | c1x1 | mec
    in_layout: str
    out_layout: str
    impl: Optional[Callable]          # (x, w, stride) -> y; None => simulated-only
    traits: dict

    def applicable(self, k: int, c: int, im: int, s: int, f: int) -> bool:
        if f > im:
            return False
        if self.family == "wino3":
            return f == 3 and s == 1 and im >= self.traits.get("tile_n", 4)
        if self.family == "wino5":
            return f == 5 and s == 1 and im >= self.traits.get("tile_n", 6)
        if self.family == "c1x1":
            return f == 1
        if self.family == "kn2":
            return s == 1
        return True


def _mk(name, family, inl, outl, impl, **traits) -> Primitive:
    return Primitive(name, family, inl, outl, impl, traits)


def build_registry() -> Dict[str, Primitive]:
    P: List[Primitive] = []
    # --- direct ---
    P.append(_mk("direct-sum2d", "direct", "chw", "chw", direct_sum2d))
    # --- im2col / im2row (16) ---
    for trav in ("copy", "scan"):
        scan = trav == "scan"
        P.append(_mk(f"im2col-{trav}-ab-ki", "im2", "chw", "chw",
                     partial(im2col, scan=scan, out_ik=False), trav=trav, order="ki",
                     epilogue=True))
        P.append(_mk(f"im2col-{trav}-atb-ik", "im2", "chw", "hwc",
                     partial(im2col, scan=scan, out_ik=True), trav=trav, order="ik"))
        P.append(_mk(f"im2col-{trav}-atb-ki", "im2", "chw", "chw", None, trav=trav, order="ki", t="atb"))
        P.append(_mk(f"im2col-{trav}-atbt-ik", "im2", "chw", "hwc", None, trav=trav, order="ik", t="atbt"))
        P.append(_mk(f"im2row-{trav}-ab-ik", "im2", "hwc", "hwc",
                     partial(im2row, scan=scan, out_ik=True), trav=trav, order="ik", row=True))
        P.append(_mk(f"im2row-{trav}-abt-ki", "im2", "hwc", "chw",
                     partial(im2row, scan=scan, out_ik=False), trav=trav, order="ki", row=True))
        P.append(_mk(f"im2row-{trav}-abt-ik", "im2", "hwc", "hwc", None, trav=trav, order="ik", row=True, t="abt"))
        P.append(_mk(f"im2row-{trav}-atbt-ki", "im2", "hwc", "chw", None, trav=trav, order="ki", row=True, t="atbt"))
    # --- kn2 (6) ---
    P.append(_mk("kn2row", "kn2", "chw", "chw", kn2row))
    P.append(_mk("kn2row-as", "kn2", "chw", "chw", partial(kn2row, stacked=True), variant="as"))
    P.append(_mk("kn2row-aa-ab", "kn2", "chw", "chw", None, variant="aa-ab"))
    P.append(_mk("kn2row-aa-atb", "kn2", "chw", "chw", None, variant="aa-atb"))
    P.append(_mk("kn2col", "kn2", "hwc", "hwc", kn2col))
    P.append(_mk("kn2col-as", "kn2", "hwc", "hwc", None, variant="as"))
    # --- wino3 (10) ---
    P.append(_mk("winograd-2-3", "wino3", "chw", "chw",
                 partial(winograd1d, m=2, r=3), tile_m=2, tile_n=4, oned=True))
    P.append(_mk("winograd-2-3-vec-4", "wino3", "chw", "chw", None, tile_m=2, tile_n=4, oned=True, vec=4))
    P.append(_mk("winograd-2x2-3x3", "wino3", "chw", "chw",
                 partial(winograd2d, m=2, r=3), tile_m=2, tile_n=4, epilogue=True))
    for v in (4, 8, 16):
        P.append(_mk(f"winograd-2x2-3x3-vec-{v}", "wino3", "chw", "chw", None, tile_m=2, tile_n=4, vec=v))
    P.append(_mk("winograd-4x4-3x3", "wino3", "chw", "chw",
                 partial(winograd2d, m=4, r=3), tile_m=4, tile_n=6, epilogue=True))
    for v in (4, 8, 16):
        P.append(_mk(f"winograd-4x4-3x3-vec-{v}", "wino3", "chw", "chw", None, tile_m=4, tile_n=6, vec=v))
    # --- wino5 (6) ---
    P.append(_mk("winograd-2-5", "wino5", "chw", "chw",
                 partial(winograd1d, m=2, r=5), tile_m=2, tile_n=6, oned=True))
    P.append(_mk("winograd-2-5-vec-4", "wino5", "chw", "chw", None, tile_m=2, tile_n=6, oned=True, vec=4))
    P.append(_mk("winograd-2x2-5x5", "wino5", "chw", "chw",
                 partial(winograd2d, m=2, r=5), tile_m=2, tile_n=6))
    for v in (4, 8, 16):
        P.append(_mk(f"winograd-2x2-5x5-vec-{v}", "wino5", "chw", "chw", None, tile_m=2, tile_n=6, vec=v))
    # --- conv-1x1 (8) ---
    P.append(_mk("conv-1x1-gemm-ab-ki", "c1x1", "chw", "chw", partial(conv1x1, ik=False), order="ki",
                 epilogue=True))
    P.append(_mk("conv-1x1-gemm-atb-ik", "c1x1", "hwc", "hwc", partial(conv1x1, ik=True), order="ik"))
    for nm, lay in (("ab-ik", "hwc"), ("abt-ki", "chw"), ("abt-ik", "hwc"),
                    ("atb-ki", "chw"), ("atbt-ik", "hwc"), ("atbt-ki", "chw")):
        P.append(_mk(f"conv-1x1-gemm-{nm}", "c1x1", lay, lay, None, order=nm.split("-")[1]))
    # --- mec (2) ---
    P.append(_mk("mec-col", "mec", "chw", "chw", mec_col))
    P.append(_mk("mec-row-partition", "mec", "hwc", "hwc", mec_row))

    reg = {p.name: p for p in P}
    assert len(reg) == len(P), "duplicate primitive names"
    return reg


REGISTRY: Dict[str, Primitive] = build_registry()
PRIMITIVE_NAMES: List[str] = list(REGISTRY)
RUNNABLE: List[str] = [n for n, p in REGISTRY.items() if p.impl is not None]
FAMILIES = ("direct", "im2", "kn2", "wino3", "wino5", "c1x1", "mec")


# ---------------------------------------------------------------------------
# Tile-config columns (DESIGN.md §9)
#
# A column name "prim@tile" denotes a base registry primitive executed under
# a specific kernel tile configuration (e.g. a Pallas matmul block shape):
# the performance model and the PBQP treat each (primitive, tile) pair as
# its own column, so tile selection IS primitive selection. Registry traits,
# layouts and applicability come from the base primitive; only the cost
# model (and its noise stream, keyed on the full column name) distinguishes
# tiles.
# ---------------------------------------------------------------------------

TILE_SEP = "@"


def split_tile(name: str) -> Tuple[str, Optional[str]]:
    """'prim@tile' -> (base primitive name, tile variant); plain registry
    names return (name, None)."""
    base, sep, variant = name.partition(TILE_SEP)
    return base, (variant if sep else None)


def resolve(name: str) -> Primitive:
    """Registry entry for a (possibly tile-suffixed) column name."""
    return REGISTRY[split_tile(name)[0]]


# Base primitives the variant-aware plan lowering (plan.py / primitives.
# variants) can route through a Pallas kernel. Generic matmul tilings
# ("mm-*") apply to every GEMM-shaped base: the lowering feeds the base's
# patch/pointwise/transform GEMM through kernels/matmul with that block
# config. "conv-bk*" is the fused im2col kernel's K-block — im2col-family
# (and 1x1, a degenerate f=1 im2col) only. "wino-*" tiles the Winograd
# point-GEMM — 2-D wino3 bases only. Everything else has no Pallas lowering.
MM_LOWERABLE_BASES = ("im2col-copy-ab-ki", "im2col-scan-ab-ki",
                      "conv-1x1-gemm-ab-ki",
                      "winograd-2x2-3x3", "winograd-4x4-3x3")
CONVBK_LOWERABLE_BASES = ("im2col-copy-ab-ki", "im2col-scan-ab-ki",
                          "conv-1x1-gemm-ab-ki")
WINO_LOWERABLE_BASES = ("winograd-2x2-3x3", "winograd-4x4-3x3")


def variant_compatible(base: str, variant: Optional[str]) -> bool:
    """True iff the plan lowering can execute ``base`` under tile ``variant``
    (kernel shape constraints consulted — PBQP must never select a tile the
    lowering would reject at compile time)."""
    if variant is None:
        return True
    p = REGISTRY.get(base)
    if p is None or p.impl is None:
        return False
    # kernel VARIANTS imports are function-scope: kernels/winograd/ops
    # imports _WINO_SETS from this module at import time
    if variant.startswith("mm-"):
        from repro.kernels.matmul.ops import VARIANTS
        return variant in VARIANTS and base in MM_LOWERABLE_BASES
    if variant.startswith("conv-bk"):
        from repro.kernels.im2col_gemm.ops import VARIANTS
        return variant in VARIANTS and base in CONVBK_LOWERABLE_BASES
    if variant.startswith("wino-"):
        from repro.kernels.winograd.ops import VARIANTS
        return variant in VARIANTS and base in WINO_LOWERABLE_BASES
    return False


def is_runnable(name: str) -> bool:
    """A tile column is runnable iff its base primitive is AND the lowering
    accepts the (base, variant) pair's kernel shape constraints."""
    base, variant = split_tile(name)
    if base not in REGISTRY or REGISTRY[base].impl is None:
        return False
    return variant is None or variant_compatible(base, variant)


def supports_epilogue(name: str) -> bool:
    """Whether the column's base primitive advertises fused elementwise
    epilogues (bias / ReLU / residual add applied before HBM writeback)."""
    base, _ = split_tile(name)
    p = REGISTRY.get(base)
    return bool(p is not None and p.traits.get("epilogue", False))


def tile_columns(bases: Sequence[str], variants: Sequence[str]) -> List[str]:
    """The (base × tile-variant) cross product as column names, filtered to
    pairs the lowering can actually execute."""
    return [f"{b}{TILE_SEP}{v}" for b in bases for v in variants
            if variant_compatible(b, v)]


def family_of(name: str) -> str:
    return resolve(name).family


# ---------------------------------------------------------------------------
# Compiled per-column trait arrays (batched estimation, DESIGN.md §2.4)
# ---------------------------------------------------------------------------

# transpose-variant codes shared with the simulators: index into this tuple
T_VARIANTS: Tuple[Optional[str], ...] = (None, "atb", "abt", "atbt")


def name_hash64(s: str) -> int:
    """Stable 64-bit key for a registry/DLT name (noise stream seeding)."""
    return int.from_bytes(hashlib.sha256(s.encode()).digest()[:8], "big")


@dataclasses.dataclass(frozen=True)
class ColumnTraits:
    """Registry traits of a column list, pre-compiled into numpy arrays so the
    simulator time models can broadcast over (configs × columns) at once."""
    names: Tuple[str, ...]
    fam: np.ndarray            # (P,) int8 index into FAMILIES
    vec: np.ndarray            # (P,) float64 SIMD lanes, 0.0 = unspecified
    t_idx: np.ndarray          # (P,) int8 index into T_VARIANTS
    scan: np.ndarray           # (P,) bool, trav == "scan"
    order_ki: np.ndarray       # (P,) bool, order == "ki"
    tile_m: np.ndarray         # (P,) int64 Winograd output tile, 0 = n/a
    tile_n: np.ndarray         # (P,) int64 Winograd input tile, 0 = n/a
    oned: np.ndarray           # (P,) bool, 1-D Winograd
    variant_as: np.ndarray     # (P,) bool, kn2 "-as" stacked accumulation
    in_layout: np.ndarray      # (P,) int8 index into layouts.LAYOUTS
    out_layout: np.ndarray     # (P,) int8 index into layouts.LAYOUTS
    key: np.ndarray            # (P,) uint64 per-column noise-stream key
    epilogue: np.ndarray       # (P,) bool, fused elementwise epilogue support

    def applicable_mask(self, k: np.ndarray, c: np.ndarray, im: np.ndarray,
                        s: np.ndarray, f: np.ndarray) -> np.ndarray:
        """(L, P) bool mask mirroring ``Primitive.applicable`` — vectorised
        over (L,) config component arrays and the compiled columns."""
        k, c, im, s, f = (np.asarray(a).reshape(-1, 1) for a in (k, c, im, s, f))
        fam = self.fam[None, :]
        wino = (fam == FAMILIES.index("wino3")) | (fam == FAMILIES.index("wino5"))
        wino_f = np.where(self.fam == FAMILIES.index("wino5"), 5, 3)[None, :]
        return ((f <= im)
                & np.where(wino, (f == wino_f) & (s == 1)
                           & (im >= self.tile_n[None, :]), True)
                & np.where(fam == FAMILIES.index("c1x1"), f == 1, True)
                & np.where(fam == FAMILIES.index("kn2"), s == 1, True))


@lru_cache(maxsize=256)
def compile_traits(names: Tuple[str, ...]) -> ColumnTraits:
    # tile columns ("prim@tile") compile to their BASE primitive's traits —
    # layouts/applicability are tile-invariant — but keep a per-column noise
    # key from the full name so every tile gets its own deterministic stream
    prims = [resolve(n) for n in names]
    t = [p.traits for p in prims]
    return ColumnTraits(
        names=tuple(names),
        fam=np.array([FAMILIES.index(p.family) for p in prims], np.int8),
        vec=np.array([float(x.get("vec", 0) or 0) for x in t], np.float64),
        t_idx=np.array([T_VARIANTS.index(x.get("t")) for x in t], np.int8),
        scan=np.array([x.get("trav") == "scan" for x in t], bool),
        order_ki=np.array([x.get("order") == "ki" for x in t], bool),
        tile_m=np.array([int(x.get("tile_m", 0)) for x in t], np.int64),
        # same defaults as Primitive.applicable: wino3 -> 4, wino5 -> 6
        tile_n=np.array([int(x.get("tile_n", {"wino3": 4, "wino5": 6}.get(p.family, 0)))
                         for p, x in zip(prims, t)], np.int64),
        oned=np.array([bool(x.get("oned", False)) for x in t], bool),
        variant_as=np.array([str(x.get("variant", "")).startswith("as") for x in t], bool),
        in_layout=np.array([L.LAYOUTS.index(p.in_layout) for p in prims], np.int8),
        out_layout=np.array([L.LAYOUTS.index(p.out_layout) for p in prims], np.int8),
        key=np.array([name_hash64(n) for n in names], np.uint64),
        epilogue=np.array([bool(x.get("epilogue", False)) for x in t], bool),
    )


def run_primitive(name: str, x_chw: jnp.ndarray, w: jnp.ndarray, stride: int) -> jnp.ndarray:
    """Run primitive ``name`` on a chw image, returning chw output —
    layout conversions applied around the primitive's native layouts.
    (Used by tests and the real-CPU executor; the executor also accounts
    for the DLT costs explicitly.) Tile columns run their base impl — on
    this host's XLA path the tile config is a Pallas dispatch hint, not a
    different algorithm."""
    p = resolve(name)
    if p.impl is None:
        raise ValueError(f"{name} is a simulated-only primitive")
    x = L.from_chw(x_chw, p.in_layout)
    y = p.impl(x, w, stride)
    return L.to_chw(y, p.out_layout)


# ---------------------------------------------------------------------------
# Batched entry points (plan compiler, DESIGN.md §6)
# ---------------------------------------------------------------------------

def batch_impl(prim: Primitive) -> Callable:
    """Batched callable ``(x (n, *in_layout), w, stride) -> (n, *out_layout)``.

    Every built-in runnable impl is rank-polymorphic over leading batch axes,
    so the single-image impl *is* the batched impl; a primitive whose traits
    set ``batch=False`` (e.g. an impl with hard-coded rank-3 indexing) falls
    back to ``jax.vmap`` over the single-image call.
    """
    if prim.impl is None:
        raise ValueError(f"{prim.name} is a simulated-only primitive")
    if prim.traits.get("batch", True):
        return prim.impl
    return jax.vmap(prim.impl, in_axes=(0, None, None))


def run_primitive_batch(name: str, x_chw: jnp.ndarray, w: jnp.ndarray,
                        stride: int) -> jnp.ndarray:
    """Batched ``run_primitive``: (n, c, im, im) chw in, (n, k, oh, ow) out."""
    p = resolve(name)
    fn = batch_impl(p)
    y = fn(L.from_chw(x_chw, p.in_layout), w, stride)
    return L.to_chw(y, p.out_layout)


def reference_conv_batch(x_chw: jnp.ndarray, w: jnp.ndarray, stride: int) -> jnp.ndarray:
    """Batched oracle: XLA's native convolution, NCHW batch."""
    return jax.lax.conv_general_dilated(
        x_chw, w, window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))

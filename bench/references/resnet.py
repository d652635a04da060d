"""ResNet as He et al. (arXiv:1512.03385, Table 1) describe it, with the
departures its configuration file lists: valid convolutions, no pooling,
no batch norm, no activation, no head. Residual joins centre-crop both
operands to the smaller spatial size, since valid convolutions shrink the
two branches unequally.

Keys read: ``in_channels``, ``stem_channels``, ``stem_kernel``,
``stem_stride``, ``block`` (``basic`` or ``bottleneck``), ``widths``,
``blocks``, ``expansion``."""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp

Conv = Tuple[int, int, int, int]          # (out channels, in channels, kernel, stride)


def convs(cfg: Dict) -> List[Conv]:
    """Every convolution, in the order the blocks run them: the stem, then
    per block its main path and, where shapes change, the projection."""
    out = [(cfg["stem_channels"], cfg["in_channels"], cfg["stem_kernel"],
            cfg["stem_stride"])]
    c_in = cfg["stem_channels"]
    bottleneck = cfg["block"] == "bottleneck"
    for stage, (width, n) in enumerate(zip(cfg["widths"], cfg["blocks"])):
        out_c = width * cfg["expansion"]
        for blk in range(n):
            s = 2 if stage > 0 and blk == 0 else 1
            if bottleneck:
                out += [(width, c_in, 1, 1), (width, width, 3, s),
                        (out_c, width, 1, 1)]
            else:
                out += [(width, c_in, 3, s), (width, width, 3, 1)]
            if s != 1 or c_in != out_c:
                out.append((out_c, c_in, 1, s))
            c_in = out_c
    return out


def _crop(x: jnp.ndarray, h: int, w: int) -> jnp.ndarray:
    dh, dw = (x.shape[-2] - h) // 2, (x.shape[-1] - w) // 2
    return x[..., dh:dh + h, dw:dw + w]


def run(cfg: Dict, weights, x, conv) -> jnp.ndarray:
    """The block structure of ``convs``, with ``conv(x, w, stride)``."""
    it = iter(weights)
    y = conv(x, next(it), cfg["stem_stride"])
    c_in = cfg["stem_channels"]
    bottleneck = cfg["block"] == "bottleneck"
    for stage, (width, n) in enumerate(zip(cfg["widths"], cfg["blocks"])):
        out_c = width * cfg["expansion"]
        for blk in range(n):
            s = 2 if stage > 0 and blk == 0 else 1
            if bottleneck:
                t = conv(conv(conv(y, next(it), 1), next(it), s), next(it), 1)
            else:
                t = conv(conv(y, next(it), s), next(it), 1)
            sc = conv(y, next(it), s) if (s != 1 or c_in != out_c) else y
            h = min(t.shape[-2], sc.shape[-2])
            w = min(t.shape[-1], sc.shape[-1])
            y = _crop(t, h, w) + _crop(sc, h, w)
            c_in = out_c
    return y

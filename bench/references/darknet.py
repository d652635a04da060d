"""DarkNet-53 as Redmon & Farhadi (arXiv:1804.02767, Table 1) describe it,
with the departures its configuration file lists: no batch norm, no
activation, no head. Every 3x3 convolution sees its input zero-padded by 1
on every side (Darknet's ``pad=1``), by ``jnp.pad`` before the valid
``conv``; XLA's ``SAME`` would pad a stride-2 convolution of an even size
on one side only.

Keys read: ``in_channels``, ``stem_channels``, ``widths``, ``blocks``."""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp

Conv = Tuple[int, int, int, int]          # (out channels, in channels, kernel, stride)


def convs(cfg: Dict) -> List[Conv]:
    """Every convolution in the order the network runs them: the 3x3 stem,
    then per stage its 3x3/2 opening and per block its 1x1 and 3x3."""
    out = [(cfg["stem_channels"], cfg["in_channels"], 3, 1)]
    c = cfg["stem_channels"]
    for width, n in zip(cfg["widths"], cfg["blocks"]):
        out.append((width, c, 3, 2))
        out += [(width // 2, width, 1, 1), (width, width // 2, 3, 1)] * n
        c = width
    return out


def _pad1(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)))


def run(cfg: Dict, weights, x, conv) -> jnp.ndarray:
    """The stages of ``convs``, with ``conv(x, w, stride)`` and an identity
    residual add after each block."""
    it = iter(weights)
    y = conv(_pad1(x), next(it), 1)
    for _, n in zip(cfg["widths"], cfg["blocks"]):
        y = conv(_pad1(y), next(it), 2)
        for _ in range(n):
            t = conv(y, next(it), 1)
            y = y + conv(_pad1(t), next(it), 1)
    return y

"""Plain float32 reference of a configuration's network, and its weights.

The network is built from the configuration file alone (``configs/*.json``):
a ResNet as He et al. (arXiv:1512.03385, Table 1) describe it, with the
departures the file lists — valid convolutions, no pooling, no batch norm,
no activation, no head. Residual joins centre-crop both operands to the
smaller spatial size, since valid convolutions shrink the two branches
unequally. Nothing here imports the system under test.

``forward`` runs at ``Precision.HIGHEST``: a TPU rounds float32 matmul
operands to bfloat16 unless told otherwise. Its control, one precision
below the configuration's, is the same network at ``Precision.HIGH``
(three bfloat16 passes), or those passes written out for a CPU.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

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


def _run(cfg: Dict, weights, x, conv) -> jnp.ndarray:
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


def _conv(x, w, s, precision=lax.Precision.HIGHEST) -> jnp.ndarray:
    return lax.conv_general_dilated(
        x, w, (s, s), "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=precision, preferred_element_type=jnp.float32)


def _split(a):
    """``a`` = hi + lo: hi is ``a`` rounded to bfloat16 (to nearest, ties
    to even) and lo the rest, rounded to bfloat16 in turn. The rounding is
    done on the bits: a float32 → bfloat16 → float32 round trip could be
    folded away by a compiler that allows excess precision."""
    bits = lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    hi = lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _conv_bf16x3(x, w, s) -> jnp.ndarray:
    """hi·hi + hi·lo + lo·hi over bfloat16 splits of both operands, each
    product exact in float32: the arithmetic of ``Precision.HIGH``'s three
    passes, written out so that a CPU computes it too."""
    (xh, xl), (wh, wl) = _split(x), _split(w)
    one = functools.partial(_conv, s=s, precision=lax.Precision.DEFAULT)
    return one(xh, wh) + (one(xh, wl) + one(xl, wh))


_CONVS = {"highest": _conv,
          "high": functools.partial(_conv, precision=lax.Precision.HIGH),
          "bf16x3": _conv_bf16x3}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _forward(cfg_key, arith, weights, x):
    return _run(dict(cfg_key), weights, x, _CONVS[arith])


def _key(cfg: Dict) -> Tuple:
    return tuple((k, tuple(v) if isinstance(v, list) else v)
                 for k, v in sorted(cfg.items())
                 if k in ("in_channels", "stem_channels", "stem_kernel",
                          "stem_stride", "block", "widths", "blocks",
                          "expansion"))


def forward(cfg: Dict, weights, xs: np.ndarray, arith: str = "highest",
            rows: int = 16) -> np.ndarray:
    """Output (n, channels, h, w) of images ``xs`` (n, c, im, im), computed
    ``rows`` images at a time so that it fits beside nothing else.

    ``arith``: ``"highest"`` is the reference (float32, ``HIGHEST``).
    The control, one precision below: ``"high"`` (``Precision.HIGH``,
    three bfloat16 passes on a TPU, exact float32 elsewhere) or
    ``"bf16x3"`` (the same three passes written out, on any device)."""
    out = [np.asarray(_forward(_key(cfg), arith, weights,
                               jnp.asarray(xs[i:i + rows])))
           for i in range(0, len(xs), rows)]
    return np.concatenate(out)


def seed32(seed: int) -> int:
    """A 32-bit key for ``jax.random`` from any whole-number seed."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def make_weights(cfg: Dict, seed: int) -> List[jnp.ndarray]:
    """Every convolution's weights (k, c, f, f), made on the default device
    in one jitted call. He-style scale 1/(f·√c) keeps each convolution's
    output at its input's scale."""
    shapes = tuple((k, c, f, f) for k, c, f, _ in convs(cfg))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return [jax.random.normal(kk, s, jnp.float32) / (s[2] * np.sqrt(s[1]))
                for kk, s in zip(keys, shapes)]

    return make(jax.random.key(seed32(seed)))


def make_images(cfg: Dict, seed: int, n: int) -> np.ndarray:
    """``n`` distinct float32 N(0, 1) images (n, c, im, im) from ``seed``."""
    rng = np.random.default_rng(seed)
    shape = (n, cfg["in_channels"], cfg["image"], cfg["image"])
    return rng.standard_normal(shape, dtype=np.float32)


def executed_flops(cfg: Dict) -> int:
    """Direct-convolution FLOPs of one image at the shapes the network
    really runs (2 per multiply-add), whatever primitive implements them:
    the shapes come from tracing ``_run`` itself."""
    total = []

    def conv(x, w, s):
        y = _conv(x, w, s)
        k, c, f, _ = w.shape
        total.append(2 * k * c * f * f * y.shape[-2] * y.shape[-1])
        return y

    ws = [jax.ShapeDtypeStruct((k, c, f, f), jnp.float32)
          for k, c, f, _ in convs(cfg)]
    x = jax.ShapeDtypeStruct((1, cfg["in_channels"], cfg["image"],
                              cfg["image"]), jnp.float32)
    jax.eval_shape(lambda w, x: _run(cfg, w, x, conv), ws, x)
    return sum(total)

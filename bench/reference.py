"""Plain float32 reference of a configuration's network, and its weights.

The network is built from the configuration file alone
(``configs/<config>.json``): its key ``"reference"`` names a module
``references/<reference>.py`` that exports

- ``convs(cfg)``: every convolution as (out channels, in channels, kernel,
  stride), in the order of the system's conv nodes;
- ``run(cfg, weights, x, conv)``: the network on images ``x`` (n, c, h, w),
  built on a given ``conv(x, w, stride)``.

What every network shares is here: the convolution at ``HIGHEST`` and its
controls, ``forward`` in blocks of rows, the seeded weights and images, and
the executed FLOP count. Nothing here or there imports the system under
test.

``forward`` runs at ``Precision.HIGHEST``: a TPU rounds float32 matmul
operands to bfloat16 unless told otherwise. Its control, one precision
below the configuration's, is the same network at ``Precision.HIGH``
(three bfloat16 passes), or those passes written out for a CPU.
"""
from __future__ import annotations

import functools
import json
from types import ModuleType
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import registry

Conv = Tuple[int, int, int, int]          # (out channels, in channels, kernel, stride)

# keys of a configuration file that no reference reads: prose, the cut's
# bookkeeping and the limits of the check
_NOT_READ = frozenset({"name", "source", "published", "departures", "reduced",
                       "assumed", "executed_gflop_per_image",
                       "max_rel_err_limit"})


def network(cfg: Dict) -> ModuleType:
    """The configuration's reference module, ``references/<reference>.py``."""
    return registry.load_module("references", cfg["reference"])


def convs(cfg: Dict) -> List[Conv]:
    return network(cfg).convs(cfg)


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
def _forward(cfg_key: str, arith, weights, x):
    cfg = json.loads(cfg_key)
    return network(cfg).run(cfg, weights, x, _CONVS[arith])


def _key(cfg: Dict) -> str:
    """Every key a reference may read, sorted, as JSON text: configurations
    that differ in any shape never share a compiled reference."""
    return json.dumps({k: v for k, v in cfg.items() if k not in _NOT_READ},
                      sort_keys=True)


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
    the shapes come from tracing the reference's ``run`` itself."""
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
    net = network(cfg)
    jax.eval_shape(lambda w, x: net.run(cfg, w, x, conv), ws, x)
    return sum(total)

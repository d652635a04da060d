"""Zero padding as a property of a convolution: a padded layer runs the
valid convolution of its zero-padded input through every primitive family
the selector can pick for a 3x3, pads once under its step's ``pack/pad``
scope (folded into the Winograd and fused im2col kernels' own pads), is
priced as the valid convolution of the padded size, and an unpadded layer
emits nothing new."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.models import cnn_zoo
from repro.models.cnn_zoo import ConvLayer, _Builder
from repro.primitives import layouts as L
from repro.primitives.conv import REGISTRY
from repro.primitives.plan import compile_plan
from repro.primitives.variants import conv_variant_call


def _reference(x, w, stride, pad):
    """XLA's convolution with explicit symmetric padding, at HIGHEST."""
    return lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)


def _inputs(rng, n, c, im, k, f):
    x = jnp.asarray(rng.standard_normal((n, c, im, im)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, c, f, f)) / (f * np.sqrt(c)),
                    jnp.float32)
    return x, w


# every (base, tile) family the selector can pick for a 3x3, and a 1x1;
# ``folds``: the kernel folds the padding into a pad it already makes
FAMILIES = [
    ("im2col-copy-ab-ki", "mm-256x128x128", 3, False),
    ("im2col-scan-ab-ki", "mm-128x256x128", 3, False),
    ("im2col-copy-ab-ki", "conv-bk64", 3, True),
    ("im2col-scan-ab-ki", "conv-bk128", 3, True),
    ("winograd-2x2-3x3", "mm-128x128x128", 3, True),
    ("winograd-4x4-3x3", "mm-128x128x256", 3, True),
    ("winograd-2x2-3x3", "wino-128x128", 3, True),
    ("winograd-4x4-3x3", "wino-256x128", 3, True),
    ("conv-1x1-gemm-ab-ki", "mm-128x128x128", 1, False),
    ("conv-1x1-gemm-ab-ki", "conv-bk256", 1, True),
]


def _cases():
    for base, variant, f, _ in FAMILIES:
        wino = base.startswith("winograd")
        for stride in (1,) if wino else (1, 2):
            for im in (15, 16):                   # odd and even sizes
                # darknet53's 1x1s run unpadded; a padded one is valid too
                for pad in (0, 1) if f == 1 else (1,):
                    yield base, variant, f, stride, im, pad


@pytest.mark.parametrize("base,variant,f,stride,im,pad", list(_cases()))
def test_padded_conv_matches_reference(base, variant, f, stride, im, pad,
                                       rng):
    """Two images, so that the ``mm-*`` GEMMs run per image where a padded
    output has a lane tile of pixels (15², 16²) and fold the batch where it
    has not (8², at stride 2); with the epilogue, the residual being of the
    padded output's size."""
    x, w = _inputs(rng, 2, 6, im, 40, f)
    ref = _reference(x, w, stride, pad)
    got = conv_variant_call(REGISTRY[base], variant, x, w, stride, pad=pad)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    res = jnp.asarray(rng.standard_normal(ref.shape), jnp.float32)
    got = conv_variant_call(REGISTRY[base], variant, x, w, stride,
                            residual=res, relu=True, pad=pad)
    np.testing.assert_allclose(got, jnp.maximum(ref + res, 0.0),
                               rtol=1e-4, atol=1e-4)


def _pad_scopes(jaxpr, outer: str = "") -> list:
    """The name stack of every ``pad`` the program itself makes (a Pallas
    kernel's own body, and interpret mode's padding of its blocks, are not
    walked)."""
    out = []
    for e in jaxpr.eqns:
        stack = "/".join(s for s in (outer, str(e.source_info.name_stack)) if s)
        if e.primitive.name == "pad":
            out.append(stack)
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            if isinstance(v, ClosedJaxpr):
                out += _pad_scopes(v.jaxpr, stack)
            elif isinstance(v, Jaxpr):
                out += _pad_scopes(v, stack)
    return out


@pytest.mark.parametrize("base,variant,f,folds", FAMILIES,
                         ids=[f"{b}@{v}" for b, v, _, _ in FAMILIES])
def test_a_padded_step_pads_once(base, variant, f, folds):
    """At pad 0 no op has a ``pad`` scope; at pad 1 exactly one does, under
    ``pack``, and a kernel that pads to tiles, phases or channels anyway
    makes no second pad. (20² pixels: the ``mm-*`` GEMMs run per image at
    either padding, and add no pads of their own.)"""
    x = jnp.ones((2, 8, 20, 20), jnp.float32)
    w = jnp.ones((16, 8, f, f), jnp.float32)

    def pads(p):
        return _pad_scopes(jax.make_jaxpr(
            lambda a, b: conv_variant_call(REGISTRY[base], variant, a, b, 1,
                                           pad=p))(x, w).jaxpr)
    p0, p1 = pads(0), pads(1)
    assert not [s for s in p0 if s.split("/")[-1] == "pad"], p0
    assert [s for s in p1 if s.split("/")[-1] == "pad"] == ["pack/pad"], p1
    assert len(p1) == len(p0) + (0 if folds else 1), (p0, p1)


def _two_step(p: int):
    b = _Builder(f"two-step-p{p}")
    x = b.conv(8, 4, 12, 1, 3, p=p)
    b.conv(8, 8, 12 + 2 * p - 2, 2, 3, prev=x, p=p)
    return b.build()


@pytest.mark.parametrize("column", ["im2col-copy-ab-ki", "im2row-scan-ab-ik",
                                    "direct-sum2d", "winograd-2x2-3x3"])
def test_plan_pads_base_impls_once_per_padded_step(column, rng):
    """A base impl (no tile variant) gets its input padded by the plan, in
    the primitive's own layout, under ``conv<n>/pack/pad``; an unpadded
    network of the same steps has no such pad (the Winograd impl's pad to
    whole tiles is its own), and both agree with the reference."""
    for p in (0, 1):
        spec = _two_step(p)
        # Winograd runs stride 1 only: the stride-2 step lowers by im2col
        asg = {0: column, 1: column if not column.startswith("winograd")
               else "im2col-copy-ab-ki"}
        x = jnp.asarray(rng.standard_normal((2, 4, 12, 12)), jnp.float32)
        ws = {i: jnp.asarray(rng.standard_normal((n.k, n.c, n.f, n.f)) / 6,
                             jnp.float32)
              for i, n in enumerate(spec.nodes)}
        plan = compile_plan(spec, asg, x.shape)
        scopes = [s for s in _pad_scopes(jax.make_jaxpr(plan.fn)(
            {0: x}, ws).jaxpr) if s.endswith("/pad")]
        assert scopes == ["conv0/pack/pad", "conv1/pack/pad"][:2 * p], scopes
        # both inputs, 4 and 8 channels, of two 14² padded images, float32
        assert plan.pads[2] == {"steps": 2 * p,
                                "bytes": p * 2 * (4 + 8) * 14 * 14 * 4}
        sink = plan.sinks[-1]
        got = L.to_chw(plan({0: x}, ws)[sink], plan.layouts[sink])
        want = _reference(_reference(x, ws[0], 1, p), ws[1], 2, p)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_a_padded_layer_is_priced_as_the_valid_one_on_the_padded_input():
    """``config`` (what the perf model, profiling and simulators read)
    counts the padding; ``im`` stays the input the server checks."""
    layer = ConvLayer("l", 64, 32, 128, 1, 3, 1)
    assert layer.config == (64, 32, 130, 1, 3)
    assert layer.out_im == 128
    assert ConvLayer("l", 64, 32, 128, 2, 3, 1).out_im == 64
    assert ConvLayer("l", 64, 32, 128, 1, 3).config == (64, 32, 128, 1, 3)


def test_darknet53_layers():
    """52 convolutions, every 3x3 padded by 1 and every 1x1 unpadded; sizes
    256 → 8, each residual add at its block's exact size."""
    spec = cnn_zoo.get("darknet53")
    convs = spec.conv_layers
    assert len(convs) == 52
    assert all(n.p == (1 if n.f == 3 else 0) for n in convs)
    assert sorted({n.out_im for n in convs}) == [8, 16, 32, 64, 128, 256]
    for i, n in enumerate(spec.nodes):
        if isinstance(n, cnn_zoo.JoinNode):
            ins = [spec.nodes[u] for u, v in spec.edges if v == i]
            assert [m.out_im if isinstance(m, ConvLayer) else m.im
                    for m in ins] == [n.im, n.im]
    assert "darknet53" in cnn_zoo.EXECUTABLE_NETS


def test_profiling_pool_leaves_darknet53_out():
    """The pool is a fixed list of networks: darknet53's padded triplets
    would retrain the perf model and move resnet50's selection."""
    assert "darknet53" not in cnn_zoo.POOL_NETS
    assert set(cnn_zoo.POOL_NETS) <= set(cnn_zoo.ZOO)


# resnet50's assignment on PallasPlatform (seed 0), as selected before
# darknet53 entered the zoo: sha256 of its sorted (node, column) pairs
RESNET50_ASSIGNMENT_SHA256 = (
    "8f0e9e16f5be4b5e57c7b792a47e73eebb6436e9bbf389f1b52075dde8fc0cf4")


def test_resnet50_assignment_is_pinned():
    from repro.service.pipeline import optimise
    from repro.service.platforms import PallasPlatform
    opt = optimise(cnn_zoo.get("resnet50"), PallasPlatform(),
                   executable=True, seed=0)
    text = json.dumps(sorted(opt.assignment.items()))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        RESNET50_ASSIGNMENT_SHA256


def test_pads_counted_per_bucket():
    """``stats()["pads"]`` counts, per precompiled bucket, the padded conv
    steps and the bytes of their padded inputs."""
    from repro.primitives.plan import heuristic_assignment
    from repro.service.pipeline import OptimisedNetwork
    from repro.service.server import OptimisedServer
    spec = cnn_zoo.darknet("darknet-tiny", 16, 4, (8,), (1,))
    server = OptimisedServer(max_batch=2, latency_budget_ms=float("inf"))
    server.register(OptimisedNetwork.from_assignment(
        spec, heuristic_assignment(spec)))
    pads = server.stats("darknet-tiny")["pads"]
    server.stop()
    # stem (3 x 18²), the stride-2 opening (4 x 18²), the block's 3x3
    # (4 x 10²), float32
    per_image = 4 * (3 * 18 * 18 + 4 * 18 * 18 + 4 * 10 * 10)
    assert pads == {1: {"steps": 3, "bytes": per_image},
                    2: {"steps": 3, "bytes": 2 * per_image}}

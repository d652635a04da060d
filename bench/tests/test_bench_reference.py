"""The plain reference: its FLOP count, its agreement with the served
plan, and its control, which the limit has to catch."""

import numpy as np
import pytest

from bench import reference, registry
from bench.tests import tiny


@pytest.mark.parametrize("over,gflop,convs,flops", [
    ({}, 16.9, 53, 16_895_092_096),
    ({"block": "basic", "blocks": [2, 2, 2, 2], "expansion": 1}, 7.3, 20,
     7_322_068_352),
], ids=["resnet50", "resnet18"])
def test_executed_flops_of_the_shapes_that_run(over, gflop, convs, flops):
    """16.9 and 7.3 GFLOP per 224x224 image: 2.3x and 2.5x the networks'
    published counts, since valid convolutions without pooling keep the
    stages at 109-1 pixels instead of 56-7. The integers are those of the
    reference before it moved into ``references/resnet.py``: ``mfu``'s
    divisor."""
    cfg = {**registry.load_json("configs", "resnet50-valid"), **over}
    assert len(reference.convs(cfg)) == convs
    assert reference.executed_flops(cfg) == flops
    assert reference.executed_flops(cfg) / 1e9 == pytest.approx(gflop, abs=0.05)
    if not over:
        assert cfg["executed_gflop_per_image"] == pytest.approx(gflop)


def _tile_assignment(spec):
    """Every kernel family the resnet plans select: im2col and 1x1 GEMMs
    through the tiled matmul, stride-1 3x3 through Winograd 4x4."""
    from repro.models.cnn_zoo import ConvLayer
    asg = {}
    for i, n in enumerate(spec.nodes):
        if not isinstance(n, ConvLayer):
            asg[i] = "chw"
        elif n.f == 1:
            asg[i] = "conv-1x1-gemm-ab-ki@mm-128x128x128"
        elif n.f == 3 and n.s == 1:
            asg[i] = "winograd-4x4-3x3@mm-128x128x128"
        else:
            asg[i] = "im2col-copy-ab-ki@mm-128x128x128"
    return asg


@pytest.mark.parametrize("block", ["basic", "bottleneck"])
def test_reference_agrees_with_the_served_plan(block):
    """The served plan (Pallas kernels in interpret mode, epilogue-fused
    residual adds) and the reference, from the same weights and images."""
    from bench.serve import _net_weights
    from repro.primitives import layouts as L
    from repro.primitives.plan import compile_plan
    cfg = tiny.config(block)
    spec = tiny.spec(cfg)
    w = reference.make_weights(cfg, 3)
    xs = reference.make_images(cfg, 3, 3)
    plan = compile_plan(spec, _tile_assignment(spec), xs.shape)
    assert plan.epilogue_signature, "no residual add was fused"
    got = np.asarray(L.to_chw(plan(xs, _net_weights(spec, cfg, w))[plan.sinks[-1]],
                              plan.layouts[plan.sinks[-1]]))
    want = reference.forward(cfg, w, xs)
    assert got.shape == want.shape
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-5


def test_weights_and_images_follow_the_seed_only():
    cfg = tiny.config()
    big = 2 ** 31 + 12345
    a, b = reference.make_weights(cfg, big), reference.make_weights(cfg, big)
    c = reference.make_weights(cfg, big + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert np.array_equal(reference.make_images(cfg, big, 2),
                          reference.make_images(cfg, big, 2))


@pytest.fixture(scope="module")
def at_seed_17():
    """Each configuration's weights, one image and the reference's output
    at seed 17."""
    out = {}
    for c in registry.benchmark()["configs"]:
        cfg = registry.load_json("configs", c["name"])
        w = reference.make_weights(cfg, 17)
        xs = reference.make_images(cfg, 17, 1)
        out[c["name"]] = cfg, w, xs, reference.forward(cfg, w, xs)
    return out


def test_resnet50_reference_output_is_pinned(at_seed_17):
    """resnet50-valid's reference output at seed 17 (2048 values) as the
    reference computed it before it moved into ``references/resnet.py``:
    the same weights, images and network, so the same ``correct``."""
    ref = at_seed_17["resnet50-valid"][3].astype(np.float64)
    assert ref.shape == (1, 2048, 1, 1)
    assert np.abs(ref).sum() == pytest.approx(408773.06834697723, rel=1e-6)
    assert (ref ** 2).sum() == pytest.approx(126790537.5754112, rel=1e-6)
    np.testing.assert_allclose(
        ref.reshape(-1)[:4], [-307.7466125488281, -323.2938232421875,
                              -228.32626342773438, 239.79019165039062],
        rtol=1e-6)


@pytest.mark.parametrize("name", ["resnet50-valid"])
def test_control_fails_the_configs_limit(name, at_seed_17):
    """The reference at three bfloat16 passes (the precision below the
    configuration's float32 at highest), in the program's place at the
    cell's own widths, reads above the limit: the comparison would catch a
    plan that computed at that precision."""
    cfg, w, xs, ref = at_seed_17[name]
    ctl = reference.forward(cfg, w, xs, "bf16x3")
    err = float(np.abs(ctl - ref).max() / np.abs(ref).max())
    assert err > cfg["max_rel_err_limit"], (err, cfg["max_rel_err_limit"])

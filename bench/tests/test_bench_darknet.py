"""darknet53: the configuration's plain reference against the system's
network, its FLOP count, its agreement with the served plan on a small
DarkNet, and its control against the limit; the ``pad_share`` reader."""
import time

import numpy as np
import pytest

from bench import reference, registry
from bench import run as R
from bench.tests import tiny

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _tiny_config() -> dict:
    """Two stages at an eighth of the widths, 32² images."""
    cfg = registry.load_json("configs", "darknet53")
    cfg.update(name="darknet-tiny", image=32, stem_channels=4, widths=[8, 16],
               blocks=[1, 2])
    return cfg


def _tiny_spec(cfg: dict):
    from repro.models import cnn_zoo
    return cnn_zoo.darknet(cfg["name"], cfg["image"], cfg["stem_channels"],
                           cfg["widths"], cfg["blocks"])


def test_darknet53_is_the_configs_network():
    """The zoo's 52 conv nodes are the reference's convolutions, in order;
    18,568,183,808 FLOPs a 256² image, the paper's "18.7 Bn Ops"."""
    from repro.models import cnn_zoo
    cfg = registry.load_json("configs", "darknet53")
    got = [(n.k, n.c, n.f, n.s) for n in cnn_zoo.get("darknet53").conv_layers]
    assert got == reference.convs(cfg) and len(got) == 52
    assert reference.executed_flops(cfg) == 18_568_183_808
    assert cfg["executed_gflop_per_image"] == pytest.approx(18.57)


def _tiles(spec):
    """Every kernel family a selection can give a DarkNet: 1x1 GEMMs, the
    stride-2 openings by im2col (tiled and fused), the stride-1 3x3s by
    Winograd, alternating point-GEMM tilings."""
    from repro.models.cnn_zoo import ConvLayer
    asg, k = {}, 0
    for i, n in enumerate(spec.nodes):
        if not isinstance(n, ConvLayer):
            asg[i] = "chw"
        elif n.f == 1:
            asg[i] = "conv-1x1-gemm-ab-ki@mm-128x128x128"
        elif n.s == 2:
            asg[i] = ("im2col-scan-ab-ki@conv-bk64", "im2col-copy-ab-ki@mm-"
                      "128x128x128")[i % 2]
        else:
            asg[i] = ("winograd-4x4-3x3@mm-128x128x256",
                      "winograd-2x2-3x3@wino-128x128",
                      "im2col-copy-ab-ki@mm-256x128x128")[k % 3]
            k += 1
    return asg


def _assignment(kind, spec):
    from repro.primitives.plan import heuristic_assignment
    if kind == "tiles":
        return _tiles(spec)
    if kind == "base":
        return heuristic_assignment(spec)
    from repro.service.pipeline import optimise
    from repro.service.platforms import PallasPlatform
    return optimise(spec, PallasPlatform(), executable=True,
                    seed=0).assignment


@pytest.mark.parametrize("kind", ["tiles", "base", "selected"])
def test_small_darknet_agrees_with_the_reference(kind):
    """A small DarkNet through ``compile_plan`` (sinks, residual adds folded
    into the 3x3s' epilogues), through the executor's compiled ``"all"``
    plan and through its interpreted path, against the reference on the
    same seeded weights and images, float32."""
    from bench.serve import _net_weights
    from repro.primitives import layouts as L
    from repro.primitives.executor import execute
    from repro.primitives.plan import compile_plan
    cfg = _tiny_config()
    spec = _tiny_spec(cfg)
    w = _net_weights(spec, cfg, reference.make_weights(cfg, 5))
    xs = reference.make_images(cfg, 5, 2)
    want = reference.forward(cfg, [w[k] for k in sorted(w)], xs)
    scale = np.abs(want).max()
    asg = _assignment(kind, spec)
    plan = compile_plan(spec, asg, xs.shape)
    if kind != "base":
        assert plan.epilogue_signature, "no residual add was folded"
    sink = plan.sinks[-1]
    got = np.asarray(L.to_chw(plan(xs, w)[sink], plan.layouts[sink]))
    assert got.shape == want.shape
    assert np.abs(got - want).max() / scale < 1e-5
    for compiled in (True, False):
        rep = execute(spec, asg, w, x=xs[1], compiled=compiled)
        got = np.asarray(L.to_chw(rep.outputs[sink], plan.layouts[sink]))
        assert np.abs(got - want[1]).max() / scale < 1e-5, compiled


def test_small_darknet_cell_is_correct():
    """``run_cell`` on the CPU: the configuration's reference found by
    name, the small DarkNet selected, served and checked."""
    from repro.service.pipeline import optimise
    from repro.service.platforms import PallasPlatform
    cfg = _tiny_config()
    spec = _tiny_spec(cfg)
    opt = optimise(spec, PallasPlatform(), executable=True, seed=0)
    cell = tiny.cell(cfg, "offline")
    cell["end_to_end"] = registry.cell(registry.benchmark(),
                                       "darknet53.offline")["end_to_end"]
    out = R.run_cell(cell, 2 ** 31 + 23, 1.0, False, PEAKS, spec=spec,
                     optimise_fn=lambda s: opt, t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"img_per_s", "setup_s"} == set(out["metrics"])


def test_control_fails_darknet53s_limit():
    """The reference at three bfloat16 passes, at the cell's own widths and
    size, reads above the configuration's limit on one image."""
    cfg = registry.load_json("configs", "darknet53")
    w = reference.make_weights(cfg, 17)
    xs = reference.make_images(cfg, 17, 1)
    ref = reference.forward(cfg, w, xs)
    ctl = reference.forward(cfg, w, xs, "bf16x3")
    err = float(np.abs(ctl - ref).max() / np.abs(ref).max())
    assert err > cfg["max_rel_err_limit"], (err, cfg["max_rel_err_limit"])


# -- pad_share ---------------------------------------------------------------

_PROGRAM = """HloModule m

ENTRY %main {
  %p = f32[2,4,8,8]{3,2,1,0} parameter(0)
  %pad.1 = f32[2,4,10,10]{3,2,1,0} pad(%p), padding=0_0x0_0x1_1x1_1, metadata={op_name="jit(fn)/conv3/pack/pad/jit(_pad)/pad"}
  %fusion.2 = f32[2,4,10,10]{3,2,1,0} fusion(%pad.1), kind=kLoop, metadata={op_name="jit(fn)/conv3/pack/mul"}
  ROOT %copy.3 = f32[2,4,10,10]{3,2,1,0} copy(%fusion.2), metadata={op_name="jit(fn)/conv5/pack/copy"}
}
"""


class _Run:
    def __init__(self, programs, events, busy_s=1e-3):
        self.trace = {"busy_s": busy_s}
        self.programs = programs
        self.trace_events = {"planes": [{"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": events}]}]}


def _event(name, ns):
    return (f"%{name} = f32[2,4,10,10]{{3,2,1,0}} op()", 0, ns)


def test_pad_share_reads_the_pads_a_step_materialised():
    read = registry.load_module("metrics", "pad_share").read
    events = [_event("pad.1", 250_000), _event("fusion.2", 500_000),
              _event("copy.3", 250_000)]
    assert read(_Run([_PROGRAM], events)) == pytest.approx(25.0)
    # every pad fused: the scope is in the program, no op of its own ran
    assert read(_Run([_PROGRAM], events[1:])) == 0.0
    # a program that marks no pad (an unpadded network, or one from before
    # pads had a scope): nothing to read
    unmarked = _PROGRAM.replace("pack/pad/", "pack/")
    assert read(_Run([unmarked], events)) is None
    untraced = _Run([_PROGRAM], events)
    untraced.trace = None
    assert read(untraced) is None

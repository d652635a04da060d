"""Compile-only checks of the served Pallas kernels for a TPU v5e.

The chip's compiler is installed even where no chip is attached: these tests
compile each kernel family at resnet50 widths for one chip of a described
``v5e:2x2`` topology, with ``interpret=False`` passed explicitly (on a CPU
backend the kernels would otherwise default to interpret mode and compile
to no kernel at all), and assert that the optimised program holds the
Mosaic kernel. Nothing runs; the compiler refuses what the chip would
refuse (unaligned blocks, VMEM overruns).

The topology is described inside a fixture: only the test worker that runs
this file loads the TPU library, and where it cannot be described the
tests skip.
"""
from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.im2col_gemm.im2col_gemm import conv_im2col_batch
from repro.kernels.matmul.matmul import matmul
from repro.kernels.matmul.ops import VARIANTS as MM_VARIANTS
from repro.kernels.winograd.ops import VARIANTS as WINO_VARIANTS
from repro.kernels.winograd.ops import winograd_conv_batch

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_mm_kernel_fused_epilogue_compiles(one_chip):
    """resnet50 stage-2 expand 1x1 (128 -> 512 at 28², batch 8 folded into
    GEMM N) with bias, residual and ReLU fused into the kernel's store."""
    bm, bk, bn = MM_VARIANTS["mm-256x128x256"]
    M, K, N = 512, 128, 8 * 28 * 28

    def fn(x, y, b, r):
        return matmul(x, y, bm=bm, bk=bk, bn=bn, bias=b, residual=r,
                      relu=True, interpret=False)

    text = _compile_text(fn, one_chip, (M, K), (K, N), (M,), (M, N))
    assert text.count(KERNEL) == 1


@pytest.mark.parametrize("cfg", [
    # (N, C, H, K, f, stride, bk): resnet50's 3x3 at 56², and the 7x7 stem
    (8, 64, 56, 64, 3, 1, 64),
    (8, 3, 224, 64, 7, 2, 128),
], ids=["3x3-s1", "stem-7x7-s2"])
def test_conv_bk_kernel_compiles(cfg, one_chip):
    N, C, H, K, f, s, bk = cfg
    oh = (H - f) // s + 1

    def fn(x, w, r):
        return conv_im2col_batch(x, w, s, bk=bk, residual=r, relu=True,
                                 interpret=False)

    text = _compile_text(fn, one_chip, (N, C, H, H), (K, C, f, f),
                         (N, K, oh, oh))
    assert text.count(KERNEL) == 1


def test_wino_kernel_compiles(one_chip):
    """resnet50 stage-3 3x3 (256 -> 256 at 14², batch 8) through the
    Winograd F(2x2, 3x3) point-GEMM kernel."""
    bk, bt = WINO_VARIANTS["wino-128x128"]

    def fn(x, w):
        return winograd_conv_batch(x, w, m=2, bk=bk, bt=bt, relu=True,
                                   interpret=False)

    text = _compile_text(fn, one_chip, (8, 256, 14, 14), (256, 256, 3, 3))
    assert text.count(KERNEL) == 1


def test_mm_per_image_1x1_has_no_activation_glue(one_chip, monkeypatch):
    """resnet50 stage-1 expand 1x1 (64 -> 256 at 107², batch 8) with residual
    and ReLU through ``conv_variant_call``'s ``mm-*`` lowering: the batch is
    a grid axis of the kernel, so the optimised program holds the one
    ``matmul_op`` kernel and no pad, slice or transpose of an
    activation-sized operand (107² pixels are no multiple of a block)."""
    from bench import trace as T
    from repro.kernels.matmul import ops as mm_ops
    from repro.primitives.conv import REGISTRY
    from repro.primitives.variants import conv_variant_call, gemm_path

    N, C, H, K = 8, 64, 107, 256
    prim, variant = REGISTRY["conv-1x1-gemm-ab-ki"], "mm-256x128x256"
    assert gemm_path(prim, variant, N, H * H) == "per_image"

    def fn(x, w, r):
        with jax.default_matmul_precision("float32"):
            return conv_variant_call(prim, variant, x, w, 1, residual=r,
                                     relu=True)

    monkeypatch.setattr(mm_ops, "default_interpret", lambda: False)
    jax.clear_caches()
    try:
        text = _compile_text(fn, one_chip, (N, C, H, H), (K, C, 1, 1),
                             (N, K, H, H))
    finally:
        jax.clear_caches()
    calls = T.custom_calls(text)
    assert [c["family"] for c in calls.values()] == ["matmul_op"]
    (call,) = calls.values()
    assert [dims for _, dims in call["operands"]] == [
        (K, C), (N, C, H * H), (N, K, H * H)]
    glue = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%[\w.\-]+\s+=\s+\w+\[([\d,]*)\]\S*\s+"
                     r"(pad|slice|transpose)\(", line)
        if m and math.prod(map(int, m.group(1).split(","))) >= N * C * H * H:
            glue.append(line.strip()[:120])
    assert not glue, glue


def test_plan_ops_map_to_plan_step_scopes(one_chip, monkeypatch):
    """A small bottleneck plan with ``mm-*`` steps (1x1, im2col, Winograd
    bases) and a ``wino-*`` step, compiled whole for the chip: every
    instruction of the optimised program but the parameters maps to a plan
    step's scope, directly or through the fallbacks of ``bench.spans``,
    and the kernels keep their families (the innermost ``jit``)."""
    from bench import spans
    from bench import trace as T
    from repro.kernels.im2col_gemm import ops as conv_ops
    from repro.kernels.matmul import ops as mm_ops
    from repro.kernels.winograd import ops as wino_ops
    from repro.models.cnn_zoo import ConvLayer, _Builder
    from repro.primitives.plan import clear_plan_cache, compile_plan

    b = _Builder("scoped")
    stem = b.conv(16, 3, 20, 1, 3)                       # im2col → 18²
    x = b.conv(8, 16, 18, 1, 1, prev=stem)
    x = b.conv(8, 8, 18, 1, 3, prev=x)                   # wino-* → 16²
    x = b.conv(8, 8, 16, 1, 3, prev=x)                   # mm-* on wino → 14²
    x = b.conv(16, 8, 14, 1, 1, prev=x)
    b.join("add", 16, 14, [x, stem])
    spec = b.build()
    asg = {}
    for i, n in enumerate(spec.nodes):
        if not isinstance(n, ConvLayer):
            asg[i] = "chw"
        elif n.f == 1:
            asg[i] = "conv-1x1-gemm-ab-ki@mm-128x128x128"
        elif i == 0:
            asg[i] = "im2col-copy-ab-ki@mm-128x128x128"
        else:
            asg[i] = ("winograd-2x2-3x3@wino-128x128" if i == 2
                      else "winograd-4x4-3x3@mm-128x128x256")
    for mod in (mm_ops, wino_ops, conv_ops):     # compile the kernels, not
        monkeypatch.setattr(mod, "default_interpret", lambda: False)
    jax.clear_caches()                           # their interpreted traces
    clear_plan_cache()
    try:
        shape = (2, 3, 20, 20)
        plan = compile_plan(spec, asg, shape)
        src, sink = plan.sources[0], plan.sinks[-1]
        weights = {i: jax.ShapeDtypeStruct((n.k, n.c, n.f, n.f), jnp.float32,
                                           sharding=one_chip)
                   for i, n in enumerate(spec.nodes)
                   if isinstance(n, ConvLayer)}
        text = jax.jit(lambda a, w: plan.fn({src: a}, w)[sink]).lower(
            jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip),
            weights).compile().as_text()
    finally:
        jax.clear_caches()
        clear_plan_cache()
    families = sorted(c["family"] for c in T.custom_calls(text).values())
    assert families == ["matmul_op"] * 3 + ["winograd_conv_batch"] * 2
    scopes = spans.scope_map([text])
    entry = text[text.index("\nENTRY"):].split("\n}")[0].splitlines()[2:]
    ops = [m.group(1) for m in map(spans._INSTR.match, entry)
           if m and " parameter(" not in m.group(2)]
    assert ops
    unscoped = [name for name in ops
                if any(s[1] is None for s in scopes[name])]
    assert not unscoped, unscoped
    roles = {s[2] for name in ops for s in scopes[name]}
    assert {"kernel", "pack", "wpack"} <= roles <= {
        "kernel", "pack", "wpack", "dlt", "other"}
    assert {s[1] for name in ops for s in scopes[name]} == {
        f"conv{i}" for i, n in enumerate(spec.nodes)
        if isinstance(n, ConvLayer)}


@pytest.mark.parametrize("base,variant,n,c,h,k,stride", [
    # darknet53 stage-2 3x3 (64 -> 128 at 64²) through Winograd F(4x4)
    ("winograd-4x4-3x3", "mm-128x128x256", 8, 64, 64, 128, 1),
    # its stage-1 3x3 (32 -> 64 at 128²), im2col with a per-image GEMM
    ("im2col-copy-ab-ki", "mm-256x256x256", 8, 32, 128, 64, 1),
    # the stride-2 opening of stage 4 (256 -> 512, 32² -> 16²), fused
    ("im2col-scan-ab-ki", "conv-bk128", 8, 256, 32, 512, 2),
], ids=["wino-s1", "im2col-mm", "conv-bk-s2"])
def test_padded_step_compiles(base, variant, n, c, h, k, stride, one_chip,
                              monkeypatch):
    """A padded 3x3 at darknet53's widths through ``conv_variant_call``
    with a residual fused: the kernel compiles, and the compiled program
    holds ops under the step's ``pack/pad`` scope."""
    from repro.kernels.im2col_gemm import ops as conv_ops
    from repro.kernels.matmul import ops as mm_ops
    from repro.kernels.winograd import ops as wino_ops
    from repro.primitives.conv import REGISTRY
    from repro.primitives.variants import conv_variant_call

    oh = (h + 2 - 3) // stride + 1

    def fn(x, w, r):
        with jax.default_matmul_precision("float32"):
            with jax.named_scope("conv1"):
                return conv_variant_call(REGISTRY[base], variant, x, w,
                                         stride, residual=r, pad=1)

    for mod in (mm_ops, wino_ops, conv_ops):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)
    jax.clear_caches()
    try:
        text = _compile_text(fn, one_chip, (n, c, h, h), (k, c, 3, 3),
                             (n, k, oh, oh))
    finally:
        jax.clear_caches()
    assert text.count(KERNEL) >= 1
    # the kernel's own jit sits between the step and its roles
    assert re.search(r'op_name="[^"]*conv1/(jit\([^)]*\)/)?pack/pad/', text)

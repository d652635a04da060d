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

import os

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

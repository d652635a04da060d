"""Kernel work functions against counts made by hand."""
from bench import registry


def test_matmul_op_counts_by_hand():
    work = registry.load_module("work", "matmul_op").work
    # 512x128 @ 128x1024: 2*512*1024*128 FLOPs; 4-byte operands and result
    flops, nbytes = work([("f32", (512, 128)), ("f32", (128, 1024))],
                         [("f32", (512, 1024))])
    assert flops == 134_217_728
    assert nbytes == 4 * (512 * 128 + 128 * 1024 + 512 * 1024) == 2_883_584


def test_matmul_op_epilogue_adds_one_op_per_output_each():
    work = registry.load_module("work", "matmul_op").work
    flops, nbytes = work([("f32", (8, 4)), ("f32", (4, 16)), ("f32", (1, 8)),
                          ("f32", (8, 16))], [("f32", (8, 16))])
    assert flops == 2 * 8 * 16 * 4 + 2 * 8 * 16 == 1280
    assert nbytes == 4 * (32 + 64 + 8 + 128 + 128) == 1440


def test_winograd_point_gemm_counts_by_hand():
    work = registry.load_module("work", "winograd_conv_batch").work
    # 36 points, K=64, C=32, T=25 tiles, batch 2: 2*2*36*64*32*25 FLOPs
    flops, nbytes = work([("f32", (36, 64, 32)), ("f32", (2, 36, 32, 25))],
                         [("f32", (2, 36, 64, 25))])
    assert flops == 7_372_800
    assert nbytes == 4 * (36 * 64 * 32 + 2 * 36 * 32 * 25
                          + 2 * 36 * 64 * 25) == 986_112


def test_work_refuses_shapes_that_do_not_agree():
    import pytest
    work = registry.load_module("work", "matmul_op").work
    with pytest.raises(ValueError):
        work([("f32", (8, 4)), ("f32", (5, 16))], [("f32", (8, 16))])

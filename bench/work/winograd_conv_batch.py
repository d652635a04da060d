"""``winograd_conv_batch`` (``kernels/winograd``): the batched Winograd
point-GEMM, u (P, K, C) shared over the batch @ v (N, P, C, T) → (N, P, K, T).

FLOPs: 2·N·P·K·C·T, the multiply-adds of the point-GEMM itself (the input
and output transforms run outside the kernel, in XLA). Bytes: every
operand read once and the result written once."""
from math import prod


def work(operands, results):
    (_, u), (_, v) = operands
    (_, out), = results
    n, p, c, t = v
    if u != (p, out[2], c) or out != (n, p, u[1], t):
        raise ValueError(f"winograd_conv_batch shapes do not agree: "
                         f"{operands} -> {results}")
    return 2 * prod(out) * c, _bytes(operands) + _bytes(results)


def _bytes(shapes):
    size = {"f32": 4, "bf16": 2, "s8": 1, "f16": 2}
    return sum(size[dt] * prod(dims) for dt, dims in shapes)

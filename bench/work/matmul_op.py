"""``matmul_op`` (``kernels/matmul``): x (..., M, K) @ y (..., K, N) with an
optional bias (1, M) and residual (..., M, N) added in the kernel's store.

FLOPs: 2·M·N·K per matrix, plus one add per output element for each of
bias and residual. Bytes: every operand read once and the result written
once, the least traffic any schedule of the call can have."""
from math import prod


def work(operands, results):
    (_, x), (_, y) = operands[:2]
    (_, out), = results
    k = x[-1]
    if y[-2] != k or out[-2:] != (x[-2], y[-1]):
        raise ValueError(f"matmul_op shapes do not agree: {operands} -> {results}")
    flops = 2 * prod(out) * k + (len(operands) - 2) * prod(out)
    return flops, _bytes(operands) + _bytes(results)


def _bytes(shapes):
    size = {"f32": 4, "bf16": 2, "s8": 1, "f16": 2}
    return sum(size[dt] * prod(dims) for dt, dims in shapes)

"""Pallas kernels' share of their roofline: the sum over kernel calls of
max(FLOPs / bf16 peak, bytes / HBM bandwidth), from each call's operand and
result shapes (``work/<family>.py``), over the kernels' device time."""


def read(run):
    tr = run.trace
    if not tr or not tr["kernel_s"]:
        return None
    return 100.0 * tr["bound_s"] / tr["kernel_s"]

"""Pallas kernels' share of the device's busy time (``tpu_custom_call`` ops);
the rest is XLA glue: im2col patches, transposes, pads, Winograd transforms."""


def read(run):
    tr = run.trace
    if not tr or not tr["busy_s"] or not tr["kernel_calls"]:
        return None
    return 100.0 * tr["kernel_s"] / tr["busy_s"]

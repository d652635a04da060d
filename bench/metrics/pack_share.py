"""Share of the device's busy time in ops under a plan step's ``pack`` scope:
activation packing around the kernels: strided slices, im2col patches,
transposes to and from (C, N·H·W), pads, Winograd input and output
transforms (``pack`` scopes). Op durations summed as ``pallas_share`` sums
kernel time."""
from bench import spans


def read(run):
    return spans.role_share(run, "pack")

"""Whole plan step's share of the chip's bf16 peak: direct-convolution FLOPs
of the shapes the network really runs, times the images served in the
window as ``img_per_s`` counts them, over window seconds times peak
FLOP/s. The same work counts the same whatever primitive implements it."""


def read(run):
    done = run.images_in_window()
    if not done:
        return None
    peak = run.peaks["bf16_flops_per_s"]
    return 100.0 * run.flops_per_image * done / ((run.t1 - run.t0) * peak)

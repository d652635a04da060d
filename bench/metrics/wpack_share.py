"""Share of the device's busy time in ops under a plan step's ``wpack`` scope:
weight preparation run on every dispatch: weight reshapes and pads, the
Winograd weight transform (``wpack`` scopes). Op durations summed as
``pallas_share`` sums kernel time."""
from bench import spans


def read(run):
    return spans.role_share(run, "wpack")

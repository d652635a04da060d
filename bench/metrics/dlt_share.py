"""Share of the device's busy time in ops under a plan step's ``dlt`` scope:
the plan's edge permutations and crops (``dlt`` scopes of ``plan._emit``).
Op durations summed as ``pallas_share`` sums kernel time."""
from bench import spans


def read(run):
    return spans.role_share(run, "dlt")

"""Arithmetic that several metric readers share."""


def idle_share(run):
    """Percent of the traced window with no operation on the device."""
    tr = run.trace
    if not tr or not run.window_traced_s or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / run.window_traced_s)

"""Device busy time of the traced window per dispatch claimed in it."""


def read(run):
    n = run.dispatches()
    if not run.trace or not n or not run.trace["busy_s"]:
        return None
    return run.trace["busy_s"] / n * 1e3

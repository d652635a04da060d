"""Images per dispatch over the dispatches claimed in the window."""


def read(run):
    n = run.dispatches()
    return run.images_dispatched() / n if n else None

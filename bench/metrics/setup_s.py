"""Set-up: process start to the first request of the window, compilation
included (host clock)."""


def read(run):
    return run.setup_s

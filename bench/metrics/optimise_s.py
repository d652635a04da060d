"""Seconds in ``pipeline.optimise``: profiling surface, performance model
and PBQP selection (host clock around the call)."""


def read(run):
    return run.optimise_s

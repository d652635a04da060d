"""Seconds in ``OptimisedServer.register``: compiling (or loading from the
persistent cache) and warming one dispatch handle per pow2 bucket (host
clock around the call)."""


def read(run):
    return run.compile_s

"""Milliseconds the serving worker waited for a batch window to close
(``serve.window`` spans) in the traced window, per dispatch (``serve.execute``
spans): the batch window's share of a request's latency."""
from bench import spans


def read(run):
    sp = spans.of(run)
    if sp is None or not sp["host"]["dispatches"]:
        return None
    return sp["host"]["window_ms"] / len(sp["host"]["dispatches"])

"""Median latency, scheduled send to completion, over every request due in
the window; a failed or refused request counts as +inf (host clock)."""
from bench.serve import percentile


def read(run):
    return percentile(run.latencies_s(), 50) * 1e3

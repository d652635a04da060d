"""99th-percentile latency over every request due in the window, on the
same terms as ``p50_ms`` (host clock)."""
from bench.serve import percentile


def read(run):
    return percentile(run.latencies_s(), 99) * 1e3

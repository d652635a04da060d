"""99th percentile of the time the window's requests waited in the serving
queue, submit to claim (``Ticket.dispatched_s - submitted_s``)."""
from bench.serve import percentile


def read(run):
    waits = [r.ticket.dispatched_s - r.ticket.submitted_s
             for r in run.answered()]
    return percentile(waits, 99) * 1e3 if waits else None

"""Open loop: Poisson arrivals at ``rate_per_s``, sent on schedule whether
or not earlier requests have been answered (MLPerf's Server scenario).

Every seed gets the same arrivals in another order: the gaps are the
exponential distribution's quantiles at evenly spaced levels, scaled to
fill the window exactly, and the seed shuffles them. So the seed changes
which gaps come together, never how much work a run offers."""
from __future__ import annotations

import time

import numpy as np


def arrivals(mix, seed, seconds):
    """Offsets in [0, seconds) of the requests a window of that length sends."""
    rate = float(mix["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    np.random.default_rng(seed).shuffle(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds / gaps.sum())


def drive(submit, mix, seed, t0, t1, clock):
    """Send each arrival at its time; ``submit(due_s)`` returns its ticket."""
    for off in arrivals(mix, seed, t1 - t0):
        due = t0 + off
        wait = due - clock()
        if wait > 0:
            time.sleep(wait)
        submit(due)

"""Closed loop: keep ``outstanding`` requests in flight, and send the next
one as soon as the oldest has been answered. Each request is due when it
is sent. ``outstanding: 1`` is MLPerf's SingleStream; ``2 × max_batch``
keeps every dispatch of MLPerf's Offline full."""
from __future__ import annotations

from collections import deque


def drive(submit, mix, seed, t0, t1, clock):
    """Send until ``t1``; ``submit(due_s)`` sends one request and returns
    its ticket. Requests still open at ``t1`` are left to the caller."""
    inflight = deque()
    while True:
        while len(inflight) < mix["outstanding"]:
            now = clock()
            if now >= t1:
                return
            inflight.append(submit(now))
        left = t1 - clock()
        if left <= 0:
            return
        if inflight[0].wait(left):
            while inflight and inflight[0].done:
                inflight.popleft()

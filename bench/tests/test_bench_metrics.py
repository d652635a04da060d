"""The end-to-end metrics' arithmetic on made-up tickets, and the open-loop
generator's arrivals."""
import types

import numpy as np
import pytest

from bench import registry
from bench.serve import Request, Run


def _req(due, dispatched, completed):
    t = types.SimpleNamespace(done=True, error=None, rejected=False,
                              submitted_s=due, dispatched_s=dispatched,
                              completed_s=completed)
    return Request(img=0, due_s=due, sent_s=due, ticket=t)


def _read(name, run):
    return registry.load_module("metrics", name).read(run)


def test_img_per_s_counts_the_batch_at_the_close_in_part():
    """Batches of 4 every 0.3 s from t0 = 0: a window that closes a third
    of the way through a batch counts a third of it, so the rate has no
    step of one batch."""
    reqs = [_req(0.0, 0.3 * b, 0.3 * (b + 1)) for b in range(4)
            for _ in range(4)]
    run = Run(t0=0.0, t1=1.0, requests=reqs)
    assert run.images_in_window() == pytest.approx(4 * (3 + 1 / 3))
    assert _read("img_per_s", run) == pytest.approx(4 / 0.3)
    longer = Run(t0=0.0, t1=1.1, requests=reqs)
    assert _read("img_per_s", longer) == pytest.approx(4 / 0.3)


def test_img_per_s_leaves_out_failed_answers_and_work_outside():
    reqs = [_req(0.0, 0.0, 0.5), _req(0.0, 1.0, 1.5), _req(0.0, 0.2, 0.4)]
    reqs[2].ticket.error = RuntimeError("lost")
    run = Run(t0=0.0, t1=1.0, requests=reqs)
    assert _read("img_per_s", run) == pytest.approx(1.0)


@pytest.mark.parametrize("name,q", [("p50_ms", 50), ("p99_ms", 99)])
def test_a_failed_request_is_an_infinite_latency(name, q):
    reqs = [_req(0.0, 0.0, (i + 1) * 1e-3) for i in range(199)]
    reqs.append(_req(0.0, 0.0, 0.001))
    reqs[-1].ticket.done = False
    run = Run(t0=0.0, t1=1.0, requests=reqs)
    want = {50: 100.0, 99: 198.0}[q]
    assert _read(name, run) == pytest.approx(want)
    for r in reqs[:3]:
        r.ticket.done = False
    assert _read("p99_ms", run) == np.inf


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 99, 2 ** 40])
def test_poisson_offers_the_same_arrivals_to_every_seed(seed):
    """The seed orders the gaps; the count, the span and the set of gaps
    stay the same."""
    poisson = registry.load_module("traffic", "poisson")
    mix = {"rate_per_s": 120.0}
    a, b = poisson.arrivals(mix, 1, 30.0), poisson.arrivals(mix, seed, 30.0)
    assert len(a) == len(b) == 3600
    assert a[0] == b[0] == 0.0 and a[-1] < 30.0 and b[-1] < 30.0
    assert np.all(np.diff(b) > 0)
    ga = np.sort(np.diff(np.append(a, 30.0)))
    gb = np.sort(np.diff(np.append(b, 30.0)))
    np.testing.assert_allclose(ga, gb, rtol=1e-9, atol=1e-12)
    assert np.mean(np.diff(b)) == pytest.approx(1 / 120.0, rel=0.01)

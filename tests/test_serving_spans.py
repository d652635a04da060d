"""Host spans and claim counters of the served path (DESIGN.md §8.6): the
``serve.*`` spans nest as documented and carry the dispatch ids the tickets
carry, a run with no profiler session serves and counts exactly what a
traced run does, and ``stats()["claims"]`` counts each claim by its
reason."""
from __future__ import annotations

import numpy as np
import pytest

import jax

from repro.models import cnn_zoo
from repro.primitives.plan import heuristic_assignment
from repro.service import OptimisedNetwork, OptimisedServer
from repro.service.serving.queues import BatchGroup, NetQueue, Ticket

PHASES = ["serve.assemble", "serve.call", "serve.device", "serve.fetch",
          "serve.validate", "serve.deliver"]


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += float(dt)


@pytest.fixture(scope="module")
def net():
    spec = cnn_zoo.get("edge_cnn")
    return OptimisedNetwork.from_assignment(spec, heuristic_assignment(spec),
                                            predicted_cost_s=2e-3)


def _images(spec, n, seed=0):
    n0 = spec.nodes[0]
    return np.random.default_rng(seed).standard_normal(
        (n, n0.c, n0.im, n0.im)).astype(np.float32)


def test_claim_reason_of_a_queue():
    q = NetQueue(depth=8, batch_cap=2, max_wait_s=1.0)
    assert q.claim_reason(0.0, drain=True) is None          # empty
    q.push(Ticket(net="n", x=np.zeros(1), submitted_s=10.0))
    assert q.claim_reason(10.5) is None                     # window open
    assert q.claim_reason(10.5, drain=True) == "drain"
    assert q.claim_reason(11.0) == "window"
    q.push(Ticket(net="n", x=np.zeros(1), submitted_s=10.9))
    assert q.claim_reason(10.9) == q.claim_reason(11.0, drain=True) == "full"
    q.take(2)
    q.push_group(BatchGroup(tickets=[Ticket(net="n", x=np.zeros(1))],
                            xs=np.zeros((1, 1))))
    assert q.claim_reason(0.0) == "group"


def test_claims_counted_by_reason(net):
    """One full, one window and one drain claim on the injected clock; the
    tickets of each claim carry that claim's dispatch id, in claim order."""
    clock = FakeClock()
    server = OptimisedServer(max_batch=2, latency_budget_ms=1e9,
                             max_wait_ms=10.0, clock=clock)
    server.register(net)
    xs = _images(net.spec, 4)
    full = [server.submit(net.net, x) for x in xs[:2]]
    assert server.pump(drain=False) == 1                    # full batch
    win = server.submit(net.net, xs[2])
    assert server.pump(drain=False) == 0
    clock.advance(0.011)
    assert server.pump(drain=False) == 1                    # window expired
    drained = server.submit(net.net, xs[3])
    assert server.pump(drain=True) == 1                     # window open
    st = server.stats(net.net)
    assert st["claims"] == {"full": 1, "window": 1, "drain": 1, "group": 0}
    assert [t.dispatch for t in full + [win, drained]] == [0, 0, 1, 2]
    assert all(t.done and t.error is None for t in full + [win, drained])
    assert "busy_s" not in st and "images_per_s" not in st


def _serve(net, weights, xs):
    """Serve ``xs`` in pump mode on a clock that stands still (deterministic
    batches and claims): the outputs, the counters, the dispatch handles."""
    server = OptimisedServer(max_batch=4, latency_budget_ms=1e9,
                             clock=FakeClock())
    server.register(net, weights=weights)
    ts = [server.submit(net.net, x) for x in xs]
    server.pump()
    st = server.stats(net.net)
    return [t.result for t in ts], {k: st[k] for k in (
        "dispatches", "images", "padded", "claims", "rejected", "retries",
        "failed_dispatches", "failed_tickets", "fallback_images")}, \
        server.plan_handles(net.net)


def test_no_profiler_session_serves_and_counts_the_same(net, tmp_path):
    """Outside a profiler session the spans change nothing: the outputs
    are the dispatch handle's own, bit for bit, and outputs and counters
    are those of the same requests served under a profiler session."""
    from repro.primitives.executor import make_weights
    weights = make_weights(net.spec)
    xs = _images(net.spec, 7, seed=1)
    plain, counts, handles = _serve(net, weights, xs)
    with jax.profiler.trace(str(tmp_path)):
        traced, traced_counts, _ = _serve(net, weights, xs)
    assert counts == traced_counts == {
        "dispatches": 2, "images": 7, "padded": 1,
        "claims": {"full": 1, "window": 0, "drain": 1, "group": 0},
        "rejected": 0, "retries": 0, "failed_dispatches": 0,
        "failed_tickets": 0, "fallback_images": 0}
    direct = np.concatenate([
        np.asarray(handles[4](xs[:4], weights)),
        np.asarray(handles[4](np.concatenate([xs[4:], xs[6:]]),
                              weights))[:3]])
    for a, b, d in zip(plain, traced, direct):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, d)


def test_spans_nest_and_carry_dispatch_ids(net, tmp_path):
    """A worker-pool server traced by ``jax.profiler``: every dispatch is
    one ``serve.execute`` span holding its phases once each, in order; its
    ``dispatch`` metadata is the id its tickets carry; the worker waited
    for each lone request's batch window inside ``serve.window`` (a window
    long enough that the worker is waiting before it closes)."""
    from bench import trace as T
    xs = _images(net.spec, 5, seed=2)
    server = OptimisedServer(max_batch=4, latency_budget_ms=1e9,
                             max_wait_ms=50.0, workers=1)
    server.register(net)
    tickets = []
    with jax.profiler.trace(str(tmp_path)):
        for x in xs:                              # one request at a time
            tickets.append(server.submit(net.net, x))
            assert tickets[-1].wait(30.0)
    server.stop()
    assert all(t.error is None for t in tickets)
    assert server.stats(net.net)["claims"]["window"] == len(xs)

    lines = [ln for p in T.load(tmp_path)["planes"]
             if not p["name"].startswith("/device:") for ln in p["lines"]]
    spans = {ln["name"] + str(i): sorted(
        (ev for ev in ln["events"] if ev[0].startswith("serve.")),
        key=lambda ev: ev[1]) for i, ln in enumerate(lines)}
    execute = [(k, ev) for k, evs in spans.items() for ev in evs
               if ev[0] == "serve.execute"]
    assert len(execute) == len(xs)
    for k, (_, s, d) in execute:
        inside = [ev[0] for ev in spans[k]
                  if s <= ev[1] and ev[1] + ev[2] <= s + d
                  and ev[0] != "serve.execute"]
        assert inside == PHASES
    names = [ev[0] for evs in spans.values() for ev in evs]
    assert names.count("serve.submit") == len(xs)
    assert names.count("serve.window") >= len(xs)

    ids = set()
    data = jax.profiler.ProfileData.from_file(
        str(sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))[-1]))
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "serve.execute":
                    meta = dict(ev.stats)
                    assert (int(meta["bucket"]), int(meta["images"])) == (1, 1)
                    ids.add(int(meta["dispatch"]))
    assert ids == {t.dispatch for t in tickets}
    assert len(ids) == len(xs)

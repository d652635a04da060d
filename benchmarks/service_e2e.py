"""Service-layer end to end: cold vs warm optimise time, served img/s,
concurrent multi-network serving vs the serial pump baseline, zero-cost
drift recalibration from served traffic, predicted-cost cross-backend
routing, deadline-aware batch windows, and availability under injected
faults (DESIGN.md §11).

Cold pass: a fresh artifact store — pretrain the base platform model,
calibrate onto the target platform, PBQP-select. Warm pass: identical calls
against the now-populated store — every model and the selection must come
back from disk, selecting the *same assignment*, ≥10x faster (the paper's
Table 4 "seconds, not hours" claim as a regression gate). Then the optimised
network is served through ``OptimisedServer`` for a throughput figure, and a
multi-network load (optimised + fixed-primitive variants of the net) is
served twice — synchronous ``pump()`` vs the worker-pool serving core — to
measure the concurrency win and p50/p99 queueing latency.

The recalibration row drives a drifting platform until the serving loop
detects the drift and hot-swaps a recalibration built from its OWN served
observations (DESIGN.md §8.5), then times the fresh-profiling alternative on
the same drifted platform. Profiling cost is made visible by charging each
``profile()``'d config a nominal wall-clock cost (a real device pays
repeats × runtime per config; the analytic simulator would otherwise hide
exactly the cost the served-sample path eliminates).

The fleet_recal row (DESIGN.md §14) runs the recalibration story across two
hosts sharing one simulated object-store bucket: host A drifts,
recalibrates from served traffic, and publishes the evidence under the
platform's pool fingerprint; host B warm-starts everything from the shared
bucket and hot-swaps a recalibration built from A's pooled evidence alone —
gated on zero freshly profiled configs for B and byte-identical post-swap
assignments.

The multibackend row optimises the same net for several backends against
one artifact store (per-backend selections, checked byte-reproducible on a
second warm optimise), then serves one request stream three ways: each
backend alone, and all backends registered behind one logical net with the
predicted-cost router (DESIGN.md §9) spreading batches across them. Each
backend is charged its nominal device time per image as wall-clock (same
reasoning as the recalibration row: one host CPU standing in for several
devices would hide exactly the parallelism being measured). The gate
requires routed throughput ≥ the best single backend.

The deadline row serves a paced lone-request load twice: an effectively
unbounded latency budget (batch windows run to their static cap) vs a tight
budget (windows capped at budget − predicted execution, shrunk further by
the drift monitor when observed p99 queueing exceeds the budget).

The frontend_scaling row serves the same warmed load through the thread
front end (submitter threads, GIL-bound batch assembly) and through the
multi-process shared-memory front end (DESIGN.md §12: intake processes
writing payloads once into slab buckets, workers executing zero-copy
views), equal workers, then re-drives the shm path under an injected fault
plan — the chaos accounting identity (zero lost, zero duplicated) must
hold on slabs too. The bucket_cost row serves pow2-bucket bursts of ≥ 2 zoo
nets and scores the batch-shape-aware per-image cost model
(``BucketScaleHead``, §12.3) against the batch-size-invariant linear model
on held-out served latencies; the head must be strictly more accurate on
every net.

Writes ``BENCH_service.json``. Exits nonzero if the warm pass is < 10x
faster than cold, picks a different assignment, concurrent multi-network
throughput falls below the serial baseline (parity with a 15% noise
allowance on single-core runners, where the worker pool has no hardware
to overlap on), the drift recalibration is not
mostly served-sampled (≥ 50%) and faster than fresh profiling, the fleet
row's second host fails to warm-start, profiles any config freshly, or
diverges from host A's assignment, routed
multi-backend throughput falls below the best single backend, the
deadline-aware window misses the budget on the smoke load, or the
availability row drops below 99% served / loses / duplicates tickets under
its injected raise+hang+slowdown fault plan, the process front end falls
below the thread front end (parity allowance on ≤2-core runners), the shm
chaos drive loses or duplicates tickets, or the bucket-aware cost model is
not strictly more accurate than linear on every listed net — the CI smoke
gates (``--smoke``).

Run:  PYTHONPATH=src:. python benchmarks/service_e2e.py [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, Optional

import numpy as np

from benchmarks.common import emit

OUT_PATH = os.environ.get("REPRO_BENCH_SERVICE_JSON", "BENCH_service.json")


def optimise_pass(store_root: str, *, net: str, platform: str, base: str,
                  max_triplets: int, max_iters: int) -> Dict:
    """One full optimise run against ``store_root``; fresh Platform objects
    so nothing is warm except what the store provides."""
    from repro.service import ArtifactStore, get_platform, optimise

    store = ArtifactStore(store_root)
    t0 = time.perf_counter()
    base_models = get_platform(base, max_triplets=max_triplets).pretrain(
        "nn2", store=store, max_iters=max_iters)
    opt = optimise(net, get_platform(platform, max_triplets=max_triplets),
                   store=store, base=base_models, mode="factor",
                   executable=True)
    seconds = time.perf_counter() - t0
    return {"seconds": seconds, "opt": opt,
            "warm": base_models.warm and opt.warm}


def serve_pass(opt, requests: int, budget_ms: float) -> Dict:
    from repro.service import OptimisedServer

    server = OptimisedServer(latency_budget_ms=budget_ms)
    server.register(opt)
    n0 = opt.spec.nodes[0]
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((requests, n0.c, n0.im, n0.im)).astype(np.float32)
    server.serve(opt.net, xs)                          # warm the plan cache
    s0 = server.stats(opt.net)
    t0 = time.perf_counter()
    server.serve(opt.net, xs)
    dt = time.perf_counter() - t0
    s = server.stats(opt.net)                          # delta = timed pass only
    return {"requests": requests, "seconds": dt,
            "images_per_s": requests / dt, "batch_cap": s["batch_cap"],
            "dispatches": s["dispatches"] - s0["dispatches"],
            "padded": s["padded"] - s0["padded"]}


def _multinet_opts(opt) -> list:
    """The multi-network load: the optimised assignment plus two
    fixed-primitive variants of the same topology (an A/B serving shape —
    three models live behind one server)."""
    from repro.models.cnn_zoo import ConvLayer
    from repro.primitives.plan import heuristic_assignment
    from repro.service import OptimisedNetwork

    spec = opt.spec
    heur = OptimisedNetwork.from_assignment(
        spec, heuristic_assignment(spec), net=f"{opt.net}@heuristic",
        predicted_cost_s=opt.predicted_cost_s)
    fixed_asg = {i: ("conv-1x1-gemm-ab-ki" if getattr(n, "f", 0) == 1
                     else "direct-sum2d") if isinstance(n, ConvLayer) else "chw"
                 for i, n in enumerate(spec.nodes)}
    fixed = OptimisedNetwork.from_assignment(
        spec, fixed_asg, net=f"{opt.net}@fixed",
        predicted_cost_s=opt.predicted_cost_s)
    return [opt, heur, fixed]


def multinet_pass(opts, weights, requests_per_net: int, budget_ms: float,
                  *, workers: int, max_wait_ms: float) -> Dict:
    """Serve ``requests_per_net`` per network, submissions interleaved
    round-robin. ``workers=0`` is the serial pump baseline; ``workers>0`` the
    concurrent serving core. Returns throughput + queueing percentiles."""
    import numpy as np
    from repro.service import OptimisedServer

    server = OptimisedServer(max_batch=8, latency_budget_ms=budget_ms,
                             workers=workers, max_wait_ms=max_wait_ms,
                             queue_depth=4096)
    for o in opts:
        server.register(o, weights=weights)
    n0 = opts[0].spec.nodes[0]
    rng = np.random.default_rng(1)
    xs = rng.standard_normal(
        (requests_per_net, n0.c, n0.im, n0.im)).astype(np.float32)

    tickets = []
    t0 = time.perf_counter()
    for i in range(requests_per_net):
        for o in opts:
            tickets.append(server.submit(o.net, xs[i]))
    if workers:
        for t in tickets:
            t.wait(300.0)
    else:
        while any(not t.done for t in tickets):
            server.pump()
    dt = time.perf_counter() - t0
    # a ticket that never finished (wait timed out) is a failure too
    failed = [t for t in tickets if t.error or not t.done]
    per_net = {o.net: server.stats(o.net) for o in opts}
    server.stop()
    waits_p50 = max(s["queue_wait_p50_ms"] for s in per_net.values())
    waits_p99 = max(s["queue_wait_p99_ms"] for s in per_net.values())
    return {"workers": workers, "requests": len(tickets), "seconds": dt,
            "failed": len(failed),
            "images_per_s": len(tickets) / dt,
            "queue_wait_p50_ms": waits_p50, "queue_wait_p99_ms": waits_p99,
            "dispatches": sum(s["dispatches"] for s in per_net.values()),
            "padded": sum(s["padded"] for s in per_net.values())}


def concurrent_pass(opt, requests_per_net: int, budget_ms: float,
                    workers: int, max_wait_ms: float) -> Dict:
    """Serial-pump vs worker-pool serving of the same 3-network load."""
    from repro.primitives.executor import make_weights
    from repro.service import OptimisedServer

    opts = _multinet_opts(opt)
    weights = make_weights(opt.spec)
    # warm every (net, pow2-bucket) plan once: the global plan cache serves
    # both measured passes, so neither pays jit compile
    warm = OptimisedServer(max_batch=8, latency_budget_ms=budget_ms)
    for o in opts:
        warm.register(o, weights=weights)
    n0 = opt.spec.nodes[0]
    rng = np.random.default_rng(2)
    for o in opts:
        for b in (1, 2, 4, 8):
            warm.serve(o.net, rng.standard_normal(
                (b, n0.c, n0.im, n0.im)).astype(np.float32))

    serial = multinet_pass(opts, weights, requests_per_net, budget_ms,
                           workers=0, max_wait_ms=max_wait_ms)
    conc = multinet_pass(opts, weights, requests_per_net, budget_ms,
                         workers=workers, max_wait_ms=max_wait_ms)
    return {"networks": [o.net for o in opts], "serial": serial,
            "concurrent": conc,
            "speedup": conc["images_per_s"] / serial["images_per_s"]}


def multibackend_pass(store_root: str, *, net: str, backends, base: str,
                      max_triplets: int, max_iters: int, requests: int,
                      budget_ms: float, workers: int,
                      device_s: float = 0.012) -> Dict:
    """Cross-backend routed serving (DESIGN.md §9) vs each single backend
    alone on the same workload, with per-backend selections warm-started
    from one ``ArtifactStore`` and checked reproducible.

    Every listed backend executes on THIS host's CPU, which would hide
    exactly the device parallelism the router exploits (and on a one-core
    runner there is none to find). So, in the style of the recalibration
    row's ``ChargedPlatform``, each backend is charged a nominal device
    time per dispatched image — ``device_s`` for the first backend, halved
    per position after it — as a wall-clock sleep inside ``_run_plan``.
    Sleeps overlap across worker threads the way independent devices do,
    and the distinct speeds make the router's predicted-cost split (fast
    device gets the larger share) part of what the gate measures."""
    from repro.primitives.executor import make_weights
    from repro.service import (ArtifactStore, OptimisedServer, get_platform,
                               optimise)

    charge = {b: device_s / (2 ** i) for i, b in enumerate(backends)}

    class DeviceChargedServer(OptimisedServer):
        def _run_plan(self, o, xs, weights):
            out = super()._run_plan(o, xs, weights)
            time.sleep(charge.get(o.platform.name, 0.0) * xs.shape[0])
            return out

    store = ArtifactStore(store_root)
    base_models = get_platform(base, max_triplets=max_triplets).pretrain(
        "nn2", store=store, max_iters=max_iters)

    def optimise_backend(b):
        return optimise(net, get_platform(b, max_triplets=max_triplets),
                        store=store, base=base_models, mode="factor",
                        executable=True)

    opts = {b: optimise_backend(b) for b in backends}
    # reproducibility: a second optimise per backend must warm-load the
    # SAME assignment from the store (backend-keyed artifacts, no collision)
    rerun = {b: optimise_backend(b) for b in backends}
    repro_ok = all(rerun[b].warm and rerun[b].assignment == opts[b].assignment
                   for b in backends)

    spec = opts[backends[0]].spec
    weights = make_weights(spec)
    n0 = spec.nodes[0]
    rng = np.random.default_rng(5)
    xs = rng.standard_normal((requests, n0.c, n0.im, n0.im)).astype(np.float32)

    def timed(members) -> Dict:
        server = DeviceChargedServer(max_batch=8, latency_budget_ms=budget_ms,
                                     workers=workers, max_wait_ms=2.0,
                                     queue_depth=4096)
        for bname, o in members:
            server.register(o, weights=weights, backend=bname,
                            max_inflight=1)
        # warm every backend through its exact state key: compiles each
        # (assignment, pow2-bucket) plan AND primes the router's observed
        # per-image cost so the timed burst routes on served truth
        for bname, o in members:
            key = o.net if bname is None else f"{o.net}#{bname}"
            for k in (1, 2, 4, 8):
                server.serve(key, xs[:k])
        t0 = time.perf_counter()
        tickets = [server.submit(net, x) for x in xs]
        for t in tickets:
            t.wait(300.0)
        dt = time.perf_counter() - t0
        failed = sum(1 for t in tickets if t.error or not t.done)
        s = server.stats(net)
        server.stop()
        out = {"seconds": dt, "images_per_s": len(xs) / dt,
               "failed": failed}
        if "backends" in s:
            out["per_backend"] = {
                b: {"dispatches": bs["dispatches"], "images": bs["images"],
                    "queue_wait_p50_ms": bs["queue_wait_p50_ms"],
                    "queue_wait_p99_ms": bs["queue_wait_p99_ms"]}
                for b, bs in s["backends"].items()}
        return out

    single = {b: timed([(None, opts[b])]) for b in backends}
    routed = timed([(b, opts[b]) for b in backends])
    best = max(single, key=lambda b: single[b]["images_per_s"])
    ratio = routed["images_per_s"] / single[best]["images_per_s"]
    return {"backends": list(backends), "single": single, "routed": routed,
            "best_single": best, "routed_vs_best_single": ratio,
            "reproducible_from_store": repro_ok}


def _charged_platform(name: str, charge_s: float, max_triplets: int):
    """A SimulatedPlatform charging wall-clock per profiled config: a real
    device pays repeats × runtime for every measurement; the analytic
    simulator answering instantly would hide the cost §8.5/§14.3
    eliminate. ``profiled_configs`` counts every freshly measured config."""
    from repro.service.platforms import SimulatedPlatform

    class ChargedPlatform(SimulatedPlatform):
        def __init__(self, name, charge_s, **kw):
            super().__init__(name, **kw)
            self.charge_s = charge_s
            self.profiled_configs = 0

        def profile(self, configs):
            cfgs = np.atleast_2d(np.asarray(configs))
            self.profiled_configs += len(cfgs)
            time.sleep(self.charge_s * len(cfgs))
            return super().profile(cfgs)

    return ChargedPlatform(name, charge_s, max_triplets=max_triplets)


def _drifting_server(**kw):
    """An OptimisedServer whose plan execution slows down by the network
    platform's ``time_scale`` (sleep proportional to the excess), so
    observed per-image latency rises exactly like on a slower machine."""
    from repro.service import OptimisedServer

    class DriftingServer(OptimisedServer):
        def _run_plan(self, o, xs, weights):
            out = super()._run_plan(o, xs, weights)
            scale = getattr(o.platform, "time_scale", 1.0) or 1.0
            if scale != 1.0:
                time.sleep(0.02 * xs.shape[0] * (scale - 1.0))
            return out

    return DriftingServer(**kw)


def recalibration_pass(opt, *, sample_n: int, charge_s: float = 0.05,
                       timeout_s: float = 120.0) -> Dict:
    """Drift → detect → recalibrate-from-served-traffic → hot_swap, timed
    against the fresh-profiling alternative on the same drifted platform."""
    from repro.service import make_recalibrator, reoptimise

    platform = _charged_platform(opt.platform.name, charge_s,
                                 opt.platform.max_triplets)
    opt = dataclasses.replace(opt, platform=platform)

    timing: Dict = {}
    inner = make_recalibrator(sample_n=sample_n, mode="factor")

    def recalibrate(o, served=None):
        p0 = platform.profiled_configs
        t0 = time.perf_counter()
        new = inner(o, served=served)
        timing["served_seconds"] = time.perf_counter() - t0
        timing["served_profiled_configs"] = platform.profiled_configs - p0
        return new

    server = _drifting_server(
        max_batch=4, latency_budget_ms=1e9, workers=2, max_wait_ms=3.0,
        drift_threshold=1.5, drift_alpha=0.5, drift_calib_obs=2,
        recalibrate=recalibrate)
    server.register(opt)
    n0 = opt.spec.nodes[0]
    rng = np.random.default_rng(3)
    xs = rng.standard_normal((4, n0.c, n0.im, n0.im)).astype(np.float32)

    # healthy phase: until the reference and the observation buffer exist
    deadline = time.time() + timeout_s
    while (server.stats(opt.net)["observed_dispatches"] < 6
           and time.time() < deadline):
        server.serve(opt.net, xs)
    platform.time_scale = 4.0          # the machine gets 4x slower
    platform.invalidate_datasets()
    while (server.stats(opt.net)["recalibrations"] == 0
           and time.time() < deadline):
        server.serve(opt.net, xs)
    st = server.stats(opt.net)
    server.stop()

    # the alternative on the same drifted platform: freshly profile the
    # full calibration sample (pre-§8.5 behaviour), then recalibrate
    p0 = platform.profiled_configs
    t0 = time.perf_counter()
    sample = platform.measure_sample(sample_n, seed=999)
    reoptimise(opt, sample=sample, mode="factor")
    fresh_seconds = time.perf_counter() - t0
    return {"recalibrations": st["recalibrations"],
            "generation": st["generation"],
            "sample": st["recal_sample"],
            "served_seconds": timing.get("served_seconds"),
            "served_profiled_configs": timing.get("served_profiled_configs"),
            "fresh_seconds": fresh_seconds,
            "fresh_profiled_configs": platform.profiled_configs - p0,
            "charge_s_per_config": charge_s,
            "drift_ratio_at_stop": st["drift_ratio"]}


def fleet_recal_pass(*, net: str, platform: str, max_triplets: int,
                     max_iters: int, charge_s: float = 0.05,
                     timeout_s: float = 120.0) -> Dict:
    """Fleet calibration sharing (DESIGN.md §14): two hosts of the same
    hardware class share one simulated object-store bucket. Host A
    optimises cold against it, serves a drifting machine, recalibrates from
    its own served traffic, and publishes the evidence under the platform's
    pool fingerprint. Host B warm-starts everything from the shared bucket,
    never serves a request, polls the pool, and hot-swaps a recalibration
    built from A's published evidence alone. Both hosts calibrate on the
    evidence's config coverage (a fresh top-up would defeat the
    zero-profiling objective), so the gate can require ZERO freshly
    profiled configs for B — and byte-identical post-swap assignments."""
    from repro.service import (ArtifactStore, ObjectStoreBackend,
                               layer_profile, make_recalibrator, optimise)

    shared = ObjectStoreBackend()
    storeA = ArtifactStore(backend=shared.share())
    storeB = ArtifactStore(backend=shared.share())

    platformA = _charged_platform(platform, charge_s, max_triplets)
    t0 = time.perf_counter()
    optA = optimise(net, platformA, store=storeA, executable=True,
                    max_iters=max_iters)
    a_cold_seconds = time.perf_counter() - t0
    prof = layer_profile(optA)
    n_cfg = len({tuple(map(int, r)) for r in prof.feats})

    serverA = _drifting_server(
        max_batch=4, latency_budget_ms=1e9, workers=2, max_wait_ms=3.0,
        drift_threshold=1.5, drift_alpha=0.5, drift_calib_obs=2,
        recalibrate=make_recalibrator(store=storeA, sample_n=n_cfg,
                                      mode="factor", pool=True, host="A"))
    serverA.register(optA)
    n0 = optA.spec.nodes[0]
    rng = np.random.default_rng(3)
    xs = rng.standard_normal((4, n0.c, n0.im, n0.im)).astype(np.float32)
    deadline = time.time() + timeout_s
    while (serverA.stats(optA.net)["observed_dispatches"] < 6
           and time.time() < deadline):
        serverA.serve(optA.net, xs)
    platformA.time_scale = 4.0
    platformA.invalidate_datasets()
    while (serverA.stats(optA.net)["recalibrations"] == 0
           and time.time() < deadline):
        serverA.serve(optA.net, xs)
    stA = serverA.stats(optA.net)
    with serverA._cond:
        a_new = serverA._nets[optA.net].opt
    serverA.stop()
    published = storeA.drift_entries(platformA.pool_fingerprint())

    # host B: same hardware class, fresh process — everything warm-loads
    platformB = _charged_platform(platform, charge_s, max_triplets)
    t0 = time.perf_counter()
    optB = optimise(net, platformB, store=storeB, executable=True,
                    max_iters=max_iters)
    b_warm_seconds = time.perf_counter() - t0

    serverB = _drifting_server(
        max_batch=4, latency_budget_ms=1e9, workers=2, max_wait_ms=3.0,
        drift_threshold=1.5, drift_alpha=0.5, drift_calib_obs=2,
        recalibrate=make_recalibrator(store=storeB, sample_n=n_cfg,
                                      mode="factor", pool=True, host="B"))
    serverB.register(optB)
    p0 = platformB.profiled_configs
    t0 = time.perf_counter()
    polled = serverB.poll_pool(storeB, host="B")
    deadline = time.time() + timeout_s
    while not serverB.recalibrations_idle() and time.time() < deadline:
        time.sleep(0.01)
    b_recal_seconds = time.perf_counter() - t0
    stB = serverB.stats(optB.net)
    with serverB._cond:
        b_new = serverB._nets[optB.net].opt
    serverB.stop()

    return {"sample_n": n_cfg,
            "a_cold_seconds": a_cold_seconds,
            "a_recalibrations": stA["recalibrations"],
            "a_generation": stA["generation"],
            "published_entries": len(published),
            "b_warm": optB.warm,
            "b_warm_seconds": b_warm_seconds,
            "b_polled": polled,
            "b_recalibrations": stB["recalibrations"],
            "b_recal_seconds": b_recal_seconds,
            "b_recal_error": stB["last_recal_error"],
            "b_profiled_configs": platformB.profiled_configs - p0,
            "b_sample": stB["recal_sample"],
            "warm_assignments_match": optB.assignment == optA.assignment,
            "assignments_match": b_new.assignment == a_new.assignment}


def deadline_pass(opt, requests: int, budget_ms: float,
                  max_wait_ms: float = 200.0) -> Dict:
    """Paced lone-request load twice: unbounded budget (windows run to the
    static cap) vs a tight budget (deadline-capped, monitor-shrunk). The
    gate: with the budget set, steady-state p99 queueing stays within it."""
    from repro.primitives.executor import make_weights
    from repro.service import OptimisedServer

    weights = make_weights(opt.spec)

    def run(budget) -> Dict:
        server = OptimisedServer(max_batch=16, latency_budget_ms=budget,
                                 workers=2, max_wait_ms=max_wait_ms,
                                 queue_depth=4096)
        server.register(opt, weights=weights)
        n0 = opt.spec.nodes[0]
        rng = np.random.default_rng(4)
        imgs = rng.standard_normal(
            (8, n0.c, n0.im, n0.im)).astype(np.float32)
        server.serve(opt.net, imgs[:2])            # warm small buckets
        tickets = []
        for i in range(requests):                  # paced lone arrivals:
            tickets.append(server.submit(opt.net, imgs[i % len(imgs)]))
            time.sleep(0.02)                       # windows, not batch-fill,
        for t in tickets:                          # decide dispatch
            t.wait(60.0)
        st = server.stats(opt.net)
        server.stop()
        waits = np.array([t.queue_wait_s for t in tickets
                          if t.done and not t.rejected], np.float64)
        steady = waits[len(waits) // 2:]           # after window adaptation
        return {"budget_ms": budget, "requests": len(tickets),
                "queue_wait_p50_ms": float(np.percentile(waits, 50)) * 1e3,
                "queue_wait_p99_ms": float(np.percentile(waits, 99)) * 1e3,
                "steady_p99_ms": float(np.percentile(steady, 99)) * 1e3,
                "budget_hit_rate": (float(np.mean(waits <= budget * 1e-3))
                                    if np.isfinite(budget) else 1.0),
                "window_scale": st["window_scale"],
                "effective_wait_ms": st["effective_wait_ms"],
                "dispatches": st["dispatches"]}

    return {"max_wait_ms": max_wait_ms,
            "unbounded": run(1e9), "budgeted": run(budget_ms)}


def availability_pass(opt, *, budget_ms: float, workers: int = 2) -> Dict:
    """Fault-tolerant serving under a seeded chaos plan (DESIGN.md §11):
    backend a of a two-backend route is poisoned — three dispatches raise
    (retry included), the first half-open probe hangs past the execution
    deadline, the next stalls past it, the third is clean — while b stays
    healthy. A closed-loop client drives bursts until the breaker has
    tripped and recovered, then a little clean traffic. The row reports the
    availability contract the chaos soak test asserts: accepted vs served,
    degraded (fallback) count, zero lost, zero duplicated (exact accounting
    identity), breaker open/close counts, worker restarts."""
    from repro.primitives.executor import make_weights
    from repro.primitives.plan import heuristic_assignment
    from repro.service import (Fault, FaultInjector, OptimisedNetwork,
                               OptimisedServer)

    spec = opt.spec
    weights = make_weights(spec)
    n0 = spec.nodes[0]
    rng = np.random.default_rng(6)
    imgs = rng.standard_normal((4, n0.c, n0.im, n0.im)).astype(np.float32)
    net = "avail_cnn"

    def mk(pred):
        return OptimisedNetwork.from_assignment(
            spec, heuristic_assignment(spec), net=net, predicted_cost_s=pred)

    # warm the global plan cache so healthy dispatches never pay jit compile
    # against the execution deadline
    warm = OptimisedServer(max_batch=4, latency_budget_ms=budget_ms)
    warm.register(mk(1e-3), weights=weights)
    for b in (1, 2, 4):
        warm.serve(net, imgs[:b])

    inj = FaultInjector([
        Fault("raise", net=f"{net}#a", first=0, last=6),
        Fault("hang", net=f"{net}#a", first=6, last=7, seconds=0.75),
        Fault("slowdown", net=f"{net}#a", first=7, last=8, seconds=0.3)])
    server = OptimisedServer(
        max_batch=4, latency_budget_ms=budget_ms, workers=workers,
        max_wait_ms=0.0, queue_depth=10_000, exec_deadline_ms=60.0,
        breaker_failures=3, breaker_cooldown_ms=120.0, faults=inj)
    # a predicts far cheaper: preferred whenever its breaker allows, so the
    # fault schedule is hit deterministically; b is the healthy spill target
    server.register(mk(1e-6), weights=weights, backend="a")
    server.register(mk(1e-3), weights=weights, backend="b")

    tickets = []
    recovered = False
    t0 = time.perf_counter()
    deadline = t0 + 90.0
    while time.perf_counter() < deadline:
        burst = [server.submit(net, imgs[len(tickets) % 4])
                 for _ in range(2)]
        tickets.extend(burst)
        for t in burst:
            t.wait(30.0)
        br = server.stats(net)["backends"]["a"]["breaker"]
        if br["closes"] >= 1 and br["state"] == "closed":
            recovered = True
            break
        time.sleep(0.01)
    for _ in range(5):                         # post-recovery clean traffic
        burst = [server.submit(net, imgs[len(tickets) % 4])
                 for _ in range(2)]
        tickets.extend(burst)
        for t in burst:
            t.wait(30.0)
    dt = time.perf_counter() - t0
    s = server.stats(net)
    restarts = server._pool.restarts if server._pool is not None else 0
    server.stop(timeout=60.0)

    accepted = sum(1 for t in tickets if not t.rejected)
    lost = sum(1 for t in tickets if not t.done)
    served = [t for t in tickets if t.done and t.result is not None]
    ba = s["backends"]["a"]["breaker"]
    return {"tickets": len(tickets), "accepted": accepted, "lost": lost,
            "served": len(served),
            "degraded": sum(1 for t in served if t.degraded),
            "failed_tickets": s["failed_tickets"],
            "availability": len(served) / max(accepted, 1),
            # != 0 would mean a ticket was double-delivered or lost between
            # the primary path and the fallback: the accounting identity
            "duplicated": (s["images"] + s["fallback_images"]) - len(served),
            "seconds": dt,
            "injected_faults": [list(e) for e in inj.injected],
            "breaker_opens": ba["opens"], "breaker_closes": ba["closes"],
            "breaker_state": ba["state"], "breaker_recovered": recovered,
            "worker_restarts": restarts, "rollbacks": s["rollbacks"],
            "spill_images": s["backends"]["b"]["images"],
            "failure_ledger": s["failures"]}


def frontend_scaling_pass(opt, requests: int, budget_ms: float, *,
                          workers: int, procs: int,
                          chaos_requests: int = 48) -> Dict:
    """Thread front end vs the multi-process shared-memory front end
    (DESIGN.md §12) on the same warmed single-net load, equal workers.

    Thread pass: ``procs`` submitter threads push lone requests through
    ``submit`` — batch assembly (payload copy, pow2 pad, result slicing)
    runs under the parent's GIL. Process pass: the same request count
    through ``ProcessFrontend.drive`` — intake processes write payloads
    once into shared-memory slabs and the workers execute zero-copy views.
    A second drive runs under an injected fault plan (the shm chaos soak):
    the accounting identity — served + failed + rejected == requests, with
    zero lost and zero duplicated — must survive the slab path."""
    import threading

    from repro.primitives.executor import make_weights
    from repro.service import Fault, FaultInjector, OptimisedServer

    spec = opt.spec
    weights = make_weights(spec)
    n0 = spec.nodes[0]
    rng = np.random.default_rng(8)

    def mk_server(**kw):
        server = OptimisedServer(max_batch=8, latency_budget_ms=budget_ms,
                                 workers=workers, max_wait_ms=2.0,
                                 queue_depth=4096, **kw)
        server.register(opt, weights=weights)
        for b in (1, 2, 4, 8):        # warm every (net, bucket) plan
            server.serve(opt.net, rng.standard_normal(
                (b, n0.c, n0.im, n0.im)).astype(np.float32))
        return server

    # -- thread front end --------------------------------------------------
    server = mk_server()
    xs = rng.standard_normal(
        (requests, n0.c, n0.im, n0.im)).astype(np.float32)
    shares = np.array_split(np.arange(requests), procs)
    tickets: list = [[] for _ in shares]

    def submitter(i):
        for j in shares[i]:
            tickets[i].append(server.submit(opt.net, xs[j]))

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(procs)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    flat = [t for part in tickets for t in part]
    for t in flat:
        t.wait(300.0)
    dt = time.perf_counter() - t0
    thread_row = {"images_per_s": requests / dt, "seconds": dt,
                  "failed": sum(1 for t in flat if not t.done or t.error)}
    server.stop()

    # -- process front end (clean, then under the chaos fault plan) --------
    server = mk_server(frontend_procs=procs)
    fe = server.frontend()
    clean = fe.drive(opt.net, requests, seed=9)
    server.stop()

    inj = FaultInjector([Fault("raise", net=opt.net, first=5, last=7)])
    server = mk_server(frontend_procs=procs, faults=inj)
    s0 = server.stats(opt.net)                 # warm traffic, pre-drive
    chaos = server.frontend().drive(opt.net, chaos_requests, seed=10)
    s = server.stats(opt.net)
    # lost/duplicated on the slab path: every request resolved exactly once,
    # and the served-image accounting delta matches the deliveries
    chaos["lost"] = chaos_requests - (chaos["served"] + chaos["failed"]
                                      + chaos["rejected"])
    chaos["duplicated"] = ((s["images"] + s["fallback_images"])
                           - (s0["images"] + s0["fallback_images"])
                           - chaos["served"])
    chaos["injected_faults"] = len(inj.injected)
    server.stop()

    return {"workers": workers, "procs": procs, "requests": requests,
            "threads": thread_row, "processes": clean,
            "speedup": clean["images_per_s"] / thread_row["images_per_s"],
            "chaos": chaos}


def bucket_cost_pass(nets, *, buckets=(1, 2, 4), rounds: int = 24) -> Dict:
    """Batch-shape-aware vs linear per-image cost on really-served traffic
    (DESIGN.md §12.3), per zoo net.

    Each net serves ``rounds`` bursts per pow2 bucket (pump mode, plans
    warmed) with per-dispatch per-image latency recorded; even rounds fit,
    odd rounds evaluate. The linear model is the count-weighted mean
    per-image cost over the fit half (what a batch-size-invariant predictor
    settles on); the bucket model is ``BucketScaleHead`` fitted from the
    same half. Error is the count-weighted mean absolute log-space gap
    between each bucket's held-out **median** and the model — the median
    (plus the larger round count) keeps a single scheduler stall on a
    loaded runner from deciding the gate, which matters more now that the
    §13.3 dispatch fast path has removed most of the fixed per-dispatch
    overhead the head models. The gate requires the bucket model strictly
    below linear on every listed net."""
    from repro.core.perfmodel import BucketScaleHead
    from repro.models import cnn_zoo
    from repro.primitives.plan import heuristic_assignment
    from repro.service import OptimisedNetwork, OptimisedServer

    out = {}
    for net in nets:
        spec = cnn_zoo.get(net)
        opt = OptimisedNetwork.from_assignment(
            spec, heuristic_assignment(spec), predicted_cost_s=2e-3)
        server = OptimisedServer(max_batch=8, latency_budget_ms=1e9)
        server.register(opt)
        n0 = spec.nodes[0]
        rng = np.random.default_rng(7)
        xs = {b: rng.standard_normal(
            (b, n0.c, n0.im, n0.im)).astype(np.float32) for b in buckets}
        for b in buckets:                      # warm: jit compile excluded
            server.serve(net, xs[b])
        fit, ev = [], {b: [] for b in buckets}
        for r in range(rounds):
            for b in buckets:
                t0 = time.perf_counter()
                server.serve(net, xs[b])
                per = (time.perf_counter() - t0) / b
                if r % 2 == 0:
                    fit.append((b, np.log(per)))
                else:
                    ev[b].append(np.log(per))
        server.stop()
        head = BucketScaleHead.fit(fit, normalize=False)
        counts: Dict[int, int] = {}
        for b, _ in fit:
            counts[b] = counts.get(b, 0) + 1
        base = float(np.average(
            [np.log(head.scale(b)) for b in head.buckets()],
            weights=[counts[b] for b in head.buckets()]))
        lin, buc, w = [], [], []
        for b in buckets:
            m = float(np.median(ev[b]))
            lin.append(abs(m - base))
            buc.append(abs(m - np.log(head.scale(b))))
            w.append(len(ev[b]))
        out[net] = {
            "per_image_ms": {int(b): float(np.exp(np.log(head.scale(b))))
                             * 1e3 for b in head.buckets()},
            "linear_per_image_ms": float(np.exp(base)) * 1e3,
            "linear_err": float(np.average(lin, weights=w)),
            "bucket_err": float(np.average(buc, weights=w)),
        }
        out[net]["bucket_wins"] = (out[net]["bucket_err"]
                                   < out[net]["linear_err"])
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small pools / fewer iters (CI gate)")
    ap.add_argument("--net", default="edge_cnn")
    ap.add_argument("--platform", default="arm")
    ap.add_argument("--base", default="intel")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--budget-ms", type=float, default=50.0)
    ap.add_argument("--workers", type=int, default=3,
                    help="worker threads for the concurrent serving row")
    ap.add_argument("--max-wait-ms", type=float, default=4.0,
                    help="batch window for the concurrent serving row")
    ap.add_argument("--recal-sample-n", type=int, default=12,
                    help="calibration sample size for the drift "
                         "recalibration row")
    ap.add_argument("--backends", default="arm,amd",
                    help="comma-separated platform specs for the "
                         "cross-backend routing row (simulated-CPU "
                         "platforms: the row's device-charge model needs "
                         "real per-image compute to be incidental, and "
                         "since tile variants lower to real interpret-mode "
                         "Pallas kernels (DESIGN.md §13.1) a 'tpu' backend "
                         "burns enough host CPU to fight the other "
                         "backend for cores instead of overlapping)")
    ap.add_argument("--frontend-procs", type=int, default=2,
                    help="intake processes for the frontend scaling row")
    ap.add_argument("--bucket-nets", default="edge_cnn,alexnet",
                    help="comma-separated zoo nets for the bucket-aware "
                         "cost model row (>= 2)")
    ap.add_argument("--store", default=None,
                    help="artifact store root (default: fresh temp dir, "
                         "removed afterwards, so the first pass is cold)")
    args = ap.parse_args()

    max_triplets = 30 if args.smoke else 60
    max_iters = 600 if args.smoke else 2000
    requests = args.requests or (32 if args.smoke else 128)

    root = args.store or tempfile.mkdtemp(prefix="repro-service-e2e-")
    cleanup = args.store is None
    try:
        kw = dict(net=args.net, platform=args.platform, base=args.base,
                  max_triplets=max_triplets, max_iters=max_iters)
        cold = optimise_pass(root, **kw)
        warm = optimise_pass(root, **kw)
        ratio = cold["seconds"] / max(warm["seconds"], 1e-9)
        same = cold["opt"].assignment == warm["opt"].assignment
        emit("service.optimise_cold_us", cold["seconds"] * 1e6,
             f"{cold['seconds']:.2f}s train+select")
        emit("service.optimise_warm_us", warm["seconds"] * 1e6,
             f"{warm['seconds']:.3f}s from artifacts ({ratio:.0f}x)")

        served = serve_pass(warm["opt"], requests, args.budget_ms)
        emit("service.served_img_s", 1e6 / served["images_per_s"],
             f"{served['images_per_s']:.1f} img/s "
             f"cap={served['batch_cap']} dispatches={served['dispatches']}")

        rpn = max(requests // 2, 16)
        concurrent = concurrent_pass(warm["opt"], rpn, args.budget_ms,
                                     args.workers, args.max_wait_ms)
        emit("service.concurrent_img_s",
             1e6 / concurrent["concurrent"]["images_per_s"],
             f"{concurrent['concurrent']['images_per_s']:.1f} img/s over "
             f"{len(concurrent['networks'])} nets with "
             f"{args.workers} workers ({concurrent['speedup']:.2f}x serial, "
             f"queue p50/p99 "
             f"{concurrent['concurrent']['queue_wait_p50_ms']:.2f}/"
             f"{concurrent['concurrent']['queue_wait_p99_ms']:.2f} ms)")

        recal = recalibration_pass(warm["opt"], sample_n=args.recal_sample_n)
        frac = (recal["sample"] or {}).get("served_fraction", 0.0)
        if recal["served_seconds"] is not None:
            served_note = (f"{recal['served_seconds']:.2f}s, "
                           f"{frac:.0%} served rows, "
                           f"{recal['served_profiled_configs']} configs "
                           f"profiled")
        else:                          # drift loop never hot-swapped: the
            served_note = "NO served-sample recalibration ran"   # gate fails
        emit("service.recal_served_us",
             (recal["served_seconds"] or float("inf")) * 1e6,
             f"drift recal from served traffic: {served_note} "
             f"(fresh path: {recal['fresh_seconds']:.2f}s for "
             f"{recal['fresh_profiled_configs']} configs)")

        fr = fleet_recal_pass(net=args.net, platform=args.platform,
                              max_triplets=max_triplets, max_iters=max_iters)
        emit("service.fleet_recal_us", fr["b_recal_seconds"] * 1e6,
             f"host B pooled recal {fr['b_recal_seconds']:.2f}s from "
             f"{fr['published_entries']} published entr"
             f"{'y' if fr['published_entries'] == 1 else 'ies'}, "
             f"{fr['b_profiled_configs']} configs profiled "
             f"(warm-start {'ok' if fr['b_warm'] else 'COLD'} "
             f"{fr['b_warm_seconds']:.2f}s, assignments "
             f"{'match' if fr['assignments_match'] else 'DIVERGE'})")

        mb = multibackend_pass(root, net=args.net,
                               backends=tuple(args.backends.split(",")),
                               base=args.base, max_triplets=max_triplets,
                               max_iters=max_iters, requests=requests,
                               budget_ms=args.budget_ms,
                               workers=max(args.workers, 2))
        emit("service.multibackend_img_s",
             1e6 / mb["routed"]["images_per_s"],
             f"{mb['routed']['images_per_s']:.1f} img/s routed across "
             f"{len(mb['backends'])} backends "
             f"({mb['routed_vs_best_single']:.2f}x best single "
             f"'{mb['best_single']}' "
             f"{mb['single'][mb['best_single']]['images_per_s']:.1f} img/s, "
             f"repro={'ok' if mb['reproducible_from_store'] else 'MISMATCH'})")

        deadline = deadline_pass(warm["opt"], max(rpn, 96), args.budget_ms)
        emit("service.deadline_p99_us",
             deadline["budgeted"]["steady_p99_ms"] * 1e3,
             f"deadline windows: steady p99 "
             f"{deadline['budgeted']['steady_p99_ms']:.1f} ms vs "
             f"{args.budget_ms:.0f} ms budget "
             f"(hit rate {deadline['budgeted']['budget_hit_rate']:.0%}, "
             f"window scale {deadline['budgeted']['window_scale']:.2f}; "
             f"unbounded p99 "
             f"{deadline['unbounded']['queue_wait_p99_ms']:.1f} ms)")

        fe = frontend_scaling_pass(warm["opt"], max(requests, 128),
                                   args.budget_ms,
                                   workers=max(args.workers, 2),
                                   procs=args.frontend_procs)
        emit("service.frontend_img_s",
             1e6 / fe["processes"]["images_per_s"],
             f"{fe['processes']['images_per_s']:.1f} img/s through "
             f"{fe['procs']} shm intake processes "
             f"({fe['speedup']:.2f}x the {fe['procs']}-thread front end "
             f"{fe['threads']['images_per_s']:.1f} img/s; chaos soak "
             f"{fe['chaos']['served']}/{fe['chaos']['requests']} served, "
             f"{fe['chaos']['lost']} lost, "
             f"{fe['chaos']['duplicated']:+d} dup)")

        bucket = bucket_cost_pass(tuple(args.bucket_nets.split(",")))
        worst = max(bucket, key=lambda n: bucket[n]["bucket_err"]
                    / max(bucket[n]["linear_err"], 1e-12))
        emit("service.bucket_cost_err_mlog",
             bucket[worst]["bucket_err"] * 1e3,
             "bucket-aware vs linear per-image cost (log-space err): " +
             ", ".join(f"{n} {r['bucket_err']:.3f} vs {r['linear_err']:.3f}"
                       for n, r in bucket.items()))

        avail = availability_pass(warm["opt"], budget_ms=args.budget_ms,
                                  workers=max(args.workers, 2))
        emit("service.unavailability_ppm",
             (1.0 - avail["availability"]) * 1e6,
             f"{avail['availability']:.2%} of {avail['accepted']} tickets "
             f"served under injected faults ({avail['degraded']} degraded, "
             f"{avail['lost']} lost, {avail['duplicated']:+d} dup, "
             f"breaker opens/closes "
             f"{avail['breaker_opens']}/{avail['breaker_closes']}, "
             f"{avail['worker_restarts']} workers replaced)")

        results = {
            "mode": "smoke" if args.smoke else "full",
            "net": args.net, "platform": args.platform, "base": args.base,
            "cold_seconds": cold["seconds"],
            "warm_seconds": warm["seconds"],
            "warm_speedup": ratio,
            "warm_was_warm": warm["warm"],
            "same_assignment": same,
            "assignment": {str(k): v for k, v in
                           sorted(warm["opt"].assignment.items())},
            "served": served,
            "concurrent_serving": concurrent,
            "recalibration": recal,
            "fleet_recalibration": fr,
            "multibackend": mb,
            "deadline_batching": deadline,
            "frontend_scaling": fe,
            "bucket_cost": bucket,
            "availability": avail,
        }
        with open(OUT_PATH, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"wrote {OUT_PATH} (warm optimise {ratio:.0f}x faster than cold)")

        failures = []
        if ratio < 10.0:
            failures.append(f"warm-start only {ratio:.1f}x faster (< 10x)")
        if not same:
            failures.append("warm-start selected a different assignment")
        if not warm["warm"]:
            failures.append("second pass retrained instead of warm-loading")
        # the worker pool's overlap win needs parallel hardware; on a
        # one-core runner the honest expectation is parity with the serial
        # pump, so only gate strictly when there are cores to overlap on
        min_conc = 1.0 if (os.cpu_count() or 1) > 1 else 0.85
        if concurrent["speedup"] < min_conc:
            failures.append(f"concurrent multi-network throughput only "
                            f"{concurrent['speedup']:.2f}x the serial pump "
                            f"(< {min_conc:.2f}x on {os.cpu_count()} cpu)")
        if concurrent["concurrent"]["failed"] or concurrent["serial"]["failed"]:
            failures.append("concurrent serving failed requests")
        if recal["recalibrations"] < 1:
            failures.append("drift recalibration did not hot-swap")
        if frac < 0.5:
            failures.append(f"recalibration used only {frac:.0%} served "
                            f"observations (< 50%)")
        if not (recal["served_seconds"] is not None
                and recal["served_seconds"] < recal["fresh_seconds"]):
            failures.append(
                f"served-sample recalibration ({recal['served_seconds']}s) "
                f"not faster than fresh profiling "
                f"({recal['fresh_seconds']:.2f}s)")
        if fr["a_recalibrations"] < 1:
            failures.append("fleet: host A never hot-swapped from served "
                            "drift")
        if fr["published_entries"] < 1:
            failures.append("fleet: host A published no drift evidence")
        if not fr["b_warm"]:
            failures.append("fleet: host B did not warm-start from the "
                            "shared backend")
        if not fr["warm_assignments_match"]:
            failures.append("fleet: host B warm-started a different "
                            "assignment than host A")
        if fr["b_polled"] != 1 or fr["b_recalibrations"] != 1:
            failures.append(
                f"fleet: host B polled {fr['b_polled']} / hot-swapped "
                f"{fr['b_recalibrations']} from pooled evidence "
                f"(expected 1/1, error: {fr['b_recal_error']})")
        if fr["b_profiled_configs"] != 0:
            failures.append(f"fleet: host B freshly profiled "
                            f"{fr['b_profiled_configs']} configs "
                            f"(expected 0)")
        if (fr["b_sample"] or {}).get("fresh_rows") != 0:
            failures.append("fleet: host B's recalibration sample was not "
                            "pure pooled evidence")
        if (fr["b_sample"] or {}).get("pooled_sources", 0) < 1:
            failures.append("fleet: host B's recalibration pulled no "
                            "pooled datasets")
        if not fr["assignments_match"]:
            failures.append("fleet: pooled recalibration selected a "
                            "different assignment than host A's")
        if mb["routed_vs_best_single"] < 1.0:
            failures.append(
                f"cross-backend routing only {mb['routed_vs_best_single']:.2f}x "
                f"the best single backend ('{mb['best_single']}')")
        if mb["routed"]["failed"] or any(s["failed"]
                                         for s in mb["single"].values()):
            failures.append("multi-backend serving failed requests")
        if not mb["reproducible_from_store"]:
            failures.append("per-backend assignments not reproducible from "
                            "the warm artifact store")
        if deadline["budgeted"]["steady_p99_ms"] > args.budget_ms:
            failures.append(
                f"deadline windows: steady p99 queueing "
                f"{deadline['budgeted']['steady_p99_ms']:.1f} ms exceeds the "
                f"{args.budget_ms:.0f} ms budget")
        # like the concurrency gate: the process front end's win is freeing
        # the parent GIL for more hardware — on a <=2-core runner there is
        # none spare, so the honest expectation is parity with noise
        min_fe = 1.0 if (os.cpu_count() or 1) > 2 else 0.75
        if fe["speedup"] < min_fe:
            failures.append(f"process front end only {fe['speedup']:.2f}x "
                            f"the thread front end "
                            f"(< {min_fe:.2f}x on {os.cpu_count()} cpu)")
        if fe["threads"]["failed"] or fe["processes"]["failed"]:
            failures.append("front-end scaling row failed requests")
        if fe["chaos"]["lost"]:
            failures.append(f"{fe['chaos']['lost']} ticket(s) lost on the "
                            f"shm front end under faults")
        if fe["chaos"]["duplicated"]:
            failures.append(f"shm front end accounting off by "
                            f"{fe['chaos']['duplicated']} under faults")
        if fe["chaos"]["served"] / fe["chaos"]["requests"] < 0.99:
            failures.append(f"shm front end served only "
                            f"{fe['chaos']['served']} of "
                            f"{fe['chaos']['requests']} under faults")
        not_winning = [n for n, r in bucket.items() if not r["bucket_wins"]]
        if len(bucket) < 2 or not_winning:
            failures.append(
                f"bucket-aware cost model not strictly better than linear "
                f"on every net ({', '.join(not_winning) or 'too few nets'})")
        if avail["availability"] < 0.99:
            failures.append(f"availability {avail['availability']:.2%} under "
                            f"injected faults (< 99%)")
        if avail["lost"]:
            failures.append(f"{avail['lost']} ticket(s) lost under faults")
        if avail["duplicated"]:
            failures.append(f"ticket accounting off by {avail['duplicated']} "
                            f"(duplicated or mis-counted delivery)")
        if not avail["breaker_recovered"]:
            failures.append("poisoned backend's breaker never recovered "
                            "through a half-open probe")
        if failures:
            print("FAIL: " + "; ".join(failures), file=sys.stderr)
            return 1
        return 0
    finally:
        if cleanup:
            shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())

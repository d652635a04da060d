"""One run of a cell: set the system up, drive the window, check answers.

The system under test is the repository's served path, used as a
deployment uses it: ``pipeline.optimise`` selects a plan for the cell's
network on ``PallasPlatform``, ``OptimisedServer`` compiles and warms one
dispatch handle per pow2 bucket, and every request goes through
``OptimisedServer.submit`` → worker → ``execute`` → the bucket's handle →
``Ticket.finish``. The benchmark makes the weights and the images from the
seed; the traffic generator decides when requests are sent.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import reference, registry

clock = time.perf_counter            # the clock Ticket timestamps use


@dataclasses.dataclass
class Request:
    img: int                         # index into the image pool
    due_s: float                     # when the traffic meant to send it
    sent_s: float
    ticket: object


@dataclasses.dataclass
class System:
    net: str
    opt: object
    server: object
    weights: List                    # in ``reference.convs`` order
    images: np.ndarray
    sink_layout: str
    optimise_s: float
    compile_s: float


@dataclasses.dataclass
class Run:
    """Everything a metric reader (``metrics/<name>.py``) may read."""
    t0: float                        # window, on ``clock``
    t1: float
    requests: List[Request]          # due in the window
    setup_s: float = 0.0
    optimise_s: float = 0.0
    compile_s: float = 0.0
    flops_per_image: int = 0
    peaks: Optional[Dict] = None
    trace: Optional[Dict] = None     # ``trace.reduce`` of the window
    window_traced_s: float = 0.0
    trace_events: Optional[Dict] = None   # the window's ``trace.load``
    programs: Optional[List[str]] = None  # texts of the buckets that ran

    def answered(self) -> List[Request]:
        return [r for r in self.requests
                if r.ticket.done and r.ticket.error is None]

    def latencies_s(self) -> np.ndarray:
        """Scheduled send to completion for every request due in the
        window; +inf for one that failed, was refused or never came."""
        ok = {id(r) for r in self.answered()}
        return np.array([r.ticket.completed_s - r.due_s if id(r) in ok
                         else np.inf for r in self.requests])

    def images_in_window(self) -> float:
        """Images served in the window: each answer counts the share of its
        dispatch, claim to completion, that lies inside the window, so a
        batch still running at the close counts in part."""
        n = 0.0
        for r in self.answered():
            a, b = r.ticket.dispatched_s, r.ticket.completed_s
            if b > a:
                n += max(min(b, self.t1) - max(a, self.t0), 0.0) / (b - a)
            else:
                n += self.t0 <= b < self.t1
        return n

    def dispatches(self) -> int:
        """Dispatches claimed inside the window that carried its requests:
        the tickets of one batch share its claim time."""
        return len({r.ticket.dispatched_s for r in self.answered()
                    if self.t0 <= r.ticket.dispatched_s < self.t1})

    def images_dispatched(self) -> int:
        return sum(1 for r in self.answered()
                   if self.t0 <= r.ticket.dispatched_s < self.t1)


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile: defined where some values are +inf."""
    v = np.sort(np.asarray(values, np.float64))
    if not v.size:
        return float("nan")
    return float(v[max(int(np.ceil(q / 100.0 * v.size)) - 1, 0)])


def _net_weights(spec, cfg: Dict, weights: List) -> Dict[int, object]:
    """Map the reference's convolutions onto the system's conv nodes, in
    order. Refuses a network whose convolutions differ from the config's,
    or that has a node the benchmark makes no weights for (an eltwise bias
    or ReLU); how its add and concat joins are wired is for ``check`` to
    judge against the reference."""
    from repro.models.cnn_zoo import ConvLayer, JoinNode
    nodes = [(i, n) for i, n in enumerate(spec.nodes)
             if isinstance(n, ConvLayer)]
    bad = [n for n in spec.nodes
           if not isinstance(n, (ConvLayer, JoinNode)) or
           (isinstance(n, JoinNode) and n.kind not in ("add", "concat"))]
    want = reference.convs(cfg)
    got = [(n.k, n.c, n.f, n.s) for _, n in nodes]
    if bad or got != want:
        raise ValueError(f"{spec.name} is not the network config "
                         f"{cfg['name']} describes")
    return {i: w for (i, _), w in zip(nodes, weights)}


def setup(cfg: Dict, mix: Dict, seed: int, log: Callable, *,
          spec=None, optimise_fn=None) -> System:
    import jax
    from repro.models import cnn_zoo
    from repro.primitives.plan import compile_plan
    from repro.service.pipeline import optimise
    from repro.service.platforms import PallasPlatform
    from repro.service.serving.server import OptimisedServer

    spec = spec if spec is not None else cnn_zoo.get(cfg["system_net"])
    weights = jax.block_until_ready(reference.make_weights(cfg, seed))
    net_w = _net_weights(spec, cfg, weights)

    t = clock()
    # the selection is the system's own product and does not depend on the
    # benchmark's seed: the same plan serves every run of a cell
    opt = (optimise_fn or (lambda s: optimise(s, PallasPlatform(),
                                              executable=True, seed=0)))(spec)
    optimise_s = clock() - t
    log(f"optimise {spec.name}: {optimise_s:.3f} s, predicted "
        f"{opt.predicted_cost_s * 1e3:.4f} ms/img")

    server = OptimisedServer(**mix["server"])
    t = clock()
    server.register(opt, weights=net_w)
    compile_s = clock() - t
    st = server.stats(spec.name)
    log(f"register (compile and warm every bucket): {compile_s:.3f} s; "
        f"per bucket {st['precompiled']}; batch cap {st['batch_cap']}")
    if st["precompile_error"]:
        server.stop()
        raise RuntimeError(f"precompile: {st['precompile_error']}")
    n0 = spec.nodes[0]
    plan = compile_plan(spec, opt.assignment, (1, n0.c, n0.im, n0.im))
    images = reference.make_images(cfg, seed, int(mix["images"]))
    return System(spec.name, opt, server, weights, images,
                  plan.layouts[plan.sinks[-1]], optimise_s, compile_s)


def drive(system: System, mix: Dict, seed: int, seconds: float,
          t_start: float, log: Callable,
          trace_dir: Optional[Path] = None) -> Dict:
    """Warm up, then send the mix for ``seconds`` and wait for every answer.
    Returns the window's requests and times; the trace, where asked for,
    covers the window alone."""
    from bench import trace as T
    gen = registry.load_module("traffic", mix["kind"])
    server, net, images = system.server, system.net, system.images
    order = np.random.default_rng(seed).permutation(len(images))
    sent: List[Request] = []

    def submit(due: float):
        img = int(order[len(sent) % len(order)])
        now = clock()
        t = server.submit(net, images[img])
        sent.append(Request(img, due, now, t))
        return t

    w0 = clock()
    gen.drive(submit, mix, seed + 1, w0, w0 + float(mix["warmup_s"]), clock)
    _wait(sent, w0 + float(mix["warmup_s"]) + 60.0)
    log(f"warm-up: {len(sent)} requests in {clock() - w0:.3f} s")
    sent.clear()

    t0 = clock()
    setup_s = t0 - t_start
    pauses: List[float] = []
    started = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            started[0] = clock()
        elif info["generation"] == 2:
            pauses.append(clock() - started[0])

    gc.callbacks.append(on_gc)
    try:
        with T.capture(trace_dir) if trace_dir else contextlib.nullcontext():
            t0 = clock()
            gen.drive(submit, mix, seed, t0, t0 + seconds, clock)
            while clock() < t0 + seconds:
                time.sleep(max(t0 + seconds - clock(), 0.0))
            t1 = clock()
    finally:
        gc.callbacks.remove(on_gc)
    log(f"full garbage collections in the window: {len(pauses)}, longest "
        f"{max(pauses, default=0.0) * 1e3:.3f} ms, total "
        f"{sum(pauses) * 1e3:.3f} ms")
    _wait(sent, t1 + 60.0)
    late = np.array([r.sent_s - r.due_s for r in sent])
    log(f"window: {len(sent)} requests in {t1 - t0:.3f} s; generator late "
        f"p50 {percentile(late, 50) * 1e3:.4f} ms, p99 "
        f"{percentile(late, 99) * 1e3:.4f} ms, max "
        f"{(late.max() if late.size else 0) * 1e3:.4f} ms")
    window = [r for r in sent if t0 <= r.due_s < t0 + seconds]
    return {"requests": window, "t0": t0, "t1": t0 + seconds,
            "setup_s": setup_s, "traced_s": t1 - t0}


def _wait(requests: List[Request], deadline: float) -> None:
    for r in requests:
        r.ticket.wait(max(deadline - clock(), 0.0))


def check(cfg: Dict, system: System, requests: List[Request]):
    """Compare every answer of the window with the plain reference of its
    own image. Returns the numbers compared, each beside its limit, and how
    many answers were compared.

    ``max_rel_err``: over answered requests, max |served − reference| over
    max |reference| of that image. ``malformed``: answers of the wrong
    shape or with a value that is not finite. ``unanswered``: requests that
    raised or never came (a refusal at a full queue is a failure of
    latency, counted by the latency metrics, not a wrong answer)."""
    from repro.primitives import layouts as L
    used = sorted({r.img for r in requests})
    ref = dict(zip(used, reference.forward(cfg, system.weights,
                                           system.images[used])))
    worst, compared, malformed, unanswered = 0.0, 0, 0, 0
    for r in requests:
        t = r.ticket
        if t.rejected:
            continue
        if not t.done or t.error is not None or t.result is None:
            unanswered += 1
            continue
        got = np.asarray(L.to_chw(t.result, system.sink_layout))
        want = ref[r.img]
        if got.shape != want.shape or not np.isfinite(got).all():
            malformed += 1
        else:
            worst = max(worst, float(np.abs(got - want).max()
                                     / np.abs(want).max()))
        compared += 1
    return {"max_rel_err": {"value": worst,
                            "limit": float(cfg["max_rel_err_limit"])},
            "malformed": {"value": malformed, "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0}}, compared


def passed(checks: Dict, compared: int) -> bool:
    """Every number within its limit, and at least one answer compared."""
    return compared > 0 and all(c["value"] <= c["limit"]
                                for c in checks.values())


def release(system: System) -> None:
    """Stop the server and drop the program's compiled state, keeping the
    weights and images that the reference needs."""
    from repro.primitives.plan import clear_plan_cache
    system.server.stop()
    system.server = None
    system.opt = None
    clear_plan_cache()
    gc.collect()

#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate the system
sustains, with no backlog that grows through the window.

    python3 bench/sweep.py --workload resnet50-valid.server \\
        --seeds 5 6 7 --seconds 15 --rates 90 110 130 150

One set-up, then each rate in turn, on each seed, through the cell's own
traffic generator and server settings. For each rate and seed it prints
one JSON line: the p50 and p99 latency, the p50 of the window's first and
last quarters, the requests still open when the window closed, and the
images per dispatch. A backlog that grows shows as a last quarter far
slower than the first and many requests open at the close
(``growing``). The benchmark's cells then offer a fixed rate below the
knee; this sweep is run once, by hand, and its table goes into PERF.md.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("not a TPU", file=sys.stderr)
        return 3
    from bench import registry, serve
    from bench.run import log
    cell = registry.cell(registry.benchmark(ROOT), args.workload)
    cfg, mix = cell["config_data"], cell["traffic_data"]
    system = serve.setup(cfg, mix, args.seeds[0], log)
    try:
        for rate in args.rates:
            for seed in args.seeds:
                print(json.dumps(_window(system, {**mix, "rate_per_s": rate},
                                         seed, args.seconds)), flush=True)
    finally:
        serve.release(system)
    return 0


def _window(system, mix, seed, seconds):
    from bench import serve
    from bench.run import log
    w = serve.drive(system, mix, seed, seconds, time.perf_counter(), log)
    reqs, t0, t1 = w["requests"], w["t0"], w["t1"]
    run = serve.Run(t0=t0, t1=t1, requests=reqs)
    lat = run.latencies_s() * 1e3
    due = np.array([r.due_s - t0 for r in reqs])
    first = serve.percentile(lat[due < seconds / 4], 50)
    last = serve.percentile(lat[due >= seconds * 3 / 4], 50)
    open_at_close = sum(1 for r in reqs if not r.ticket.done
                        or r.ticket.completed_s > t1)
    return {"rate_per_s": mix["rate_per_s"], "seed": seed,
            "requests": len(reqs),
            "p50_ms": serve.percentile(lat, 50),
            "p99_ms": serve.percentile(lat, 99),
            "p50_first_quarter_ms": first, "p50_last_quarter_ms": last,
            "open_at_close": open_at_close,
            "batch_mean": (run.images_dispatched() / run.dispatches()
                           if run.dispatches() else None),
            "growing": bool(last > 2 * first
                            or open_at_close > 0.25 * mix["rate_per_s"])}


if __name__ == "__main__":
    sys.exit(main())

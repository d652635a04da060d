#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process holds.

    python3 bench/run.py --workload resnet50-valid.offline --seed 7 \\
        --seconds 10 --trace 0

from the root of a checkout. The cell, its network configuration and its
traffic mix come from ``BENCHMARK.json`` and the files it names. With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window is traced and the result carries its per-layer
metrics and the device's busy time. Either way every answer of the window
is compared with the plain reference, and the last line of standard output
is one JSON object::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, "checks": {...}}

It refuses (exit code 3, no result) where the first device is not a TPU or
the host has fewer chips than the cell asks for. Traces go under
``bench/out/``, with the TPU runtime's logs; the persistent compilation
cache is ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
OUT = ROOT / "bench" / "out"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        log(f"no system under test at {ROOT / 'src' / 'repro'}")
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import registry
    try:
        cell = registry.cell(registry.benchmark(ROOT), args.workload)
    except (registry.Missing, KeyError, ValueError) as e:
        log(f"cannot resolve {args.workload!r}: {e}")
        return 2

    # the compilation cache lives in this checkout, at a fixed path; the
    # system's own entry points take the directory from this variable.
    # The TPU runtime's logs stay in the checkout too, not under /tmp.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"first device is {dev.platform!r}, not a TPU: no result")
        return 3
    if len(devices) < cell["chips"]:
        log(f"{len(devices)} chip(s), the cell asks for {cell['chips']}")
        return 3
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())["devices"]
    if dev.device_kind not in peaks:
        log(f"no peaks for device kind {dev.device_kind!r} in peaks.json")
        return 3
    log(f"device {dev.device_kind!r} x{len(devices)}; cell {cell['name']}; "
        f"seed {args.seed}; {args.seconds:g} s; trace {args.trace}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      peaks[dev.device_kind])
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices),
                        "memory_peak_bytes": result.pop("memory_peak_bytes"),
                        **result.pop("device_times", {})}
    checks = result.pop("checks")
    result["checks"] = checks                 # the last key of the line
    print(json.dumps(result))
    sys.stdout.flush()
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return 0


def run_cell(cell, seed: int, seconds: float, traced: bool, peaks,
             *, spec=None, optimise_fn=None, t_start: float = T_START):
    """Set up, drive and check one run; return the result's fields. Tests
    call this on the CPU with a small ``spec`` and their own faults."""
    import jax
    from bench import reference, registry, serve
    from bench import trace as T

    cfg, mix = cell["config_data"], cell["traffic_data"]
    system = serve.setup(cfg, mix, seed, log, spec=spec,
                         optimise_fn=optimise_fn)
    handles = system.server.plan_handles(system.net)
    for b, h in sorted(handles.items()):
        m = h.memory_analysis()
        if m is not None:
            log(f"bucket {b}: temp {m.temp_size_in_bytes} B, arguments "
                f"{m.argument_size_in_bytes} B, output "
                f"{m.output_size_in_bytes} B, code "
                f"{m.generated_code_size_in_bytes} B")
    trace_dir = None
    if traced:
        trace_dir = OUT / cell["name"] / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    try:
        w = serve.drive(system, mix, seed, seconds, t_start, log, trace_dir)
    except BaseException:
        serve.release(system)
        raise
    stats = system.server.stats(system.net)
    run = serve.Run(t0=w["t0"], t1=w["t1"], requests=w["requests"],
                    setup_s=w["setup_s"], optimise_s=system.optimise_s,
                    compile_s=system.compile_s,
                    flops_per_image=reference.executed_flops(cfg),
                    peaks=peaks, window_traced_s=w["traced_s"])
    log(f"served: {stats['images']} images in {stats['dispatches']} "
        f"dispatches since start; failed_dispatches "
        f"{stats['failed_dispatches']}, rejected {stats['rejected']}, "
        f"batch cap {stats['batch_cap']}")
    breakdown = None
    if traced:
        from repro.service.serving.queues import pow2_ceil
        sizes = {}
        for r in run.answered():
            sizes[r.ticket.dispatched_s] = sizes.get(r.ticket.dispatched_s,
                                                     0) + 1
        ran = sorted({pow2_ceil(n) for n in sizes.values()})
        t = time.perf_counter()
        tr = run.trace_events = T.load(trace_dir)
        run.programs = [handles[b].as_text() for b in ran]
        run.trace = T.reduce(
            tr, T.program_calls(run.programs),
            lambda fam: registry.load_module("work", fam).work,
            peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
        breakdown = {"device_ops": run.trace["device_ops"],
                     "idle_gaps": [[T.host_activity(tr, s, g), g * 1e-9]
                                   for s, g in run.trace["gaps"]]}
        log(f"trace read in {time.perf_counter() - t:.3f} s: buckets {ran}, "
            f"busy {run.trace['busy_s']:.6f} s of "
            f"{run.window_traced_s:.6f} s, kernels {run.trace['kernel_s']:.6f}"
            f" s in {run.trace['kernel_calls']} calls, families "
            f"{run.trace['families']}")
    listed = cell["per_layer"] if traced else cell["end_to_end"]
    metrics = {}
    for m in listed:
        value = registry.load_module("metrics", m["name"]).read(run)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    stats_mem = jax.devices()[0].memory_stats() or {}
    memory_peak = int(stats_mem.get("peak_bytes_in_use", 0))
    del handles
    serve.release(system)
    t = time.perf_counter()
    checks, compared = serve.check(cfg, system, run.requests)
    log(f"reference: {compared} answers of {len({r.img for r in run.requests})}"
        f" images compared in {time.perf_counter() - t:.3f} s")
    failed = sum(1 for r in run.requests
                 if not (r.ticket.done and r.ticket.error is None))
    out = {"correct": serve.passed(checks, compared),
           "attempted": len(run.requests), "failed": failed,
           "metrics": metrics, "memory_peak_bytes": memory_peak,
           "checks": checks}
    if traced:
        out["device_times"] = {"busy_s": run.trace["busy_s"],
                               "window_s": run.window_traced_s}
        out["breakdown"] = breakdown
    return out


if __name__ == "__main__":
    sys.exit(main())

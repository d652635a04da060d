#!/usr/bin/env python3
"""Serve resnet50 at 224² through its selected Pallas plan on one TPU chip.

Run from the repository root, on a machine with a TPU:

    python3 chip_smoke.py

The quickest proof that the system still starts on the chip. It drives the
main path through the entry points a user calls, with random weights from a
seed, and checks what comes out:

1. the first device must be a TPU — there is no CPU fallback;
2. ``optimise("resnet50", PallasPlatform(), executable=True)`` with no
   artifact store, so nothing warm-starts from ``artifacts/``;
3. ``OptimisedServer(fallback=False, max_batch=8)`` registers the result and
   compiles one dispatch handle per pow2 bucket (1, 2, 4, 8);
4. bursts of 1, 2, 4 and 8 requests, one dispatch per bucket;
5. each handle's optimised program holds a ``tpu_custom_call`` for every
   tile-variant step of the plan, so no step fell back to interpret mode;
6. every served output agrees with a float32 reference that uses no Pallas
   kernel: the interpreted executor over ``pipeline.safe_assignment``, run
   at ``jax.default_matmul_precision("highest")``.

Any failed, degraded or retried ticket, a bucket without a handle, a
missing kernel or an output off the reference fails the run. Times printed
on the way are set-up information, not results. The last line of standard
output is the JSON verdict ``{"ok": true, "device": {...}}``; a failing
run exits nonzero and prints no verdict.
"""
from __future__ import annotations

import collections
import json
import sys
import time
from pathlib import Path

NET = "resnet50"
BURSTS = (1, 2, 4, 8)
SEED = 0
# Largest |served - reference| over max |reference|, per image. The served
# plan computes in float32 (plan._emit): unit roundoff 2^-24 ≈ 6e-8, and a
# dot of up to 4608 terms (512 channels x 3 x 3) rounds like
# sqrt(4608) * 6e-8 ≈ 4e-6; along resnet50's longest path of 49
# convolutions that is about sqrt(49) * 4e-6 ≈ 3e-5. 1e-3 leaves headroom
# for the MXU's multi-pass float32 and still fails a plan whose matmuls run
# at bf16 (5e-2 on a v5e) or a wrong kernel.
REL_TOL = 1e-3


def _log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(Exception):
    """A phase of the smoke run did not hold."""


def serve_and_check(net: str = NET, bursts=BURSTS, seed: int = SEED) -> int:
    """Optimise, register, serve and verify ``net``; returns the number of
    images served. Raises ``SmokeFailure`` when a check does not hold."""
    import jax
    import numpy as np

    from repro.primitives import layouts as L
    from repro.primitives.conv import split_tile
    from repro.primitives.executor import execute, make_weights
    from repro.primitives.plan import ConvStep, compile_plan
    from repro.service.pipeline import optimise, safe_assignment
    from repro.service.platforms import PallasPlatform
    from repro.service.serving.server import OptimisedServer

    max_batch = max(bursts)
    t0 = time.perf_counter()
    opt = optimise(net, PallasPlatform(), executable=True, seed=seed)
    _log(f"optimise {net}: {time.perf_counter() - t0:.1f} s (set-up), "
         f"predicted {opt.predicted_cost_s * 1e3:.3f} ms/img")
    conv_cols = [c for c in opt.assignment.values()
                 if split_tile(c)[1] is not None]
    families = collections.Counter(split_tile(c)[1].split("-")[0]
                                   for c in conv_cols)
    _log(f"assignment: {len(conv_cols)} tile-variant conv columns, by "
         f"variant family {dict(families)}")
    for col, n in sorted(collections.Counter(conv_cols).items()):
        _log(f"  {n:3d} x {col}")

    spec = opt.spec
    weights = make_weights(spec, seed=seed)
    # a budget far above any bucket's run time pins the batch cap at
    # max_batch, so each burst below dispatches as exactly one batch
    server = OptimisedServer(fallback=False, max_batch=max_batch,
                             latency_budget_ms=600_000.0)
    try:
        t0 = time.perf_counter()
        server.register(opt, weights=weights)
        _log(f"register (compile + warm every bucket): "
             f"{time.perf_counter() - t0:.1f} s (set-up)")
        st = server.stats(net)
        for b, sec in sorted(st["precompiled"].items()):
            _log(f"  bucket {b}: compile {sec:.1f} s (set-up)")
        if st["precompile_error"]:
            raise SmokeFailure(f"precompile: {st['precompile_error']}")
        missing = sorted(set(bursts) - set(st["precompiled"]))
        if missing:
            raise SmokeFailure(f"no precompiled handle for buckets {missing}")

        n0 = spec.nodes[0]
        rng = np.random.default_rng(seed)
        xs = rng.standard_normal((sum(bursts), n0.c, n0.im, n0.im)
                                 ).astype(np.float32)
        served, k = [], 0
        for n in bursts:
            t0 = time.perf_counter()
            try:
                outs = server.serve(net, xs[k:k + n])
            except RuntimeError as e:
                raise SmokeFailure(f"burst of {n}: {e}") from e
            _log(f"burst of {n}: {(time.perf_counter() - t0) * 1e3:.1f} ms "
                 f"wall (set-up, not a result)")
            served.extend(outs)
            k += n
        st = server.stats(net)
        _log(f"served {st['images']} images in {st['dispatches']} "
             f"dispatches; failed_dispatches={st['failed_dispatches']} "
             f"fallback_images={st['fallback_images']} "
             f"retries={st['retries']}")
        if (len(served) != len(xs) or any(o is None for o in served)
                or st["images"] != len(xs)):
            raise SmokeFailure(f"{len(xs)} submitted, {st['images']} served")
        for fld in ("failed_dispatches", "fallback_images", "retries",
                    "failed_tickets"):
            if st[fld]:
                raise SmokeFailure(f"{fld} = {st[fld]}")

        handles = server.plan_handles(net)
        for b in bursts:
            plan = compile_plan(spec, opt.assignment, (b, n0.c, n0.im, n0.im))
            want = sum(1 for s in plan.steps
                       if isinstance(s, ConvStep) and s.variant is not None)
            got = handles[b].as_text().count(
                'custom_call_target="tpu_custom_call"')
            _log(f"bucket {b}: {got} tpu_custom_call for {want} "
                 f"tile-variant steps")
            if got < want:
                raise SmokeFailure(f"bucket {b}: {want - got} tile-variant "
                                   f"steps have no Pallas kernel")
        sink = plan.sinks[-1]
        sink_layout = plan.layouts[sink]
    finally:
        server.stop()

    safe = safe_assignment(spec)
    t0 = time.perf_counter()
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for x, out in zip(xs, served):
            ref = np.asarray(execute(spec, safe, weights=weights, x=x,
                                     compiled=False).outputs[sink])
            got = np.asarray(L.to_chw(out, sink_layout))
            if got.shape != ref.shape or not np.isfinite(got).all():
                raise SmokeFailure(f"served output {got.shape} vs reference "
                                   f"{ref.shape}, or not finite")
            scale = float(np.abs(ref).max())
            worst = max(worst, float(np.abs(got - ref).max()) / scale)
    _log(f"reference ({len(xs)} images, float32, highest precision): "
         f"{time.perf_counter() - t0:.1f} s (set-up)")
    _log(f"largest relative error vs reference: {worst:.3e} "
         f"(tolerance {REL_TOL:g} of max |reference|)")
    if not worst <= REL_TOL:
        raise SmokeFailure(f"relative error {worst:.3e} > {REL_TOL:g}")
    return len(xs)


def main() -> int:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] FAIL: first device is {dev.platform!r}, not a TPU; "
              f"this check never falls back to the CPU", file=sys.stderr)
        return 1
    _log(f"device_kind={dev.device_kind!r} count={len(devices)}")

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"[smoke] FAIL: no repository source at {src}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(src))
    from repro.compile_cache import enable_compile_cache
    _log(f"compile cache: {enable_compile_cache()}")
    try:
        serve_and_check()
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

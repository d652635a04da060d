"""Paper Table 4: time to optimise each CNN — performance-model inference
vs on-device profiling.

The model-inference time is measured for real (batched NN2 forward + PBQP).
The profiling cost is what the simulators say the measurements would take:
25 repeats of every applicable primitive on every layer (paper §4.1.1) plus
DLT profiling.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit, trained_model
from repro.core.selection import ModelProvider, SimulatedProvider, select
from repro.models import cnn_zoo
from repro.primitives.conv import REGISTRY
from repro.profiler.simulators import PLATFORMS, dlt_time, primitive_time


def profiling_seconds(spec, platform: str, repeats: int = 25) -> float:
    plat = PLATFORMS[platform]
    total = 0.0
    for layer in spec.conv_layers:
        for p in REGISTRY.values():
            t = primitive_time(plat, p, *layer.config, noisy=False)
            if np.isfinite(t):
                total += t * repeats
    for (c, im) in {( l.k, l.out_im) for l in spec.conv_layers}:
        for s in ("chw", "hcw", "hwc"):
            for d in ("chw", "hcw", "hwc"):
                if s != d:
                    total += dlt_time(plat, s, d, c, im, noisy=False) * repeats
    return total


def main() -> dict:
    prim_m = trained_model("nn2", "intel")
    dlt_m = trained_model("nn2", "intel", role="dlt")
    provider = ModelProvider(prim_m, dlt_m)
    results = {}
    for net in cnn_zoo.PAPER_SELECTION_NETS:
        spec = cnn_zoo.get(net)
        t0 = time.perf_counter()
        res = select(spec, provider)
        model_ms = (time.perf_counter() - t0) * 1e3
        prof = {p: profiling_seconds(spec, p) for p in ("intel", "amd", "arm")}
        speedup = prof["arm"] / (model_ms / 1e3)
        results[net] = {"model_ms": model_ms, **{f"profile_{k}_s": v for k, v in prof.items()},
                        "speedup_vs_arm_profiling": speedup}
        # emit() takes microseconds per call; name the unit in the label so
        # the value and its label agree (model_ms is milliseconds).
        emit(f"table4.{net}.model_inference_us", model_ms * 1e3,
             f"model={model_ms:.3f}ms profiling intel={prof['intel']:.0f}s "
             f"amd={prof['amd']:.0f}s arm={prof['arm']:.0f}s "
             f"speedup={speedup:.0f}x optimal={res.optimal}")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

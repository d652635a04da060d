"""Real-hardware validation (DESIGN.md §2.1): profile actual JAX primitives
on this container's CPU, train a perf model on the measurements, PBQP-select
for AlexNet, execute the selected network and compare wall-clock against a
fixed-primitive baseline. Small scale — the simulators carry the full-size
study; this proves the pipeline on physical hardware."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import FAST, emit
from repro.core.perfmodel import fit_perf_model
from repro.core.selection import MeasuredProvider, ModelProvider, select
from repro.models import cnn_zoo
from repro.primitives.executor import execute, make_weights
from repro.profiler import host

PRIMS = ["im2col-copy-ab-ki", "im2col-scan-ab-ki", "kn2row", "direct-sum2d",
         "mec-col", "winograd-2x2-3x3", "winograd-4x4-3x3", "conv-1x1-gemm-ab-ki"]


def main() -> dict:
    # 1. profile a small config pool on THIS cpu
    pool = [(16, 8, 28, 1, 3), (32, 16, 28, 1, 3), (32, 16, 14, 1, 3),
            (64, 32, 14, 1, 3), (16, 8, 28, 2, 3), (32, 16, 28, 1, 1),
            (64, 32, 14, 1, 1), (16, 8, 28, 1, 5), (32, 16, 14, 1, 5),
            (64, 64, 7, 1, 3), (48, 24, 20, 1, 3), (24, 12, 24, 1, 3)]
    if FAST:
        pool = pool[:6]
    t0 = time.perf_counter()
    ds = host.profile_primitive_dataset(pool, primitives=PRIMS, repeats=5)
    t_profile = time.perf_counter() - t0
    dlt = host.profile_dlt_dataset([(8, 28), (16, 28), (32, 14), (64, 7)], repeats=5)

    # 2. train small models on the measurements
    n = ds.n
    m = fit_perf_model("nn2", ds.feats[:n - 2], ds.times[:n - 2],
                       ds.feats[n - 2:], ds.times[n - 2:],
                       columns=ds.columns, max_iters=1500, patience=150)
    md = fit_perf_model("lin", dlt.feats[:-1], dlt.times[:-1],
                        dlt.feats[-1:], dlt.times[-1:], columns=dlt.columns)
    mdrae_fit = m.mdrae(ds.feats, ds.times)

    # 3. select for a reduced AlexNet-like chain and execute for real
    from repro.models.cnn_zoo import CNNSpec, ConvLayer
    spec = CNNSpec("mini-alexnet", [
        ConvLayer("c1", 16, 8, 28, 1, 3), ConvLayer("c2", 32, 16, 26, 1, 3),
        ConvLayer("c3", 64, 32, 24, 1, 3), ConvLayer("c4", 64, 64, 22, 1, 1),
    ], [(0, 1), (1, 2), (2, 3)])
    provider = ModelProvider(m, md)
    provider.columns = PRIMS
    sel = select(spec, provider)
    weights = make_weights(spec)
    rep_sel = execute(spec, sel.assignment, weights, measure=True, repeats=5)
    base_assignment = {i: "direct-sum2d" for i in range(4)}
    rep_base = execute(spec, base_assignment, weights, measure=True, repeats=5)
    speedup = rep_base.total_seconds / max(rep_sel.total_seconds, 1e-12)

    emit("realcpu.profile_stage", t_profile * 1e6,
         f"configs={len(pool)} prims={len(PRIMS)}")
    emit("realcpu.model_fit_mdrae", mdrae_fit * 100, "")
    emit("realcpu.selected_exec", rep_sel.total_seconds * 1e6,
         f"baseline={rep_base.total_seconds*1e6:.0f}us speedup={speedup:.2f}x "
         f"assignment={[sel.assignment[i] for i in range(4)]}")
    return {"profile_s": t_profile, "mdrae": mdrae_fit, "speedup": speedup}


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

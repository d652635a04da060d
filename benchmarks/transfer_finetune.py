"""Paper Figs 9/10: fine-tuning the Intel model to AMD/ARM vs training from
scratch, across training-data fractions — through the service layer's
calibrate path (repro.service.platforms), with ground-truth scoring over a
prebuilt PBQP graph (one O(build), many evaluations)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import (FAST, emit, platform as sim_platform, store,
                               trained_model)
from repro.core.selection import build_pbqp, network_cost, select
from repro.models import cnn_zoo

FRACTIONS = (0.001, 0.01, 0.1, 0.25) if not FAST else (0.01, 0.1)
SEEDS = (0, 1) if not FAST else (0,)


def main() -> dict:
    results = {}
    intel = trained_model("nn2", "intel")
    spec = cnn_zoo.get("googlenet")
    for plat in ("amd", "arm"):
        platform = sim_platform(plat)
        ds = platform.primitive_dataset()
        _, _, te = ds.split()
        truth = platform.cost_provider()
        g_truth = build_pbqp(spec, truth)    # one build, many evaluations
        c_opt = select(spec, truth).solver_cost
        full = trained_model("nn2", plat)
        results[f"{plat}.full"] = full.mdrae(te.feats, te.times)
        for frac in FRACTIONS:
            for mode in ("scratch", "finetune"):
                errs, incs = [], []
                for seed in SEEDS:
                    cal = platform.calibrate(
                        intel, frac, mode=mode, store=store(), seed=seed,
                        dlt_kind="nn2",
                        dlt_max_iters=8000 if not FAST else 2000,
                        max_iters=2000 if not FAST else 1200)
                    errs.append(cal.prim.mdrae(te.feats, te.times))
                    sel = select(spec, cal.provider())
                    c = network_cost(spec, sel.assignment, graph=g_truth)
                    incs.append(100.0 * (c / c_opt - 1.0))
                md, inc = float(np.mean(errs)), float(np.mean(incs))
                results[f"{plat}.{mode}.{frac}"] = {"mdrae": md, "increase_pct": inc}
                emit(f"fig9.{plat}.{mode}.frac{frac}", md * 100,
                     f"mdrae={md*100:.1f}% increase={inc:.2f}%")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

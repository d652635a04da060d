"""Paper Fig 8: the Intel model applied to AMD/ARM — directly and with the
1%-sample per-primitive factor correction — at both the estimation level
(MdRAE) and the GoogLeNet-selection level."""
from __future__ import annotations

from benchmarks.common import dataset, emit, trained_model
from repro.core.perfmodel import factor_correct
from repro.core.selection import (ModelProvider, SimulatedProvider, build_pbqp,
                                  network_cost, select)
from repro.models import cnn_zoo


def main() -> dict:
    results = {}
    intel = trained_model("nn2", "intel")
    intel_dlt = trained_model("nn2", "intel", role="dlt")
    spec = cnn_zoo.get("googlenet")
    for plat in ("amd", "arm"):
        ds = dataset(plat)
        tr, va, te = ds.split()
        native = trained_model("nn2", plat)
        sample = tr.subsample(0.01, seed=0)
        corrected = factor_correct(intel, sample.feats, sample.times)

        truth = SimulatedProvider(plat)
        g_truth = build_pbqp(spec, truth)        # one build, many evaluations
        c_opt = select(spec, truth).solver_cost
        dlt_native = trained_model("nn2", plat, role="dlt")
        for tag, model in (("intel", intel), ("factor_intel", corrected),
                           ("native", native)):
            md = model.mdrae(te.feats, te.times)
            prov = ModelProvider(model, dlt_native)
            c = network_cost(spec, select(spec, prov).assignment, graph=g_truth)
            inc = 100.0 * (c / c_opt - 1.0)
            results[f"{plat}.{tag}"] = {"mdrae": md, "increase_pct": inc}
            emit(f"fig8.{plat}.{tag}", md * 100,
                 f"mdrae={md*100:.1f}% googlenet_increase={inc:.2f}%")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

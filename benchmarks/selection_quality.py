"""Paper Fig 7: relative inference-time increase when optimising with
performance-model estimates instead of measured (simulated) times."""
from __future__ import annotations

from benchmarks.common import emit, trained_model
from repro.core.selection import (ModelProvider, SimulatedProvider, build_pbqp,
                                  network_cost, select)
from repro.models import cnn_zoo


def main() -> dict:
    results = {}
    for plat in ("intel", "amd", "arm"):
        prim_m = trained_model("nn2", plat)
        dlt_m = trained_model("nn2", plat, role="dlt")
        model = ModelProvider(prim_m, dlt_m)
        truth = SimulatedProvider(plat)
        for net in cnn_zoo.PAPER_SELECTION_NETS:
            spec = cnn_zoo.get(net)
            sel_model = select(spec, model)
            sel_truth = select(spec, truth)
            c_model = network_cost(spec, sel_model.assignment,
                                   graph=build_pbqp(spec, truth))
            c_truth = sel_truth.solver_cost
            inc = 100.0 * (c_model / c_truth - 1.0)
            results[f"{plat}.{net}"] = inc
            emit(f"fig7.{plat}.{net}.increase_pct", inc,
                 f"truth={c_truth*1e3:.3f}ms model={c_model*1e3:.3f}ms")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

"""Benchmark harness — one function per paper table/figure (+ roofline and
the TPU autotune feature). Prints ``name,us_per_call,derived`` CSV.

Set REPRO_BENCH_FAST=1 for a reduced-size pass.
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (autotune_tpu, dlt_accuracy, perfmodel_accuracy,
                            real_cpu_pipeline, roofline, selection_quality,
                            selection_speed, transfer_factor,
                            transfer_families, transfer_finetune)
    suites = [
        ("fig4/5 perf-model accuracy", perfmodel_accuracy),
        ("fig6 DLT accuracy", dlt_accuracy),
        ("table4 selection speed", selection_speed),
        ("fig7 selection quality", selection_quality),
        ("fig8 factor transfer", transfer_factor),
        ("fig9/10 fine-tune transfer", transfer_finetune),
        ("table5 family transfer", transfer_families),
        ("real-CPU pipeline", real_cpu_pipeline),
        ("TPU kernel autotune", autotune_tpu),
        ("roofline (dry-run artifacts)", roofline),
    ]
    failures = 0
    for name, mod in suites:
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        try:
            mod.main()
        except Exception:
            failures += 1
            traceback.print_exc()
        print(f"# ({name}: {time.time()-t0:.1f}s)", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

"""DESIGN.md §2.2 + §9: the paper's technique on TPU kernel variants.

Two sections:

  * **LM matmul sites** (the original rows): NN2 cost model over Pallas
    matmul block configs, PBQP-selected per matmul site for every assigned
    architecture.
  * **CNN zoo through the platform path** (PR 6): the wide simulator base
    model is transferred onto ``PallasPlatform`` — whose 40 columns are
    (conv primitive, matmul tile config) pairs priced by the autotune cost
    surface — and the PBQP selects tile configs exactly like primitives.
    For each zoo net the model-selected assignment over ALL tile columns is
    scored against the same model restricted to the FIXED DEFAULT tile
    (the first ``VARIANTS`` entry), both under the ground-truth provider.

Writes ``BENCH_autotune.json``. ``--smoke`` (the CI gate) exits nonzero
unless the autotuned tile selection beats the fixed default tile config on
at least one zoo net — i.e. unless tile-config selection is actually worth
doing, the paper's premise applied to kernel autotuning.

Run:  PYTHONPATH=src:. python benchmarks/autotune_tpu.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict

from benchmarks.common import emit
from repro.configs import base as cb
from repro.core.autotune import autotune_arch, train_cost_model

OUT_PATH = os.environ.get("REPRO_BENCH_AUTOTUNE_JSON", "BENCH_autotune.json")

ZOO_NETS = ("edge_cnn", "squeezenet", "mobilenet")


def lm_rows(max_iters: int) -> Dict:
    model = train_cost_model(max_iters=max_iters)
    results = {}
    for arch in cb.ASSIGNED_ARCHS:
        cfg = cb.get(arch)
        res = autotune_arch(cfg, model)
        gap = (res.predicted_s / res.oracle_s - 1.0) * 100 if res.oracle_s else 0.0
        results[arch] = {"speedup": res.speedup_vs_default,
                         "gap_to_oracle_pct": gap,
                         "assignment": res.assignment}
        emit(f"autotune.{arch}", res.predicted_s * 1e6,
             f"speedup_vs_default={res.speedup_vs_default:.2f}x "
             f"oracle_gap={gap:.1f}%")
    return results


def cnn_rows(*, max_triplets: int, max_iters: int, nets=ZOO_NETS) -> Dict:
    """Transfer the simulator base onto the Pallas platform, then per net:
    PBQP over all (primitive, tile) columns vs the same model pinned to the
    default tile — both scored by the ground-truth tile cost provider."""
    from repro.core.selection import ModelProvider, build_pbqp, network_cost, select
    from repro.kernels.matmul.ops import VARIANTS
    from repro.models import cnn_zoo
    from repro.service import PallasPlatform, get_platform

    base = get_platform("intel", max_triplets=max_triplets).pretrain(
        "nn2", max_iters=max_iters)
    tpu = PallasPlatform(max_triplets=max_triplets)
    models = tpu.calibrate(base, budget=0.05, mode="factor")
    default_tile = next(iter(VARIANTS))
    default_cols = [c for c in tpu.columns if c.endswith(f"@{default_tile}")]
    truth = tpu.cost_provider()

    results: Dict = {"default_tile": default_tile,
                     "columns": len(tpu.columns), "nets": {}}
    for net in nets:
        spec = cnn_zoo.get(net)
        tuned = select(spec, models.provider())
        fixed = select(spec, models.provider(columns=default_cols))
        graph = build_pbqp(spec, truth)
        tuned_s = network_cost(spec, tuned.assignment, graph=graph)
        fixed_s = network_cost(spec, fixed.assignment, graph=graph)
        speedup = fixed_s / tuned_s if tuned_s else 0.0
        tiles = sorted({v.split("@")[1] for v in tuned.assignment.values()
                        if "@" in v})
        results["nets"][net] = {
            "autotuned_s": tuned_s, "default_tile_s": fixed_s,
            "speedup_vs_default_tile": speedup,
            "tiles_selected": tiles,
        }
        emit(f"autotune.cnn.{net}", tuned_s * 1e6,
             f"speedup_vs_default_tile={speedup:.3f}x "
             f"tiles={len(tiles)}")
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small pools / fewer iters; gate: autotuned tiles "
                         "must beat the default tile on >= 1 zoo net")
    args = ap.parse_args(argv)

    max_iters = 600 if args.smoke else 3000
    max_triplets = 30 if args.smoke else 60

    results = {"mode": "smoke" if args.smoke else "full",
               "lm": lm_rows(max_iters),
               "cnn": cnn_rows(max_triplets=max_triplets,
                               max_iters=max_iters)}
    with open(OUT_PATH, "w") as fh:
        json.dump(results, fh, indent=2)
    wins = [n for n, r in results["cnn"]["nets"].items()
            if r["speedup_vs_default_tile"] > 1.0]
    print(f"wrote {OUT_PATH} (autotuned tiles beat the default tile on "
          f"{len(wins)}/{len(results['cnn']['nets'])} nets)")

    if not wins:
        print("FAIL: autotuned tile selection did not beat the fixed "
              "default tile config on any zoo net", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())

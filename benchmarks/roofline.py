"""§Roofline: the 40-cell baseline table, read from dry-run artifacts
(artifacts/dryrun/*.json — produced by ``python -m repro.launch.dryrun
--all``). Prints the per-cell three-term decomposition and flags cells over
the v5e HBM budget."""
from __future__ import annotations

import glob
import json
import os

from benchmarks.common import ART, emit

HBM = 16e9


def load(tag: str = "") -> list:
    suffix = f".{tag}.json" if tag else ".json"
    rows = []
    for f in sorted(glob.glob(os.path.join(ART, "dryrun", "*" + suffix))):
        base = os.path.basename(f)[:-len(suffix)]
        if not tag and base.count(".") > 2:
            continue            # skip tagged artifacts in the untagged view
        d = json.load(open(f))
        rows.append(d)
    return rows


def main() -> dict:
    results = {}
    for d in load():
        key = f"{d['arch']}.{d['shape']}.{'multi' if d['multi_pod'] else 'single'}"
        if d["status"] == "skipped":
            emit(f"roofline.{key}", 0.0, "SKIPPED (full attention)")
            continue
        if d["status"] != "ok":
            emit(f"roofline.{key}", 0.0, "ERROR")
            continue
        r = d["roofline"]
        mem = d["memory"]["peak_bytes_est"]
        over = " OVER-HBM" if mem > HBM else ""
        results[key] = r
        emit(f"roofline.{key}", r["step_time_s"] * 1e6,
             f"compute={r['compute_s']*1e3:.1f}ms memory={r['memory_s']*1e3:.1f}ms "
             f"collective={r['collective_s']*1e3:.1f}ms dom={r['dominant']} "
             f"useful={r['useful_fraction']:.3f} mem={mem/1e9:.1f}GB{over}")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

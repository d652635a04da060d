"""Paper Table 5: cross-family transfer — fine-tune the Intel model to AMD
with data from ONE primitive family, evaluate on every family. Rows are
normalised so the diagonal is 1."""
from __future__ import annotations

import numpy as np

from benchmarks.common import FAST, dataset, emit, trained_model
from repro.core.perfmodel import fit_perf_model
from repro.primitives.conv import FAMILIES, REGISTRY


def main() -> dict:
    intel = trained_model("nn2", "intel")
    ds = dataset("amd")
    tr, va, te = ds.split()
    col_fam = [REGISTRY[c].family for c in ds.columns]

    def fam_errs(model) -> dict:
        per = model.mdrae_per_column(te.feats, te.times)
        return {f: float(np.nanmedian([per[j] for j in range(len(per))
                                       if col_fam[j] == f]))
                for f in FAMILIES}

    mat = {}
    for train_fam in FAMILIES:
        # fine-tune with ONLY this family's labels (others masked out)
        times = tr.times.copy()
        for j, f in enumerate(col_fam):
            if f != train_fam:
                times[:, j] = np.nan
        m = fit_perf_model("nn2", tr.feats, times, va.feats, va.times,
                           columns=ds.columns, base=intel,
                           max_iters=2000 if not FAST else 800, patience=150)
        mat[train_fam] = fam_errs(m)

    results = {}
    for trf in FAMILIES:
        diag = mat[trf][trf]
        row = {evf: (mat[trf][evf] / diag if diag > 0 else float("nan"))
               for evf in FAMILIES}
        results[trf] = row
        emit(f"table5.{trf}", diag * 100,
             " ".join(f"{evf}={row[evf]:.1f}" for evf in FAMILIES))
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

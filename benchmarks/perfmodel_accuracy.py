"""Paper Figs 4 & 5: MdRAE of Lin / NN1 / NN2 per primitive family.

Fig 4: all three model kinds on the intel dataset.
Fig 5: NN2 on amd / arm.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import dataset, emit, trained_model
from repro.primitives.conv import REGISTRY, FAMILIES


def _family_mdrae(model, te) -> dict:
    per_col = model.mdrae_per_column(te.feats, te.times)
    out = {}
    for fam in FAMILIES:
        vals = [per_col[j] for j, n in enumerate(te.columns)
                if REGISTRY[n].family == fam and np.isfinite(per_col[j])]
        out[fam] = float(np.median(vals)) if vals else float("nan")
    return out


def main() -> dict:
    results = {}
    ds = dataset("intel")
    tr, va, te = ds.split()
    for kind, iters in (("lin", 0), ("nn1", 2500), ("nn2", 8000)):
        m = trained_model(kind, "intel", max_iters=max(iters, 1))
        fam = _family_mdrae(m, te)
        overall = m.mdrae(te.feats, te.times)
        results[f"intel_{kind}"] = {"overall": overall, **fam}
        emit(f"fig4.intel.{kind}.mdrae", overall * 100,
             " ".join(f"{k}={v*100:.1f}%" for k, v in fam.items()))
    for plat in ("amd", "arm"):
        ds_p = dataset(plat)
        _, _, te_p = ds_p.split()
        m = trained_model("nn2", plat)
        fam = _family_mdrae(m, te_p)
        overall = m.mdrae(te_p.feats, te_p.times)
        results[f"{plat}_nn2"] = {"overall": overall, **fam}
        emit(f"fig5.{plat}.nn2.mdrae", overall * 100,
             " ".join(f"{k}={v*100:.1f}%" for k, v in fam.items()))
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

"""Paper Fig 6: MdRAE of the data-layout-transformation cost models."""
from __future__ import annotations

from benchmarks.common import dlt_dataset, emit, trained_model


def main() -> dict:
    results = {}
    ds = dlt_dataset("intel")
    _, _, te = ds.split()
    for kind in ("lin", "nn1", "nn2"):
        m = trained_model(kind, "intel", role="dlt", max_iters=4000)
        overall = m.mdrae(te.feats, te.times)
        per = m.mdrae_per_column(te.feats, te.times)
        results[kind] = {"overall": overall,
                         **{c: float(p) for c, p in zip(te.columns, per)}}
        emit(f"fig6.dlt.{kind}.mdrae", overall * 100,
             " ".join(f"{c}={p*100:.1f}%" for c, p in zip(te.columns, per)))
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

"""End-to-end selection throughput: the profile→estimate→select hot path.

Measures (a) cost-matrix + PBQP-graph construction for a VGG-19-scale spec
through the seed's scalar per-(layer, primitive) path versus the vectorised
batch path (identical inputs, numerically identical graphs — see
tests/test_batch_equivalence.py), and (b) steady-state full selections per
second (estimate + build + solve) over the CNN zoo with the batch path.

Writes ``BENCH_selection.json`` — the repo's first perf trajectory point —
with both the seed-equivalent scalar timing and the new batched timing.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict

import numpy as np

from benchmarks.common import emit
from repro.core import pbqp
from repro.core.selection import (SimulatedProvider, _DLT_COLS, _edge_tensor,
                                  _in_layout, _node_choices, _out_layout,
                                  build_pbqp, select)
from repro.models import cnn_zoo
from repro.models.cnn_zoo import CNNSpec, ConvLayer
from repro.primitives import layouts as L
from repro.primitives.conv import PRIMITIVE_NAMES, REGISTRY
from repro.profiler.simulators import (PLATFORMS, _dlt_time_scalar,
                                       _primitive_time_scalar)

OUT_PATH = os.environ.get("REPRO_BENCH_SELECTION_JSON", "BENCH_selection.json")


class ScalarSimulatedProvider:
    """Seed-equivalent provider: one scalar model call per (layer, primitive)
    cell and per (pair, DLT) cell — the pre-vectorisation baseline."""

    def __init__(self, platform: str, noisy: bool = True):
        self._plat = PLATFORMS[platform]
        self.noisy = noisy
        self.columns = list(PRIMITIVE_NAMES)

    def primitive_cost_matrix(self, configs: np.ndarray) -> np.ndarray:
        out = np.full((len(configs), len(self.columns)), np.nan)
        for i, (k, c, im, s, f) in enumerate(np.asarray(configs, int)):
            for j, name in enumerate(self.columns):
                out[i, j] = _primitive_time_scalar(
                    self._plat, REGISTRY[name], k, c, im, s, f, noisy=self.noisy)
        return out

    def dlt_cost_matrix(self, pairs: np.ndarray) -> np.ndarray:
        out = np.zeros((len(pairs), len(_DLT_COLS)))
        for i, (c, im) in enumerate(np.asarray(pairs, int)):
            j = 0
            for (s, d) in L.dlt_pairs():
                if s == d:
                    continue
                out[i, j] = _dlt_time_scalar(self._plat, s, d, int(c), int(im),
                                             noisy=self.noisy)
                j += 1
        return out


def build_pbqp_scalar(spec: CNNSpec, provider) -> pbqp.PBQPGraph:
    """Seed-equivalent graph construction: Python loop over every
    (producer choice, consumer choice) pair of every edge."""
    columns = list(provider.columns)
    convs = [(i, n) for i, n in enumerate(spec.nodes) if isinstance(n, ConvLayer)]
    configs = np.array([n.config for _, n in convs], np.float64)
    cost_mat = (provider.primitive_cost_matrix(configs)
                if len(convs) else np.zeros((0, len(columns))))
    pair_list = sorted({_edge_tensor(spec.nodes[u]) for (u, v) in spec.edges})
    pair_idx = {p: i for i, p in enumerate(pair_list)}
    dlt_mat = (provider.dlt_cost_matrix(np.array(pair_list, np.float64))
               if pair_list else np.zeros((0, len(_DLT_COLS))))
    dlt_col = {name: j for j, name in enumerate(_DLT_COLS)}

    def dlt(src, dst, c, im):
        if src == dst:
            return 0.0
        return float(max(dlt_mat[pair_idx[(c, im)], dlt_col[L.dlt_name(src, dst)]], 0.0))

    g = pbqp.PBQPGraph()
    conv_cost = {i: cost_mat[r] for r, (i, _) in enumerate(convs)}
    for i, node in enumerate(spec.nodes):
        choices = _node_choices(node, columns)
        if isinstance(node, ConvLayer):
            vec = np.maximum(np.where(np.isfinite(conv_cost[i]),
                                      conv_cost[i], np.inf), 0.0)
        else:
            vec = np.zeros(len(choices))
        g.add_node(i, vec, labels=choices)
    for (u, v) in spec.edges:
        nu, nv = spec.nodes[u], spec.nodes[v]
        cu, cv = _node_choices(nu, columns), _node_choices(nv, columns)
        c, im = _edge_tensor(nu)
        m = np.zeros((len(cu), len(cv)))
        for a, pa in enumerate(cu):
            for b, pb in enumerate(cv):
                m[a, b] = dlt(_out_layout(nu, pa), _in_layout(nv, pb), c, im)
        g.add_edge(u, v, m)
    return g


def _median_seconds(fn, repeats: int) -> float:
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples))


def main() -> Dict:
    platform = "intel"
    spec = cnn_zoo.get("vgg19")

    # -- (a) cost-matrix + graph construction: scalar seed path vs batched --
    scalar_prov = ScalarSimulatedProvider(platform)
    batch_prov = SimulatedProvider(platform)
    build_pbqp(spec, batch_prov)                   # warm caches (traits etc.)
    scalar_s = _median_seconds(lambda: build_pbqp_scalar(spec, scalar_prov), 3)
    batched_s = _median_seconds(lambda: build_pbqp(spec, batch_prov), 9)
    speedup = scalar_s / batched_s
    emit("selection.vgg19.build_scalar_us", scalar_s * 1e6, "seed-equivalent path")
    emit("selection.vgg19.build_batched_us", batched_s * 1e6,
         f"vectorised path speedup={speedup:.1f}x")

    # -- (b) steady-state full selections/second over the CNN zoo ----------
    nets = {}
    total_s = 0.0
    for net in sorted(cnn_zoo.ZOO):
        sp = cnn_zoo.get(net)
        select(sp, batch_prov)                     # warm
        sel_s = _median_seconds(lambda: select(sp, batch_prov), 5)
        nets[net] = {"select_s": sel_s, "selections_per_s": 1.0 / sel_s,
                     "nodes": len(sp.nodes), "edges": len(sp.edges)}
        total_s += sel_s
        emit(f"selection.{net}.select_us", sel_s * 1e6,
             f"{1.0 / sel_s:.1f} selections/s nodes={len(sp.nodes)}")
    zoo_rate = len(nets) / total_s
    emit("selection.zoo.mean_select_us", total_s / len(nets) * 1e6,
         f"{zoo_rate:.1f} selections/s over {len(nets)} networks")

    results = {
        "platform": platform,
        "vgg19_build": {
            "scalar_seed_equivalent_s": scalar_s,
            "batched_s": batched_s,
            "speedup": speedup,
        },
        "zoo_selection": {
            "networks": nets,
            "mean_select_s": total_s / len(nets),
            "selections_per_s": zoo_rate,
        },
    }
    with open(OUT_PATH, "w") as fh:
        json.dump(results, fh, indent=2)
    print(f"wrote {OUT_PATH} (vgg19 build speedup {speedup:.1f}x, "
          f"{zoo_rate:.1f} selections/s)")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

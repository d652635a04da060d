"""Executor: run a CNN with a given primitive assignment on this host
(paper Fig 2 step iv). Supports chains and DAGs with concat/add joins;
inserts the data-layout transformations the assignment implies and can time
each component — the real-hardware end of the pipeline.

Two paths share this entry point:

* **compiled** (default for ``measure=False``): the whole assigned DAG is
  lowered by ``repro.primitives.plan.compile_plan`` into one jitted batched
  function — a single dispatch per call instead of ~2xN Python-level ones;
* **interpreted**: per-node jitted callables with explicit DLT dispatches —
  the per-component *measurement* path (``measure=True``), and the oracle
  the compiled plan is tested against.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.cnn_zoo import CNNSpec, ConvLayer, EltwiseLayer, JoinNode
from repro.primitives.conv import REGISTRY, resolve, split_tile
from repro.primitives import layouts as L
from repro.primitives import plan as P
from repro.primitives.variants import conv_variant_call, pad_input


# Jitted primitive/DLT callables cached across ``execute`` calls, keyed by
# (primitive, input shape, stride) — repeated serving traffic over the same
# network reuses compiled code instead of re-tracing every call. LRU-bounded
# so long-running multi-network serving cannot grow it without limit.
_JIT_CACHE: "OrderedDict[Tuple, Callable]" = OrderedDict()
_JIT_CACHE_CAP = 256


def clear_jit_cache() -> None:
    _JIT_CACHE.clear()


def evict_prim_entries(columns) -> int:
    """Drop cached primitive callables for the given (full, possibly
    tile-suffixed) column names — all shapes/strides. Called by the serving
    layer when a retired (net, generation) leaves columns no live
    registration uses (DESIGN.md §13.3). Returns the eviction count."""
    cols = set(columns)
    if not cols:
        return 0
    dead = [k for k in _JIT_CACHE if k[0] == "prim" and k[1] in cols]
    for k in dead:
        del _JIT_CACHE[k]
    return len(dead)


def _cached(key: Tuple, make: Callable[[], Callable]) -> Callable:
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = make()
        _JIT_CACHE[key] = fn
    else:
        _JIT_CACHE.move_to_end(key)
    while len(_JIT_CACHE) > _JIT_CACHE_CAP:
        _JIT_CACHE.popitem(last=False)
    return fn


def _cached_primitive(column: str, x: jnp.ndarray, w: jnp.ndarray,
                      stride: int, pad: int = 0) -> Callable:
    """Jitted callable for a (possibly tile-suffixed) column name. The FULL
    column name keys the cache — two tile variants of one base primitive are
    distinct compiled kernels, and must never share an entry. A padded
    layer pads as the plan does (``plan._conv``)."""
    base, variant = split_tile(column)
    prim = REGISTRY[base]
    key = ("prim", column, x.shape, str(x.dtype), w.shape, stride, pad)
    if variant is None:
        impl = prim.impl
        return _cached(key, lambda: jax.jit(
            lambda a, b: impl(pad_input(a, pad, prim.in_layout), b, stride)))
    return _cached(key, lambda: jax.jit(
        lambda a, b: conv_variant_call(prim, variant, a, b, stride,
                                       pad=pad)))


def _cached_dlt(src: str, dst: str, x: jnp.ndarray) -> Callable:
    key = ("dlt", src, dst, x.shape, str(x.dtype))
    return _cached(key, lambda: jax.jit(lambda a: L.transform(a, src, dst)))


@dataclasses.dataclass
class ExecutionReport:
    outputs: Dict[int, jnp.ndarray]
    primitive_seconds: Dict[int, float]
    dlt_seconds: Dict[Tuple[int, int], float]

    @property
    def total_seconds(self) -> float:
        return sum(self.primitive_seconds.values()) + sum(self.dlt_seconds.values())


def make_weights(spec: CNNSpec, seed: int = 0) -> Dict[int, jnp.ndarray]:
    rng = np.random.default_rng(seed)
    out = {}
    for i, node in enumerate(spec.nodes):
        if isinstance(node, ConvLayer):
            w = rng.standard_normal((node.k, node.c, node.f, node.f)) / (node.f * np.sqrt(node.c))
            out[i] = jnp.asarray(w, jnp.float32)
        elif isinstance(node, EltwiseLayer) and node.kind == "bias":
            out[i] = jnp.asarray(rng.standard_normal((node.c,)), jnp.float32)
    return out


def source_inputs(spec: CNNSpec, x: Optional[jnp.ndarray] = None) -> Dict[int, jnp.ndarray]:
    """chw input per source conv node: ``x`` if given, else N(0,1) draws
    (paper §4.1.1) — in topo order, so both executor paths see identical
    arrays for the same spec."""
    rng = np.random.default_rng(1)
    out: Dict[int, jnp.ndarray] = {}
    for i in P.source_nodes(spec):
        node = spec.nodes[i]
        if x is not None:
            out[i] = jnp.asarray(x, jnp.float32)
        else:
            out[i] = jnp.asarray(rng.standard_normal((node.c, node.im, node.im)),
                                 jnp.float32)
    return out


def execute(spec: CNNSpec, assignment: Dict[int, str],
            weights: Optional[Dict[int, jnp.ndarray]] = None,
            x: Optional[jnp.ndarray] = None,
            measure: bool = False, repeats: int = 5,
            compiled: Optional[bool] = None) -> ExecutionReport:
    """Run the network under ``assignment``. Inputs of source conv nodes are
    drawn from N(0,1) (paper §4.1.1) unless ``x`` is given (chw).

    With ``measure=True`` every primitive call and DLT is individually timed
    (jitted, warmed, median of ``repeats``) on the interpreted path;
    otherwise the call is a thin wrapper over the compiled whole-graph plan
    (``compiled=False`` forces the interpreted path without timing).
    """
    weights = weights if weights is not None else make_weights(spec)
    if compiled is None:
        compiled = not measure
    if measure or not compiled:
        return _execute_interpreted(spec, assignment, weights, x, measure, repeats)

    xs = source_inputs(spec, x)
    plan = P.compile_plan(spec, assignment,
                          tuple((1,) + v.shape for v in xs.values()),
                          outputs="all")
    outs = plan({i: v[None] for i, v in xs.items()}, weights)
    outputs = {i: o[0] for i, o in outs.items()}
    prim_secs = {i: 0.0 for i, n in enumerate(spec.nodes) if isinstance(n, ConvLayer)}
    return ExecutionReport(outputs, prim_secs, {})


def _execute_interpreted(spec: CNNSpec, assignment: Dict[int, str],
                         weights: Dict[int, jnp.ndarray],
                         x: Optional[jnp.ndarray],
                         measure: bool, repeats: int) -> ExecutionReport:
    order = P.topo_order(spec)
    prods = P.producers(spec)
    xs = source_inputs(spec, x)
    tensors: Dict[int, jnp.ndarray] = {}      # node -> output in its layout
    layouts: Dict[int, str] = {}
    prim_secs: Dict[int, float] = {}
    dlt_secs: Dict[Tuple[int, int], float] = {}

    def timed(jfn, *args) -> Tuple[jnp.ndarray, float]:
        y = jax.block_until_ready(jfn(*args))
        if not measure:
            return y, 0.0
        samples = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(jfn(*args))
            samples.append(time.perf_counter() - t0)
        return y, float(np.median(samples))

    def fetch_input(node_idx: int, want_layout: str) -> jnp.ndarray:
        """Gather and layout-convert the producer tensors for ``node_idx``."""
        ps = prods[node_idx]
        vals = []
        for p in ps:
            v, src = tensors[p], layouts[p]
            if src != want_layout:
                v2, dt = timed(_cached_dlt(src, want_layout, v), v)
                dlt_secs[(p, node_idx)] = dlt_secs.get((p, node_idx), 0.0) + dt
                v = v2
            vals.append(v)
        return vals

    for i in order:
        node = spec.nodes[i]
        if isinstance(node, ConvLayer):
            prim = resolve(assignment[i])
            if prim.impl is None:
                raise ValueError(f"assignment uses simulated-only primitive {prim.name}")
            if prods[i]:
                (xin,) = fetch_input(i, prim.in_layout)
            else:
                xin = L.from_chw(xs[i], prim.in_layout)
            y, dt = timed(_cached_primitive(assignment[i], xin, weights[i],
                                            node.s, node.p),
                          xin, weights[i])
            tensors[i], layouts[i] = y, prim.out_layout
            prim_secs[i] = dt
        elif isinstance(node, EltwiseLayer):
            lay = assignment[i]
            (v,) = fetch_input(i, lay)
            if node.kind == "relu":
                y = jnp.maximum(v, 0.0)
            elif node.kind == "bias":
                shape = [1, 1, 1]
                shape[L.C_AXIS[lay]] = node.c
                y = v + weights[i].reshape(shape)
            else:
                raise ValueError(node.kind)
            tensors[i], layouts[i] = y, lay
        else:
            lay = assignment[i]
            vals = fetch_input(i, lay)
            # Branches of valid (un-padded) convolutions can differ by a few
            # pixels across branch depths; centre-crop to the smallest
            # (a padded network such as darknet53 joins at equal sizes and
            # crops nothing; DESIGN.md §10).
            vals = P.crop_to_common(vals, lay)
            if node.kind == "concat":
                y = jnp.concatenate(vals, axis=L.C_AXIS[lay])
            elif node.kind == "add":
                y = vals[0]
                for v in vals[1:]:
                    y = y + v
            else:
                raise ValueError(node.kind)
            tensors[i], layouts[i] = y, lay

    return ExecutionReport(tensors, prim_secs, dlt_secs)

"""Plan compiler: lower an assigned CNN DAG into ONE jitted batched function.

The interpreted executor (repro.primitives.executor) dispatches ~2xN jitted
callables per image — one per primitive plus one per materialised DLT. The
paper's end product, though, is an *assignment* whose value is realised at
inference time; serving wants the assigned network treated as a single
compiled artifact (cf. Anderson & Gregg's PBQP formulation, and TASO's
whole-graph substitution view). ``compile_plan`` does that lowering:

* the topo-ordered DAG (convs, DLTs, concat/add joins, centre-crops) becomes
  one traced function over a leading batch axis, jitted once and cached by
  ``(spec, assignment, batch_shape)``;
* adjacent DLT -> primitive pairs are *fused*: a DLT is an axis permutation,
  so each edge carries a composed permutation that is (a) dropped when it is
  the identity, (b) inlined into the consumer's traced call otherwise —
  inside one XLA program the transpose fuses into the consumer's first read
  and the intermediate layout copy never materialises in HBM;
* primitives run through their batched entry points
  (``conv.batch_impl`` — rank-polymorphic impls, vmap fallback).

Lowering rules, fusion criteria and batch semantics: DESIGN.md §6.
"""
from __future__ import annotations

import dataclasses
import math
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.models.cnn_zoo import CNNSpec, ConvLayer, EltwiseLayer, JoinNode
from repro.primitives import layouts as L
from repro.primitives.conv import (REGISTRY, Primitive, batch_impl, resolve,
                                   split_tile, variant_compatible)
from repro.primitives.variants import conv_variant_call, gemm_path, pad_input



# ---------------------------------------------------------------------------
# Graph utilities (shared with the interpreted executor)
# ---------------------------------------------------------------------------

def consumers(spec: CNNSpec) -> Dict[int, List[int]]:
    out: Dict[int, List[int]] = {i: [] for i in range(len(spec.nodes))}
    for u, v in spec.edges:
        out[u].append(v)
    return out


def producers(spec: CNNSpec) -> Dict[int, List[int]]:
    out: Dict[int, List[int]] = {i: [] for i in range(len(spec.nodes))}
    for u, v in spec.edges:
        out[v].append(u)
    return out


def topo_order(spec: CNNSpec) -> List[int]:
    prods = producers(spec)
    indeg = {i: len(p) for i, p in prods.items()}
    ready = [i for i, d in indeg.items() if d == 0]
    order = []
    cons = consumers(spec)
    while ready:
        n = ready.pop()
        order.append(n)
        for v in cons[n]:
            indeg[v] -= 1
            if indeg[v] == 0:
                ready.append(v)
    if len(order) != len(spec.nodes):
        raise ValueError("cycle in CNN spec")
    return order


def source_nodes(spec: CNNSpec) -> List[int]:
    """Producer-less conv nodes, in topo order (the network inputs)."""
    prods = producers(spec)
    return [i for i in topo_order(spec)
            if not prods[i] and isinstance(spec.nodes[i], ConvLayer)]


def sink_nodes(spec: CNNSpec) -> List[int]:
    cons = consumers(spec)
    return [i for i in range(len(spec.nodes)) if not cons[i]]


def crop_to_common(vals: Sequence[jnp.ndarray], layout: str) -> List[jnp.ndarray]:
    """Centre-crop a list of same-layout tensors to the smallest spatial size
    (rank-polymorphic: layout describes the trailing three axes)."""
    ah, aw = L.SPATIAL_AXES[layout]
    h = min(v.shape[v.ndim - 3 + ah] for v in vals)
    w = min(v.shape[v.ndim - 3 + aw] for v in vals)
    out = []
    for v in vals:
        lead = v.ndim - 3
        sl = [slice(None)] * v.ndim
        oh = (v.shape[lead + ah] - h) // 2
        ow = (v.shape[lead + aw] - w) // 2
        sl[lead + ah] = slice(oh, oh + h)
        sl[lead + aw] = slice(ow, ow + w)
        out.append(v[tuple(sl)])
    return out


# ---------------------------------------------------------------------------
# Lowered steps
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EpilogueSpec:
    """Elementwise work folded into a ConvStep's kernel epilogue
    (bias -> residual -> ReLU, applied on the output tile before the HBM
    writeback — DESIGN.md §13.2). ``alias`` is the last fused node: the
    conv step now *produces* that node's output."""
    alias: int
    bias: Optional[int] = None                          # EltwiseLayer node (weights key)
    residual: Optional[Tuple[int, Tuple[int, int, int]]] = None  # (producer, perm)
    relu: bool = False

    @property
    def ops(self) -> Tuple[str, ...]:
        out = []
        if self.bias is not None:
            out.append("bias")
        if self.residual is not None:
            out.append("residual")
        if self.relu:
            out.append("relu")
        return tuple(out)


@dataclasses.dataclass(frozen=True)
class ConvStep:
    node: int
    prim: Primitive
    stride: int
    src: Optional[int]                    # None => network input
    perm: Tuple[int, int, int]            # fused DLT into prim.in_layout
    variant: Optional[str] = None         # Pallas tile variant ("mm-*", ...)
    epilogue: Optional[EpilogueSpec] = None
    pad: int = 0                          # zero padding on every side

    @property
    def out_node(self) -> int:
        """Node id this step's output stands for (the epilogue alias when
        elementwise consumers were folded in)."""
        return self.epilogue.alias if self.epilogue is not None else self.node


@dataclasses.dataclass(frozen=True)
class JoinStep:
    node: int
    kind: str                             # "concat" | "add"
    layout: str
    ins: Tuple[Tuple[int, Tuple[int, int, int]], ...]   # (producer, fused perm)


@dataclasses.dataclass(frozen=True)
class EltwiseStep:
    """Un-fused elementwise node (epilogue fusion off, or layout/ordering
    made folding impossible)."""
    node: int
    kind: str                             # "relu" | "bias"
    src: int
    perm: Tuple[int, int, int]
    layout: str


PlanStep = Union[ConvStep, JoinStep, EltwiseStep]


def _out_spatial(node) -> int:
    return node.out_im if isinstance(node, ConvLayer) else node.im


def lower(spec: CNNSpec, assignment: Dict[int, str], *,
          epilogues: bool = False) -> Tuple[List[PlanStep], Dict[int, str]]:
    """Lower the assigned DAG to a step list with DLT fusion applied.

    Returns the steps in topo order plus each node's produced layout. Every
    edge carries at most one axis permutation (identity permutations are
    eliminated at this stage, non-identity ones are inlined by the emitter).

    Tile columns ("base@variant") lower to the variant's Pallas kernel entry
    point (``primitives.variants``); ``variant_compatible`` pairs only —
    selection filters through ``conv.is_runnable`` so a rejection here means
    a hand-written assignment. With ``epilogues=True`` eligible elementwise
    consumers (bias add, ReLU, 2-input residual add) of an epilogue-capable
    conv are folded into the producing ConvStep's ``EpilogueSpec``: the conv
    step moves to the consumer's topo position and produces the consumer's
    output (``out_node``) — fusion criteria in DESIGN.md §13.2.
    """
    prods = producers(spec)
    cons = consumers(spec)
    steps: List[Optional[PlanStep]] = []
    prod_step: Dict[int, int] = {}        # node -> index of producing step
    layout_of: Dict[int, str] = {}

    def fusable(p: int, lay: str) -> Optional[ConvStep]:
        """The ConvStep producing node ``p`` if an epilogue can fold onto it:
        epilogue-capable base, chw output matching ``lay``, ``p`` consumed
        exactly once (by the node being lowered)."""
        st = steps[prod_step[p]] if p in prod_step else None
        if (isinstance(st, ConvStep) and st.prim.traits.get("epilogue")
                and st.prim.out_layout == "chw" and lay == "chw"
                and len(cons[p]) == 1):
            return st
        return None

    def refuse(p: int, st: ConvStep, ep: EpilogueSpec) -> None:
        """Move ``st`` (producer of ``p``) to the current topo position with
        the grown epilogue — its output now stands for ``ep.alias``."""
        steps[prod_step[p]] = None
        steps.append(dataclasses.replace(st, epilogue=ep))
        prod_step[ep.alias] = len(steps) - 1
        layout_of[ep.alias] = "chw"

    for i in topo_order(spec):
        node = spec.nodes[i]
        if isinstance(node, ConvLayer):
            base, variant = split_tile(assignment[i])
            prim = REGISTRY.get(base)
            if prim is None or prim.impl is None:
                raise ValueError(f"assignment uses simulated-only primitive {base}")
            if variant is not None and not variant_compatible(base, variant):
                raise ValueError(f"tile variant {variant!r} cannot lower "
                                 f"through {base!r} (node {i})")
            ps = prods[i]
            if len(ps) > 1:
                raise ValueError(f"conv node {i} has {len(ps)} producers")
            if ps:
                pm = L.perm(layout_of[ps[0]], prim.in_layout)
                steps.append(ConvStep(i, prim, node.s, ps[0], pm, variant,
                                      pad=node.p))
            else:
                pm = L.perm("chw", prim.in_layout)     # inputs arrive chw
                steps.append(ConvStep(i, prim, node.s, None, pm, variant,
                                      pad=node.p))
            prod_step[i] = len(steps) - 1
            layout_of[i] = prim.out_layout
        elif isinstance(node, EltwiseLayer):
            lay = assignment[i]
            if lay not in L.LAYOUTS:
                raise ValueError(f"eltwise node {i} assigned non-layout {lay!r}")
            (p,) = prods[i]
            st = fusable(p, lay) if epilogues else None
            ep = st.epilogue if st is not None else None
            if st is not None and node.kind == "bias" and (
                    ep is None or (ep.bias is None and ep.residual is None
                                   and not ep.relu)):
                refuse(p, st, EpilogueSpec(alias=i, bias=i,
                                           residual=ep.residual if ep else None,
                                           relu=False))
            elif st is not None and node.kind == "relu" and (
                    ep is None or not ep.relu):
                refuse(p, st, dataclasses.replace(
                    ep or EpilogueSpec(alias=i), alias=i, relu=True))
            else:
                pm = L.perm(layout_of[p], lay)
                steps.append(EltwiseStep(i, node.kind, p, pm, lay))
                prod_step[i] = len(steps) - 1
                layout_of[i] = lay
        else:
            lay = assignment[i]
            if lay not in L.LAYOUTS:
                raise ValueError(f"join node {i} assigned non-layout {lay!r}")
            ins = tuple((p, L.perm(layout_of[p], lay)) for p in prods[i])
            fused = False
            if epilogues and node.kind == "add" and len(ins) == 2:
                for (p, _), (q, qpm) in ((ins[0], ins[1]), (ins[1], ins[0])):
                    st = fusable(p, lay)
                    ep = st.epilogue if st is not None else None
                    # conv output must be the join's (smallest) spatial size —
                    # the other operand centre-crops onto it; one residual
                    # per step, and never after a folded ReLU
                    if (st is not None
                            and (ep is None or (ep.residual is None
                                                and not ep.relu))
                            and _out_spatial(spec.nodes[p]) == node.im):
                        refuse(p, st, EpilogueSpec(
                            alias=i, bias=ep.bias if ep else None,
                            residual=(q, qpm), relu=False))
                        fused = True
                        break
            if not fused:
                steps.append(JoinStep(i, node.kind, lay, ins))
                prod_step[i] = len(steps) - 1
                layout_of[i] = lay
    return [st for st in steps if st is not None], layout_of


def heuristic_assignment(spec: CNNSpec) -> Dict[int, str]:
    """Deterministic runnable assignment (no profiling): GEMM-lowered convs,
    pointwise GEMM for 1x1, chw joins — the shape of a typical selection.
    Shared by the executor benchmark and the plan tests."""
    asg: Dict[int, str] = {}
    for i, node in enumerate(spec.nodes):
        if isinstance(node, ConvLayer):
            asg[i] = "conv-1x1-gemm-ab-ki" if node.f == 1 else "im2col-copy-ab-ki"
        else:
            asg[i] = "chw"
    return asg


def fused_dlt_count(steps: Sequence[PlanStep]) -> Tuple[int, int]:
    """(eliminated identity DLTs, inlined transposes) across the plan edges."""
    fused = inlined = 0
    for st in steps:
        if isinstance(st, JoinStep):
            perms = [pm for _, pm in st.ins]
        else:
            perms = [st.perm]
            if isinstance(st, ConvStep) and st.epilogue is not None \
                    and st.epilogue.residual is not None:
                perms.append(st.epilogue.residual[1])
        for pm in perms:
            if L.is_identity(pm):
                fused += 1
            else:
                inlined += 1
    return fused, inlined


def epilogue_signature(steps: Sequence[PlanStep]) -> Tuple[Tuple[int, int, Tuple[str, ...]], ...]:
    """(conv node, alias node, fused ops) per epilogue-fused step — the
    plan's fusion fingerprint (part of benchmark rows and plan identity)."""
    return tuple((st.node, st.epilogue.alias, st.epilogue.ops)
                 for st in steps
                 if isinstance(st, ConvStep) and st.epilogue is not None)


# ---------------------------------------------------------------------------
# Plan compilation + cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompiledPlan:
    """One jitted function for the whole assigned network.

    ``__call__(x, weights)`` takes a batched chw input (n, c, im, im) — or a
    ``{source node: array}`` dict for multi-input specs — and returns
    ``{node: batched output in its native layout}`` for the requested output
    set. Steady-state serving is a single dispatch per request batch.
    """
    spec: CNNSpec
    assignment: Dict[int, str]
    steps: List[PlanStep]
    layouts: Dict[int, str]               # node -> produced layout
    sources: List[int]
    sinks: List[int]
    outputs: str                          # "sinks" | "all"
    fn: Callable                          # jitted (xs dict, weights) -> outputs
    epilogues: bool = False               # epilogue fusion pass applied
    epilogue_signature: Tuple = ()        # (conv, alias, ops) per fused step
    # batch size -> {"per_image": n, "folded": m}: how the plan's mm-* 1x1
    # and im2col steps ran their GEMM (variants.gemm_path), recorded when
    # ``fn`` is traced for that batch size
    gemm_paths: Dict[int, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    # batch size -> {"steps": n, "bytes": b}: the padded conv steps and the
    # bytes of their padded inputs, recorded as ``gemm_paths`` is
    pads: Dict[int, Dict[str, int]] = dataclasses.field(default_factory=dict)

    def __call__(self, x, weights: Dict[int, jnp.ndarray]) -> Dict[int, jnp.ndarray]:
        xs = self._as_inputs(x)
        return self.fn(xs, weights)

    def _as_inputs(self, x) -> Dict[int, jnp.ndarray]:
        if isinstance(x, dict):
            return {int(k): jnp.asarray(v) for k, v in x.items()}
        if len(self.sources) != 1:
            raise ValueError(f"spec has {len(self.sources)} inputs; pass a dict")
        return {self.sources[0]: jnp.asarray(x)}


def _crop_center(r: jnp.ndarray, oh: int, ow: int) -> jnp.ndarray:
    """Centre-crop trailing spatial axes to (oh, ow) — the chw analogue of
    ``crop_to_common`` for a single residual operand."""
    h, w = r.shape[-2:]
    dh, dw = (h - oh) // 2, (w - ow) // 2
    return r[..., dh:dh + oh, dw:dw + ow]


def _conv(st: ConvStep, xs: Dict[int, jnp.ndarray],
          tensors: Dict[int, jnp.ndarray],
          weights: Dict[int, jnp.ndarray]) -> jnp.ndarray:
    """One conv step: its input's and residual's edge permutations and the
    residual's crop (scope ``dlt``), then the primitive with its epilogue.
    A padded step runs the valid convolution of its zero-padded input: a
    tile variant pads as its kernel family does (``conv_variant_call``), a
    base impl after ``pad_input`` (scope ``pack/pad``)."""
    v = xs[st.node] if st.src is None else tensors[st.src]
    w = weights[st.node]
    ep = st.epilogue
    bias = res = None
    relu = False
    with jax.named_scope("dlt"):
        v = L.apply_perm(v, st.perm)          # fused DLT (no-op if identity)
        if ep is not None and ep.residual is not None:
            q, pm = ep.residual
            f = w.shape[-1]
            oh = (v.shape[-2] + 2 * st.pad - f) // st.stride + 1
            ow = (v.shape[-1] + 2 * st.pad - f) // st.stride + 1
            res = _crop_center(L.apply_perm(tensors[q], pm), oh, ow)
    if ep is not None:
        bias = weights[ep.bias] if ep.bias is not None else None
        relu = ep.relu
    if st.variant is not None:
        return conv_variant_call(st.prim, st.variant, v, w, st.stride,
                                 bias=bias, residual=res, relu=relu,
                                 pad=st.pad)
    with jax.named_scope("pack"):
        v = pad_input(v, st.pad, st.prim.in_layout)
    y = batch_impl(st.prim)(v, w, st.stride)
    if bias is not None:                      # chw-out (fusion criterion)
        y = y + bias[:, None, None]
    if res is not None:
        y = y + res
    if relu:
        y = jnp.maximum(y, 0.0)
    return y


def _padded_bytes(st: ConvStep, v: jnp.ndarray) -> int:
    """Bytes of conv step ``st``'s input ``v`` (its producer's layout) once
    permuted to the primitive's layout and zero-padded by ``st.pad``."""
    lead = v.ndim - 3
    dims = [v.shape[lead + a] for a in st.perm]
    for a in L.SPATIAL_AXES[st.prim.in_layout]:
        dims[a] += 2 * st.pad
    return math.prod(v.shape[:lead]) * math.prod(dims) * v.dtype.itemsize


def _eltwise(st: EltwiseStep, tensors: Dict[int, jnp.ndarray],
             weights: Dict[int, jnp.ndarray]) -> jnp.ndarray:
    with jax.named_scope("dlt"):
        v = L.apply_perm(tensors[st.src], st.perm)
    if st.kind == "relu":
        return jnp.maximum(v, 0.0)
    if st.kind == "bias":
        b = weights[st.node]
        shape = [1, 1, 1]
        shape[L.C_AXIS[st.layout]] = b.shape[0]
        return v + b.reshape(shape)
    raise ValueError(st.kind)


def _join(st: JoinStep, tensors: Dict[int, jnp.ndarray]) -> jnp.ndarray:
    with jax.named_scope("dlt"):
        vals = [L.apply_perm(tensors[p], pm) for p, pm in st.ins]
        vals = crop_to_common(vals, st.layout)
    if st.kind == "concat":
        return jnp.concatenate(vals, axis=-3 + L.C_AXIS[st.layout])
    if st.kind == "add":
        y = vals[0]
        for v in vals[1:]:
            y = y + v
        return y
    raise ValueError(st.kind)


def _emit(steps: List[PlanStep], want: List[int],
          gemm_paths: Dict[int, Dict[str, int]],
          pads: Dict[int, Dict[str, int]]) -> Callable:
    """Build the traced function replaying ``steps`` over a leading batch.

    The plan computes in float32 throughout: every matmul, in XLA and in
    the Pallas kernels alike, is traced at float32 precision. At its
    default a TPU rounds f32 matmul operands to bf16: on a v5e that put
    resnet50's served output 5e-2 (relative to its scale) off the float32
    reference.

    Each step runs under a ``jax.named_scope`` named for its kind and node
    (``conv<node>``, ``join<node>``, ``eltwise<node>``), and the glue inside
    a step under the scope of its role: ``dlt`` (the plan's edge
    permutations and crops), ``pack`` (activation packing around a kernel),
    ``wpack`` (weight preparation that runs on every dispatch). The scopes
    reach each compiled instruction's ``op_name``, so a device trace charges
    every op to its step and role (DESIGN.md §6).

    A padded conv step pads its input once, under ``pack/pad``
    (``variants.pad_input``, or folded into a Winograd or fused im2col
    kernel's own pad); an unpadded one emits no pad.

    Each trace records in ``gemm_paths``, under its batch size, how many
    ``mm-*`` 1x1 and im2col steps ran their GEMM per image and how many
    folded the batch into it (``variants.gemm_path``), and in ``pads`` the
    padded steps and the bytes of their padded inputs."""
    def fn(xs: Dict[int, jnp.ndarray], weights: Dict[int, jnp.ndarray]):
        with jax.default_matmul_precision("float32"):
            return replay(xs, weights)

    def replay(xs: Dict[int, jnp.ndarray], weights: Dict[int, jnp.ndarray]):
        tensors: Dict[int, jnp.ndarray] = {}
        paths = {"per_image": 0, "folded": 0}
        padded = {"steps": 0, "bytes": 0}
        for st in steps:
            if isinstance(st, ConvStep):
                if st.pad:
                    padded["steps"] += 1
                    padded["bytes"] += _padded_bytes(
                        st, xs[st.node] if st.src is None else tensors[st.src])
                with jax.named_scope(f"conv{st.node}"):
                    y = tensors[st.out_node] = _conv(st, xs, tensors, weights)
                path = gemm_path(st.prim, st.variant, y.shape[0],
                                 y.shape[-2] * y.shape[-1])
                if path is not None:
                    paths[path] += 1
            elif isinstance(st, EltwiseStep):
                with jax.named_scope(f"eltwise{st.node}"):
                    tensors[st.node] = _eltwise(st, tensors, weights)
            else:
                with jax.named_scope(f"join{st.node}"):
                    tensors[st.node] = _join(st, tensors)
        n = next(iter(xs.values())).shape[0]
        gemm_paths[n], pads[n] = paths, padded
        return {i: tensors[i] for i in want}
    return fn


def _spec_key(spec: CNNSpec) -> Tuple:
    return (spec.name, tuple(spec.nodes), tuple(spec.edges))


_PLAN_CACHE: "OrderedDict[Tuple, CompiledPlan]" = OrderedDict()
_PLAN_CACHE_CAP = 64


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()


def evict_plans(spec: CNNSpec, assignment: Dict[int, str]) -> int:
    """Drop every cached plan for (``spec``, ``assignment``) — all batch
    shapes, output modes and epilogue settings. Called when a served
    generation retires (hot_swap / re-register): stale compiled plans for
    dead generations must not pin jitted executables in memory. Returns the
    number of evicted entries."""
    skey = _spec_key(spec)
    akey = tuple(sorted(assignment.items()))
    dead = [k for k in _PLAN_CACHE if k[0] == skey and k[1] == akey]
    for k in dead:
        del _PLAN_CACHE[k]
    return len(dead)


def compile_plan(spec: CNNSpec, assignment: Dict[int, str],
                 batch_shape: Optional[Tuple[int, ...]] = None, *,
                 outputs: str = "sinks",
                 epilogues: Optional[bool] = None) -> CompiledPlan:
    """Compile (and cache) the whole-graph batched plan for ``assignment``.

    ``batch_shape`` is the (n, c, im, im) input shape the caller will feed —
    part of the cache key so steady-state serving of a known shape is a dict
    lookup followed by one jitted dispatch (``None`` = shape-generic entry;
    jax.jit re-specialises per concrete shape either way). ``outputs`` picks
    the returned node set: "sinks" (serving) or "all" (the interpreted
    executor's report surface).

    ``epilogues`` controls the elementwise-fusion pass (DESIGN.md §13.2):
    default on for "sinks" plans, forced off for "all" (fused interior nodes
    would not be reportable — "all" is the unfused oracle surface). The
    flag is part of the cache key; since the fused-epilogue set is a pure
    function of (spec, assignment, flag), the key also pins the plan's
    ``epilogue_signature``. Tile variants are keyed through the assignment's
    full column names.
    """
    if outputs not in ("sinks", "all"):
        raise ValueError(outputs)
    eff_ep = (outputs == "sinks") if epilogues is None \
        else (epilogues and outputs == "sinks")
    key = (_spec_key(spec), tuple(sorted(assignment.items())),
           batch_shape, outputs, eff_ep)
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        _PLAN_CACHE.move_to_end(key)
        return plan
    steps, layout_of = lower(spec, assignment, epilogues=eff_ep)
    sinks = sink_nodes(spec)
    want = sinks if outputs == "sinks" else list(range(len(spec.nodes)))
    gemm_paths: Dict[int, Dict[str, int]] = {}
    pads: Dict[int, Dict[str, int]] = {}
    plan = CompiledPlan(spec, dict(assignment), steps, layout_of,
                        source_nodes(spec), sinks, outputs,
                        jax.jit(_emit(steps, want, gemm_paths, pads)),
                        epilogues=eff_ep,
                        epilogue_signature=epilogue_signature(steps),
                        gemm_paths=gemm_paths, pads=pads)
    _PLAN_CACHE[key] = plan
    while len(_PLAN_CACHE) > _PLAN_CACHE_CAP:
        _PLAN_CACHE.popitem(last=False)
    return plan

"""PR 9 (DESIGN.md §13): variant-aware lowering, epilogue fusion, and the
serving dispatch fast path.

Covers every entry of all four kernel VARIANTS dicts numerically (vs the
base impl / reference), plan-level variant + epilogue-fusion equivalence on
edge_cnn and a winograd-bearing net, EltwiseLayer folding, plan-cache keying
by (variant, epilogue flag), selection-surface filtering
(``is_runnable``/``tile_columns``), and plan-cache / jit-cache eviction on
``hot_swap``."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import VARIANTS as FA_VARIANTS
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.im2col_gemm.ops import VARIANTS as CONV_VARIANTS
from repro.kernels.im2col_gemm.ops import (conv_im2col_batch_op,
                                           conv_im2col_op)
from repro.kernels.matmul.ops import VARIANTS as MM_VARIANTS
from repro.kernels.matmul.ops import matmul_batch_op, matmul_op
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.winograd.ops import VARIANTS as WINO_VARIANTS
from repro.kernels.winograd.ops import (winograd_conv_batch,
                                        winograd_conv_batch_op)
from repro.kernels.winograd.ref import conv3x3_ref
from repro.models import cnn_zoo
from repro.primitives.conv import (REGISTRY, is_runnable, reference_conv_batch,
                                   supports_epilogue, tile_columns,
                                   variant_compatible)
from repro.primitives.executor import (_JIT_CACHE, evict_prim_entries, execute,
                                       make_weights)
from repro.primitives.plan import (_PLAN_CACHE, compile_plan, evict_plans,
                                   heuristic_assignment, lower)
from repro.primitives.variants import conv_variant_call

TOL = dict(rtol=2e-3, atol=2e-3)


def _conv_inputs(rng, n=2, c=6, im=14, k=8, f=3):
    x = jnp.asarray(rng.standard_normal((n, c, im, im)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, c, f, f)) / (f * np.sqrt(c)),
                    jnp.float32)
    return x, w


# ---------------------------------------------------------------------------
# Every VARIANTS entry, numerically, vs base/reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", sorted(MM_VARIANTS))
def test_matmul_variants_single_and_batch(variant, rng):
    x = jnp.asarray(rng.standard_normal((150, 70)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((70, 90)), jnp.float32)
    np.testing.assert_allclose(matmul_op(x, y, variant=variant, interpret=True),
                               matmul_ref(x, y), rtol=1e-4, atol=1e-4)
    xb = jnp.asarray(rng.standard_normal((3, 150, 70)), jnp.float32)
    yb = jnp.broadcast_to(y, (3,) + y.shape)
    got = matmul_batch_op(xb, yb, variant=variant, interpret=True)
    ref = jnp.einsum("bmk,kn->bmn", xb, y)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("variant", sorted(CONV_VARIANTS))
def test_im2col_gemm_variants(variant, rng):
    x, w = _conv_inputs(rng)
    ref = reference_conv_batch(x, w, 1)
    got = conv_im2col_batch_op(x, w, 1, variant=variant, interpret=True)
    np.testing.assert_allclose(got, ref, **TOL)
    got1 = conv_im2col_op(x[0], w, 1, variant=variant, interpret=True)
    np.testing.assert_allclose(got1, ref[0], **TOL)


@pytest.mark.parametrize("variant", sorted(WINO_VARIANTS))
def test_winograd_variants(variant, rng):
    x, w = _conv_inputs(rng)
    ref = reference_conv_batch(x, w, 1)
    got = winograd_conv_batch_op(x, w, variant=variant, interpret=True)
    np.testing.assert_allclose(got, ref, **TOL)
    np.testing.assert_allclose(got[0], conv3x3_ref(x[0], w), **TOL)


@pytest.mark.parametrize("variant", sorted(FA_VARIANTS))
def test_flash_attention_variants(variant, rng):
    q, k, v = (jnp.asarray(rng.standard_normal((2, 128, 4, 32)), jnp.float32)
               for _ in range(3))
    got = flash_attention_op(q, k, v, variant=variant, interpret=True)
    B, S, H, d = q.shape
    ref = attention_ref(q.transpose(0, 2, 1, 3).reshape(B * H, S, d),
                        k.transpose(0, 2, 1, 3).reshape(B * H, S, d),
                        v.transpose(0, 2, 1, 3).reshape(B * H, S, d),
                        causal=True).reshape(B, H, S, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# conv_variant_call: every lowerable (base, variant) family pair
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("base,variant", [
    ("im2col-copy-ab-ki", "mm-256x128x128"),
    ("im2col-scan-ab-ki", "mm-128x256x128"),
    ("im2col-copy-ab-ki", "conv-bk64"),
    ("im2col-scan-ab-ki", "conv-bk128"),
    ("conv-1x1-gemm-ab-ki", "mm-128x128x256"),
    ("conv-1x1-gemm-ab-ki", "conv-bk256"),
    ("winograd-2x2-3x3", "wino-256x128"),
    ("winograd-4x4-3x3", "wino-128x256"),
    ("winograd-2x2-3x3", "mm-128x128x128"),
])
def test_conv_variant_call_matches_reference(base, variant, rng):
    prim = REGISTRY[base]
    f = 1 if prim.family == "c1x1" else 3
    stride = 2 if prim.family == "c1x1" else 1
    x, w = _conv_inputs(rng, f=f)
    ref = reference_conv_batch(x, w, stride)
    got = conv_variant_call(prim, variant, x, w, stride)
    np.testing.assert_allclose(got, ref, **TOL)
    # epilogue path: bias -> residual -> relu on top of the same conv
    bias = jnp.asarray(rng.standard_normal(w.shape[0]), jnp.float32)
    res = jnp.asarray(rng.standard_normal(ref.shape), jnp.float32)
    got_ep = conv_variant_call(prim, variant, x, w, stride,
                               bias=bias, residual=res, relu=True)
    ref_ep = jnp.maximum(ref + bias[:, None, None] + res, 0.0)
    np.testing.assert_allclose(got_ep, ref_ep, **TOL)


def test_conv_variant_call_rejects_incompatible(rng):
    x, w = _conv_inputs(rng)
    with pytest.raises(ValueError):
        conv_variant_call(REGISTRY["winograd-2x2-3x3"], "conv-bk64", x, w, 1)


def test_fuse_store_in_kernel_epilogue(rng):
    """fuse_store=True forces the epilogue into the kernel's store step —
    numerics must match the wrapper-level default exactly both ways."""
    from repro.kernels.im2col_gemm.im2col_gemm import conv_im2col_batch
    from repro.kernels.matmul.matmul import matmul
    x = jnp.asarray(rng.standard_normal((150, 70)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((70, 90)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(150), jnp.float32)
    res = jnp.asarray(rng.standard_normal((150, 90)), jnp.float32)
    ref = jnp.maximum(x @ y + bias[:, None] + res, 0.0)
    for fuse in (True, False):
        got = matmul(x, y, bm=64, bk=64, bn=64, bias=bias, residual=res,
                     relu=True, interpret=True, fuse_store=fuse)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    xc, wc = _conv_inputs(rng)
    cref = reference_conv_batch(xc, wc, 1)
    cbias = jnp.asarray(rng.standard_normal(wc.shape[0]), jnp.float32)
    cres = jnp.asarray(rng.standard_normal(cref.shape), jnp.float32)
    want = jnp.maximum(cref + cbias[:, None, None] + cres, 0.0)
    for fuse in (True, False):
        got = conv_im2col_batch(xc, wc, 1, bk=64, bias=cbias, residual=cres,
                                relu=True, interpret=True, fuse_store=fuse)
        np.testing.assert_allclose(got, want, **TOL)


# ---------------------------------------------------------------------------
# Selection surface: is_runnable / tile_columns / traits
# ---------------------------------------------------------------------------

def test_variant_compatibility_filters():
    assert variant_compatible("im2col-copy-ab-ki", "mm-128x128x128")
    assert variant_compatible("im2col-copy-ab-ki", "conv-bk64")
    assert not variant_compatible("im2col-copy-ab-ki", "wino-128x128")
    assert variant_compatible("winograd-2x2-3x3", "wino-256x128")
    assert variant_compatible("winograd-4x4-3x3", "mm-256x128x128")
    assert not variant_compatible("winograd-2x2-3x3", "conv-bk64")
    assert not variant_compatible("conv-1x1-gemm-ab-ki", "wino-128x128")
    assert not variant_compatible("im2col-copy-ab-ki", "bogus-tile")


def test_is_runnable_consults_variant():
    assert is_runnable("im2col-copy-ab-ki@conv-bk64")
    assert not is_runnable("im2col-copy-ab-ki@wino-128x128")
    assert not is_runnable("winograd-2x2-3x3@conv-bk128")


def test_tile_columns_cross_product_filtered():
    cols = tile_columns(("im2col-copy-ab-ki", "winograd-2x2-3x3"),
                        list(CONV_VARIANTS) + list(WINO_VARIANTS))
    assert cols == ["im2col-copy-ab-ki@conv-bk64",
                    "im2col-copy-ab-ki@conv-bk128",
                    "im2col-copy-ab-ki@conv-bk256",
                    "winograd-2x2-3x3@wino-128x128",
                    "winograd-2x2-3x3@wino-256x128",
                    "winograd-2x2-3x3@wino-128x256"]
    # the default (matmul-variant) pool is the full cross product: every
    # mm-* block config lowers through every GEMM-shaped base
    from repro.core.autotune import PALLAS_CONV_BASES, pallas_columns
    assert len(pallas_columns()) == len(PALLAS_CONV_BASES) * len(MM_VARIANTS)


def test_epilogue_traits():
    assert supports_epilogue("im2col-copy-ab-ki")
    assert supports_epilogue("winograd-2x2-3x3@wino-128x128")
    assert not supports_epilogue("direct-sum2d")


# ---------------------------------------------------------------------------
# Plan-level: variants + epilogue fusion on edge_cnn and a winograd net
# ---------------------------------------------------------------------------

def _wino_spec():
    """A small residual net whose convs are all 3x3 stride-1 — every one
    can carry a winograd assignment, and the add join can fuse."""
    b = cnn_zoo._Builder("wino_res")
    c0 = b.conv(8, 4, 16, 1, 3)               # out 14
    c1 = b.conv(8, 8, 14, 1, 3)               # out 12
    c2 = b.conv(8, 8, 12, 1, 3)               # out 10 == the join size
    b.join("add", 8, 10, [c1, c2])
    return b.build()


def test_variant_plan_matches_base_edge_cnn(rng):
    spec = cnn_zoo.get("edge_cnn")
    asg = heuristic_assignment(spec)
    asg_v = {i: (v + "@mm-256x128x128"
                 if v.startswith(("im2col", "conv-1x1")) else v)
             for i, v in asg.items()}
    w = make_weights(spec)
    x = jnp.asarray(rng.standard_normal((2, 3, 32, 32)), jnp.float32)
    base = compile_plan(spec, asg)(x, w)
    tiled = compile_plan(spec, asg_v)(x, w)
    for k in base:
        np.testing.assert_allclose(np.asarray(base[k]), np.asarray(tiled[k]),
                                   **TOL)


def test_fused_vs_unfused_edge_cnn(rng):
    spec = cnn_zoo.get("edge_cnn")
    asg = heuristic_assignment(spec)
    w = make_weights(spec)
    x = jnp.asarray(rng.standard_normal((2, 3, 32, 32)), jnp.float32)
    fused = compile_plan(spec, asg, epilogues=True)
    unfused = compile_plan(spec, asg, epilogues=False)
    assert fused.epilogue_signature, "edge_cnn's add joins should fuse"
    assert all(ops == ("residual",)
               for _, _, ops in fused.epilogue_signature)
    assert unfused.epilogue_signature == ()
    of, ou = fused(x, w), unfused(x, w)
    for k in of:
        np.testing.assert_allclose(np.asarray(of[k]), np.asarray(ou[k]),
                                   **TOL)


def test_fused_vs_unfused_winograd_net(rng):
    spec = _wino_spec()
    asg = {i: ("winograd-2x2-3x3@wino-128x128"
               if isinstance(n, cnn_zoo.ConvLayer) else "chw")
           for i, n in enumerate(spec.nodes)}
    w = make_weights(spec)
    x = jnp.asarray(rng.standard_normal((2, 4, 16, 16)), jnp.float32)
    fused = compile_plan(spec, asg, epilogues=True)
    unfused = compile_plan(spec, asg, epilogues=False)
    assert fused.epilogue_signature == ((2, 3, ("residual",)),)
    of, ou = fused(x, w), unfused(x, w)
    for k in of:
        np.testing.assert_allclose(np.asarray(of[k]), np.asarray(ou[k]),
                                   **TOL)
    # and against the interpreted oracle
    rep = execute(spec, asg, w, x=np.asarray(x[0]), compiled=False)
    np.testing.assert_allclose(np.asarray(of[3][0]),
                               np.asarray(rep.outputs[3]), **TOL)


def test_eltwise_bias_relu_fold_into_conv(rng):
    b = cnn_zoo._Builder("tiny_ep")
    b.conv(8, 4, 12, 1, 3)
    b.eltwise("bias", 8, 10)
    b.eltwise("relu", 8, 10)
    spec = b.build()
    asg = {0: "im2col-copy-ab-ki@conv-bk64", 1: "chw", 2: "chw"}
    w = make_weights(spec)
    x = jnp.asarray(rng.standard_normal((3, 4, 12, 12)), jnp.float32)
    plan = compile_plan(spec, asg, epilogues=True)
    assert plan.epilogue_signature == ((0, 2, ("bias", "relu")),)
    assert len(plan.steps) == 1            # conv + bias + relu -> one step
    out = plan(x, w)
    rep = execute(spec, asg, w, x=np.asarray(x[0]), compiled=False)
    np.testing.assert_allclose(np.asarray(out[2][0]),
                               np.asarray(rep.outputs[2]), **TOL)
    assert np.asarray(out[2]).min() >= 0.0    # the ReLU really applied


def test_eltwise_unfused_when_base_lacks_epilogue(rng):
    b = cnn_zoo._Builder("tiny_nf")
    b.conv(8, 4, 12, 1, 3)
    b.eltwise("relu", 8, 10)
    spec = b.build()
    asg = {0: "direct-sum2d", 1: "chw"}        # no epilogue trait
    steps, _ = lower(spec, asg, epilogues=True)
    assert len(steps) == 2                      # EltwiseStep stays separate
    w = make_weights(spec)
    x = jnp.asarray(rng.standard_normal((2, 4, 12, 12)), jnp.float32)
    out = compile_plan(spec, asg, epilogues=True)(x, w)
    rep = execute(spec, asg, w, x=np.asarray(x[0]), compiled=False)
    np.testing.assert_allclose(np.asarray(out[1][0]),
                               np.asarray(rep.outputs[1]), **TOL)


def test_lower_rejects_incompatible_tile():
    spec = cnn_zoo.get("edge_cnn")
    asg = heuristic_assignment(spec)
    conv = next(i for i, n in enumerate(spec.nodes)
                if isinstance(n, cnn_zoo.ConvLayer)
                and asg[i].startswith("im2col"))
    asg[conv] = asg[conv] + "@wino-128x128"
    with pytest.raises(ValueError):
        lower(spec, asg)


# ---------------------------------------------------------------------------
# Cache keys + eviction
# ---------------------------------------------------------------------------

def test_plan_cache_keys_variant_and_epilogues(rng):
    spec = cnn_zoo.get("edge_cnn")
    asg = heuristic_assignment(spec)
    asg_v = dict(asg)
    conv = next(i for i, v in asg.items() if v.startswith("im2col"))
    asg_v[conv] = asg_v[conv] + "@mm-256x128x128"
    p1 = compile_plan(spec, asg, epilogues=True)
    p2 = compile_plan(spec, asg, epilogues=False)
    p3 = compile_plan(spec, asg_v, epilogues=True)
    assert p1 is not p2 and p1 is not p3
    assert p1 is compile_plan(spec, asg, epilogues=True)        # cache hit
    assert p3 is compile_plan(spec, asg_v, epilogues=True)
    st = next(s for s in p3.steps
              if getattr(s, "node", None) == conv)
    assert st.variant == "mm-256x128x128"
    # "all" plans never fuse: they are the interpreted oracle surface
    pa = compile_plan(spec, asg, outputs="all", epilogues=True)
    assert pa.epilogue_signature == ()


def test_evict_plans_drops_all_entries_for_assignment():
    spec = cnn_zoo.get("edge_cnn")
    asg = heuristic_assignment(spec)
    compile_plan(spec, asg, (1, 3, 32, 32))
    compile_plan(spec, asg, (2, 3, 32, 32), epilogues=False)
    other = dict(asg)
    other[0] = "direct-sum2d"
    compile_plan(spec, other, (1, 3, 32, 32))
    assert evict_plans(spec, asg) >= 2
    akey = tuple(sorted(asg.items()))
    assert not any(k[1] == akey for k in _PLAN_CACHE)
    assert evict_plans(spec, asg) == 0          # idempotent
    assert evict_plans(spec, other) >= 1        # the other entry survived


def test_jit_cache_eviction_by_column(rng):
    from repro.primitives import layouts as L
    spec = cnn_zoo.get("edge_cnn")
    asg = heuristic_assignment(spec)
    execute(spec, asg, make_weights(spec), compiled=False)
    cols = {v for v in asg.values() if v not in L.LAYOUTS}
    assert any(k[0] == "prim" and k[1] in cols for k in _JIT_CACHE)
    assert evict_prim_entries(cols) > 0
    assert not any(k[0] == "prim" and k[1] in cols for k in _JIT_CACHE)
    assert evict_prim_entries(cols) == 0


def test_hot_swap_evicts_retired_generation(rng):
    from repro.service.pipeline import OptimisedNetwork
    from repro.service.server import OptimisedServer
    spec = cnn_zoo.get("edge_cnn")
    asg = heuristic_assignment(spec)
    asg2 = dict(asg)
    asg2[0] = "direct-sum2d"
    akey = tuple(sorted(asg.items()))
    server = OptimisedServer(max_batch=2, latency_budget_ms=float("inf"))
    server.register(OptimisedNetwork.from_assignment(spec, asg))
    x = np.random.default_rng(0).standard_normal((2, 3, 32, 32)).astype(np.float32)
    server.serve("edge_cnn", x)
    assert any(k[1] == akey for k in _PLAN_CACHE)
    assert len(server._plan_handles) == 1
    assert server.hot_swap("edge_cnn",
                           OptimisedNetwork.from_assignment(spec, asg2))
    # the retired generation's plans are gone, the new one's are live
    assert not any(k[1] == akey for k in _PLAN_CACHE)
    akey2 = tuple(sorted(asg2.items()))
    assert any(k[1] == akey2 for k in _PLAN_CACHE)
    assert len(server._plan_handles) == 1
    server.serve("edge_cnn", x)                 # still serves correctly
    server.stop()


def test_precompile_failure_is_recorded(rng):
    """A bucket whose handle fails to compile stops the precompile pass: the
    exception shows in stats(), the buckets compiled before it stay live,
    and a dispatch at the failed bucket still serves (global plan cache)."""
    from repro.service.pipeline import OptimisedNetwork
    from repro.service.server import OptimisedServer

    class RefusesBucket2(OptimisedServer):
        @staticmethod
        def _bind_plan(opt, weights, shape):
            if shape[0] == 2:
                raise RuntimeError("compile refused")
            return OptimisedServer._bind_plan(opt, weights, shape)

    spec = cnn_zoo.get("edge_cnn")
    server = RefusesBucket2(max_batch=4, latency_budget_ms=float("inf"))
    server.register(OptimisedNetwork.from_assignment(
        spec, heuristic_assignment(spec)))
    st = server.stats("edge_cnn")
    assert st["precompile_error"] == "RuntimeError: compile refused"
    assert sorted(st["precompiled"]) == [1]
    assert sorted(server.plan_handles("edge_cnn")) == [1]
    x = rng.standard_normal((2, 3, 32, 32)).astype(np.float32)
    outs = server.serve("edge_cnn", x)
    assert len(outs) == 2 and server.stats("edge_cnn")["failed_tickets"] == 0
    server.stop()

"""The readers of the program's spans and scopes (``bench/spans.py``) and
the five metrics built on them."""
import json
from pathlib import Path

import pytest

from bench import registry, spans
from bench import serve as SV
from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"
READERS = ("window_ms_per_dispatch", "host_ms_per_dispatch", "dlt_share",
           "pack_share", "wpack_share")

# an optimised program as the TPU compiler prints it (attributes elided):
# a step's ops under their roles, a weight argument copied into the kernel
# with no step of its own, a copy of an op's result with no op_name, a
# bitcast of the kernel's result, and an op of no step at all
HLO = """
ENTRY %main (a.1: f32[2,8,4,4], w.1: f32[16,8,1,1]) -> f32[2,16,4,4] {
  %a.1 = f32[2,8,4,4]{3,2,1,0} parameter(0), metadata={op_name="a"}
  %w.1 = f32[16,8,1,1]{3,2,1,0} parameter(1), metadata={op_name="w[3]"}
  %copy.1 = f32[16,8]{1,0:T(8,128)} copy(%w.1), metadata={op_name="w[3]"}
  %transpose.2 = f32[8,2,16]{2,1,0} transpose(%a.1), metadata={op_name="jit(_lambda)/jit(fn)/conv3/pack/transpose"}
  %copy.3 = f32[8,32]{0,1} copy(%transpose.2)
  %matmul_op.4 = f32[16,32]{1,0} custom-call(%copy.1, %copy.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(_lambda)/jit(fn)/conv3/jit(matmul_op)/pallas_call"}
  %bitcast.5 = f32[16,2,16]{2,1,0} bitcast(%matmul_op.4)
  %slice.6 = f32[16,2,16]{2,1,0} slice(%bitcast.5), metadata={op_name="jit(_lambda)/jit(fn)/conv3/jit(matmul_op)/rhs/slice"}
  %pad.7 = f32[16,2,16]{2,1,0} pad(%slice.6), metadata={op_name="jit(_lambda)/jit(fn)/conv3/dlt/pad"}
  %fusion.8 = f32[16,2,16]{2,1,0} fusion(%pad.7), kind=kLoop, metadata={op_name="jit(_lambda)/jit(fn)/conv3/wpack/pack/add"}
  ROOT %tuple.9 = (f32[16,2,16]) tuple(%fusion.8), metadata={op_name="jit(_lambda)/out"}
}
"""


def test_scope_map_reads_steps_roles_and_fallbacks():
    sm = spans.scope_map([HLO])
    role = {name: entries[0][1:] for name, entries in sm.items()}
    assert role["transpose.2"] == ("conv3", "pack")
    assert role["matmul_op.4"] == ("conv3", "kernel")
    assert role["copy.3"] == ("conv3", "pack")        # its operand's
    assert role["copy.1"] == ("conv3", "wpack")       # an argument into the kernel
    assert role["bitcast.5"] == ("conv3", "pack")     # reads the kernel's result
    assert role["slice.6"] == ("conv3", "other")      # a role the plan never names
    assert role["pad.7"] == ("conv3", "dlt")
    assert role["fusion.8"] == ("conv3", "pack")      # the innermost role
    assert role["tuple.9"] == ("conv3", "pack")       # no step: its operand's
    assert sm["matmul_op.4"][0][0] == (("f32", (16, 32)),)


LOOP = """
%body.1 (p.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %gte.2 = f32[8]{0} get-tuple-element(%p.1), index=1
  %bitcast_dynamic-update-slice_fusion.3 = f32[8]{0} fusion(%gte.2), kind=kLoop, calls=%fused.9
  ROOT %tuple.4 = (s32[], f32[8]{0}) tuple(%gte.2, %bitcast_dynamic-update-slice_fusion.3)
}

ENTRY %main.5 (a.6: f32[8]) -> f32[8] {
  %a.6 = f32[8]{0} parameter(0), metadata={op_name="a"}
  %tuple.7 = (s32[], f32[8]{0}) tuple(%a.6)
  %while.8 = (s32[], f32[8]{0}) while(%tuple.7), condition=%cond.1, body=%body.1, metadata={op_name="jit(fn)/conv0/pack/while"}
  ROOT %gte.9 = f32[8]{0} get-tuple-element(%while.8), index=1
}
"""


def test_an_op_of_a_loop_body_takes_the_loops_step():
    """The body of a loop (im2col's gather patches) prints with no
    ``op_name``: its ops take the step and role of the ``while`` op."""
    role = {k: v[0][1:] for k, v in spans.scope_map([LOOP]).items()}
    assert role["bitcast_dynamic-update-slice_fusion.3"] == ("conv0", "pack")
    assert role["p.1"] == role["tuple.7"] == ("conv0", "pack")


def _device_trace(events):
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [list(e) + [{}] for e in events]}]}]}


def test_step_roles_tell_programs_apart_by_result_shapes():
    """Two buckets' programs name an op alike under other steps: each event
    goes to the program whose result shape its own text gives."""
    other = (HLO.replace("conv3", "conv5")
             .replace("%copy.3 = f32[8,32]", "%copy.3 = f32[8,64]")
             .replace("%matmul_op.4 = f32[16,32]", "%matmul_op.4 = f32[16,64]"))
    sm = spans.scope_map([HLO, other])
    tr = _device_trace([
        ("%copy.3 = f32[8,32]{0,1} copy(f32[8,2,16]{2,1,0} %t)", 0, 100),
        ("%copy.3 = f32[8,64]{0,1} copy(f32[8,4,16]{2,1,0} %t)", 100, 40),
        ("%matmul_op.4 = f32[16,32]{1,0} custom-call(...)", 140, 300),
        ("%fusion.99 = f32[4]{0} fusion(%x)", 440, 60)])
    red = spans.step_roles(tr, sm)
    assert set(red["steps"]) == {"conv3", "conv5"}
    assert red["steps"]["conv3"] == pytest.approx({"pack": 100e-9,
                                                   "kernel": 300e-9})
    assert red["steps"]["conv5"] == pytest.approx({"pack": 40e-9})
    assert red["roles"] == pytest.approx({"pack": 140e-9, "kernel": 300e-9})
    assert red["unscoped"] == pytest.approx({"fusion": 60e-9})


def test_host_spans_nest_by_thread_and_time():
    tr = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["serve.execute", 0, 9, {}]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "worker", "events": [
                ["serve.window", 0, 5_000_000, {}],
                ["serve.execute", 5_000_000, 4_000_000, {}],
                ["serve.assemble", 5_000_000, 100_000, {}],
                ["serve.device", 5_500_000, 1_000_000, {}],
                ["serve.device", 7_000_000, 1_500_000, {}],   # the retry
                ["$server.py:1 execute", 5_000_000, 4_000_000, {}],
                ["serve.idle", 9_000_000, 2_000_000, {}]]},
            {"name": "client", "events": [
                ["serve.submit", 5_100_000, 50_000, {}],
                ["serve.window", 20_000_000, 1_000_000, {}]]}]}]}
    hs = spans.host_spans(tr)
    assert hs["window_ms"] == pytest.approx(6.0)
    assert hs["dispatches"] == [{"ms": pytest.approx(4.0), "phases": {
        "serve.assemble": pytest.approx(0.1),
        "serve.device": pytest.approx(2.5)}}]


def _read_all(tr, programs, busy_s):
    """Read the five metrics as ``bench/run.py``'s ``run_cell`` reads them:
    from a run that carries the loaded trace and the texts of the buckets'
    programs that ran."""
    run = SV.Run(t0=0.0, t1=1.0, requests=[], trace={"busy_s": busy_s},
                 window_traced_s=1.0, trace_events=tr,
                 programs=list(programs))
    return {m: registry.load_module("metrics", m).read(run) for m in READERS}


def test_readers_read_nothing_from_a_program_without_spans_or_scopes():
    """The parent program: no ``serve.*`` span, no step in any ``op_name``.
    Every reader returns None, and none raises."""
    rec = json.loads((DATA / "resnet50_offline_trace.json").read_text())
    plain = "\n".join(
        f'  %{k} = f32[8]{{0}} custom-call(%p), custom_call_target='
        f'"tpu_custom_call", metadata={{op_name="jit(f)/jit(matmul_op)/'
        f'pallas_call"}}' for k in rec["calls"])
    assert _read_all(rec["trace"], [plain], 1.0) == dict.fromkeys(READERS)
    run = SV.Run(t0=0.0, t1=1.0, requests=[])            # untraced
    assert all(registry.load_module("metrics", m).read(run) is None
               for m in READERS)
    run.trace = {"busy_s": 1.0}           # no loaded trace or programs
    assert all(registry.load_module("metrics", m).read(run) is None
               for m in READERS)


SLICE = json.loads((DATA / "resnet50_spans_slice.json").read_text())


def _busy(cell):
    """The existing reduction of a recorded cell (kernel calls as
    ``trace.program_calls`` gives them, shapes as tuples)."""
    calls = {k: [{"family": c["family"],
                  "operands": [(d, tuple(s)) for d, s in c["operands"]],
                  "results": [(d, tuple(s)) for d, s in c["results"]]}
                 for c in v] for k, v in cell["calls"].items()}
    return T.reduce(cell["trace"], calls,
                    lambda f: registry.load_module("work", f).work,
                    197e12, 819e9)


def test_recorded_single_slice_reads_by_hand():
    """Four dispatches of single: windows of 5.422450, 5.523570, 5.595250
    and 5.092459 ms; ``serve.execute`` of 6.032949, 5.905880, 5.551720,
    6.210560 ms holding ``serve.device`` of 4.837110, 4.438119, 4.354180,
    4.551960 ms."""
    cell = SLICE["single"]
    red = _busy(cell)
    got = _read_all(cell["trace"], cell["programs"], red["busy_s"])
    assert got["window_ms_per_dispatch"] == pytest.approx(
        (5.422450 + 5.523570 + 5.595250 + 5.092459) / 4)
    assert got["host_ms_per_dispatch"] == pytest.approx(
        ((6.032949 - 4.837110) + (5.905880 - 4.438119)
         + (5.551720 - 4.354180) + (6.210560 - 4.551960)) / 4)
    roles = spans.step_roles(cell["trace"], spans.scope_map(cell["programs"]))
    assert not roles["unscoped"]
    assert roles["roles"]["kernel"] == pytest.approx(red["kernel_s"])
    for role in ("dlt", "pack", "wpack"):
        assert got[f"{role}_share"] == pytest.approx(
            100 * roles["roles"][role] / red["busy_s"])
    assert set(roles["steps"]) == {"conv2", "conv3", "conv4", "conv6", "conv7"}


def test_recorded_offline_slice_reads_by_hand():
    """250 device ops of offline's stage 4: 7.280 µs of them under ``dlt``,
    4804.284 µs under ``pack``, 22.614 µs under ``wpack``, 1505.123 µs in
    eight kernel calls, 62.060 µs under no role, within 6401.361 µs busy;
    none unscoped."""
    cell = SLICE["offline"]
    red = _busy(cell)
    assert red["busy_s"] == pytest.approx(6401.361e-6)
    assert (red["kernel_s"], red["kernel_calls"]) == (
        pytest.approx(1505.123e-6), 8)
    got = _read_all(cell["trace"], cell["programs"], red["busy_s"])
    assert got["dlt_share"] == pytest.approx(100 * 7.280 / 6401.361)
    assert got["pack_share"] == pytest.approx(100 * 4804.284 / 6401.361)
    assert got["wpack_share"] == pytest.approx(100 * 22.614 / 6401.361)
    roles = spans.step_roles(cell["trace"], spans.scope_map(cell["programs"]))
    assert roles["roles"]["kernel"] == pytest.approx(red["kernel_s"])
    assert roles["roles"]["other"] == pytest.approx(62.060e-6)
    assert not roles["unscoped"]
    assert got["host_ms_per_dispatch"] == pytest.approx(
        239.520325 - 234.662565)


@pytest.mark.parametrize("cell", [k for k in SLICE if k != "about"])
def test_the_existing_reduction_reads_the_new_slices_as_before(cell):
    """The spans on the host planes change nothing the kernel reduction
    reads: busy, kernel time and bounds are those of the slice without
    them."""
    tr = SLICE[cell]["trace"]
    bare = {"planes": [p if p["name"].startswith("/device:") else
                       {**p, "lines": [{**ln, "events": [
                           ev for ev in ln["events"]
                           if not ev[0].startswith("serve.")]}
                           for ln in p["lines"]]}
                       for p in tr["planes"]]}
    red, red_bare = _busy(SLICE[cell]), _busy({**SLICE[cell], "trace": bare})
    assert {k: red[k] for k in ("busy_s", "kernel_s", "bound_s",
                                "kernel_calls", "families", "device_ops")} \
        == {k: red_bare[k] for k in ("busy_s", "kernel_s", "bound_s",
                                     "kernel_calls", "families",
                                     "device_ops")}
    assert red["kernel_calls"] > 0

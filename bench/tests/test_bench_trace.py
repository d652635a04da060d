"""The reduction from a trace to busy time, kernel time and roofline."""
import pytest

from bench import registry
from bench import trace as T

# a tpu_custom_call as the TPU compiler prints it (kernel body elided)
HLO = """
  %p0 = f32[512,128]{1,0} parameter(0)
  %matmul_op.1 = f32[512,1024]{1,0:T(8,128)S(1)} custom-call(%copy-done.1, %pad.0, %bitcast.1, %pad.2), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[512,128]{1,0}, f32[128,1024]{1,0}, f32[1,512]{1,0}, f32[512,1024]{1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(_lambda_)/jit(matmul_op)/pallas_call" stack_frame_id=6}, backend_config={"custom_call_config":{"body":"TUzvUg"}}
  %winograd_conv_batch.4 = f32[2,36,64,25]{3,2,1,0:T(8,128)S(1)} custom-call(%bitcast.338, %copy.70), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[36,64,64]{2,1,0}, f32[2,36,64,25]{3,2,1,0}}, metadata={op_name="jit(fn2)/jit(winograd_conv_batch)/pallas_call" stack_frame_id=15}
  %fusion.3 = f32[8]{0} fusion(%p0), kind=kLoop
"""

PEAKS = (197e12, 819e9)


def _work(family):
    return registry.load_module("work", family).work


def test_union_and_gaps_of_overlapping_intervals():
    iv = [(0, 10), (5, 10), (20, 5), (40, 1), (22, 1)]
    assert T.union_ns(iv) == 15 + 5 + 1
    assert T.gaps_ns(iv) == [(15, 5), (25, 15)]
    assert T.union_ns([]) == 0


def test_custom_calls_of_a_compiled_program():
    calls = T.custom_calls(HLO)
    assert set(calls) == {"matmul_op.1", "winograd_conv_batch.4"}
    mm = calls["matmul_op.1"]
    assert mm["family"] == "matmul_op"
    assert mm["operands"] == [("f32", (512, 128)), ("f32", (128, 1024)),
                              ("f32", (1, 512)), ("f32", (512, 1024))]
    assert mm["results"] == [("f32", (512, 1024))]
    assert calls["winograd_conv_batch.4"]["family"] == "winograd_conv_batch"


def _trace(events, plane="/device:TPU:0"):
    return {"planes": [
        {"name": plane, "lines": [
            {"name": "XLA Modules", "events": [["jit__lambda_", 0, 10_000, {}]]},
            {"name": "XLA Ops", "events": [list(e) + [{}] for e in events]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["ExecuteProgram", 3_000, 2_500, {}]]}]}]}


def test_reduce_matches_kernel_events_by_instruction_name():
    tr = _trace([("fusion.3", 0, 1_000), ("matmul_op.1", 1_000, 2_000),
                 ("copy.7", 2_500, 1_000), ("winograd_conv_batch.4", 6_000, 500)])
    red = T.reduce(tr, T.program_calls([HLO]), _work, *PEAKS)
    assert red["busy_s"] == pytest.approx(3_500e-9 + 500e-9)
    assert red["kernel_s"] == pytest.approx(2_500e-9)
    assert red["kernel_calls"] == 2
    mm_flops = 2 * 512 * 1024 * 128 + 2 * 512 * 1024
    assert red["families"]["matmul_op"]["flops"] == mm_flops
    mm_bytes = 4 * (512 * 128 + 128 * 1024 + 512 + 2 * 512 * 1024)
    assert red["families"]["matmul_op"]["bound_s"] == pytest.approx(
        max(mm_flops / PEAKS[0], mm_bytes / PEAKS[1]))
    assert red["gaps"][0] == (3_500, 2_500)
    assert T.host_activity(tr, *red["gaps"][0]) == "ExecuteProgram"


def test_a_kernel_family_without_a_work_file_is_an_error():
    hlo = HLO.replace("jit(matmul_op)", "jit(new_kernel)")
    tr = _trace([("matmul_op.1", 0, 100)])
    with pytest.raises(LookupError):
        T.reduce(tr, T.program_calls([hlo]), _work, *PEAKS)


def test_programs_sharing_a_kernel_name_are_told_apart_by_shapes():
    """Two buckets' programs name a kernel alike with other shapes; each
    event is matched by the shapes its own text gives."""
    small = HLO.replace("f32[512,1024]", "f32[512,512]").replace(
        "f32[128,1024]", "f32[128,512]")
    calls = T.program_calls([HLO, small, HLO])
    assert len(calls["matmul_op.1"]) == 2 and len(calls["winograd_conv_batch.4"]) == 1
    line = [ln for ln in small.splitlines() if "matmul_op.1 =" in ln][0]
    ev = line.strip().split(", metadata=")[0]
    tr = _trace([(ev, 0, 1_000)])
    red = T.reduce(tr, calls, _work, *PEAKS)
    assert red["families"]["matmul_op"]["flops"] == 2 * 512 * 512 * 128 + 2 * 512 * 512
    with pytest.raises(ValueError):
        T.reduce(_trace([("%matmul_op.1 = f32[8]{0} custom-call()", 0, 10)]),
                 calls, _work, *PEAKS)


def test_a_trace_without_a_tpu_is_refused():
    with pytest.raises(ValueError):
        T.reduce(_trace([], plane="/host:CPU"), {}, _work, *PEAKS)


def _recorded():
    import json
    from pathlib import Path
    data = json.loads((Path(__file__).parent / "data" /
                       "resnet50_offline_trace.json").read_text())
    calls = {k: {"family": v["family"],
                 "operands": [(d, tuple(s)) for d, s in v["operands"]],
                 "results": [(d, tuple(s)) for d, s in v["results"]]}
             for k, v in data["calls"].items()}
    return data["trace"], calls


def test_recorded_trace_matches_every_kernel_by_instruction_name():
    """A slice of a real trace: each ``XLA Ops`` event whose text is a
    ``tpu_custom_call`` is matched to the compiled program's kernel of the
    same instruction name, with the same operand shapes, and no other
    event is."""
    tr, calls = _recorded()
    calls = {k: [v] for k, v in calls.items()}
    ops = T.op_events(T.device_planes(tr)[0])
    kernels = [e for e in ops if 'custom_call_target="tpu_custom_call"' in e[0]]
    assert kernels and len(kernels) < len(ops)
    for e in kernels:
        call, = calls[T.instruction(e[0])]
        operands = e[0].split("custom-call(")[1].split("), custom_call_target")[0]
        assert [(d, s) for d, s in T.shapes(operands)] == call["operands"]
    red = T.reduce(tr, calls, _work, *PEAKS)
    assert red["kernel_calls"] == len(kernels)
    assert red["kernel_s"] == pytest.approx(sum(e[2] for e in kernels) * 1e-9)
    span = (max(e[1] + e[2] for e in ops) - min(e[1] for e in ops)) * 1e-9
    assert red["kernel_s"] < red["busy_s"] <= span
    assert red["busy_s"] == pytest.approx(
        span - sum(g for _, g in T.gaps_ns([(e[1], e[2]) for e in ops])) * 1e-9)
    assert 0 < red["bound_s"] < red["kernel_s"]
    assert set(red["families"]) == {c["family"] for v in calls.values() for c in v}
    assert T.instruction("%matmul_op.89 = f32[8]{0} custom-call()") == "matmul_op.89"

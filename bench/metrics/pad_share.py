"""Share of the device's busy time in the zero padding of padded conv steps
that XLA materialised: ops whose own ``op_name`` holds a plan step's
``pack/pad`` scope (``variants.pad_input``, or the pad folded into a
Winograd or fused im2col kernel's own), their durations summed as
``pack_share`` sums its ops. A pad fused into the op that reads it is no op
of its own: 0.0 where every pad fused. None where no program that ran
marks a pad (no padded step, or a program without the scope)."""
from collections import defaultdict

from bench import spans
from bench import trace as T


def _is_pad(op_name: str) -> bool:
    parts = op_name.split("/")
    for i, p in enumerate(parts):
        if spans.STEP.fullmatch(p):
            rest = parts[i + 1:]
            return any(a == "pack" and b == "pad"
                       for a, b in zip(rest, rest[1:]))
    return False


def _pads(programs):
    """Instruction name → {result shapes: is a pad} over the programs, and
    whether any instruction of them has a pad scope."""
    found = defaultdict(dict)
    marked = False
    for text in programs:
        for line in text.splitlines():
            m = spans._INSTR.match(line)
            if not m:
                continue
            name, rest = m.groups()
            op = spans._OP_NAME.search(rest)
            pad = bool(op) and _is_pad(op.group(1))
            marked |= pad
            head = spans._RESULT.match(rest)
            shapes = tuple(T.shapes(head.group(1))) if head else ()
            found[name][shapes] = found[name].get(shapes, False) or pad
    return found, marked


def read(run):
    tr = run.trace
    if not tr or not tr["busy_s"] or run.trace_events is None \
            or run.programs is None:
        return None
    found, marked = _pads(run.programs)
    if not marked:
        return None
    planes = T.device_planes(run.trace_events)
    pad_s = 0.0
    for plane in planes:
        for ev in T.op_events(plane):
            by_shape = found.get(T.instruction(ev[0]))
            if not by_shape:
                continue
            if len(set(by_shape.values())) == 1:
                pad = next(iter(by_shape.values()))
            else:
                m = spans._RESULT.match(ev[0].split("=", 1)[-1])
                pad = by_shape.get(tuple(T.shapes(m.group(1))) if m else (),
                                   False)
            if pad:
                pad_s += ev[2] * 1e-9 / len(planes)
    return 100.0 * pad_s / tr["busy_s"]

"""From a profiler trace of the window to device time, kernel time and
roofline bounds.

``capture`` records the window with ``jax.profiler`` in the process that
holds the chip. ``load`` turns the ``.xplane.pb`` it writes into plain
lists (planes → lines → ``(name, start_ns, duration_ns)``), which is
also the form of the small recorded trace the tests read. Everything
after that is arithmetic on those lists:

- busy time: the union of the intervals of the device's ``XLA Ops``
  events (the device's own clock), and its idle share of the window;
- kernel time: the ``XLA Ops`` events whose name is the HLO instruction
  name of a ``tpu_custom_call`` in the compiled programs that ran;
- each kernel call's least time, ``max(FLOPs / peak, bytes / bandwidth)``,
  with FLOPs and bytes from ``work/<family>.py`` over the call's operand
  and result shapes in the compiled program.
"""
from __future__ import annotations

import contextlib
import re
from collections import Counter, defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


@contextlib.contextmanager
def capture(log_dir: Path):
    import jax
    jax.profiler.start_trace(str(log_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: Path) -> Dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as plain lists of
    ``(name, start_ns, duration_ns)``. Of the device planes only the op
    line is kept; names are interned, since a trace repeats a few thousand
    names over millions of events."""
    import sys
    import jax
    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = [{"name": line.name,
                  "events": [(sys.intern(ev.name), int(ev.start_ns),
                              int(ev.duration_ns)) for ev in line.events]}
                 for line in plane.lines
                 if not device or line.name == OPS_LINE]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(tr: Dict) -> List[Dict]:
    return [p for p in tr["planes"] if re.match(r"/device:TPU:\d+$", p["name"])]


def op_events(plane: Dict) -> List[list]:
    return [ev for line in plane["lines"] if line["name"] == OPS_LINE
            for ev in line["events"]]


def instruction(event_name: str) -> str:
    """The HLO instruction name of a device op event. The TPU names each
    ``XLA Ops`` event by its instruction's text, ``%name = shape op(...)``."""
    m = re.match(r"\s*%?([^\s=]+)", event_name)
    return m.group(1) if m else event_name


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    """Length of the union of ``(start, duration)`` intervals."""
    total, end = 0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps_ns(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """``(start, length)`` of each gap between the union's pieces."""
    out, end = [], None
    for s, d in sorted(intervals):
        if end is not None and s > end:
            out.append((end, s - end))
        end = s + d if end is None else max(end, s + d)
    return out


# -- the compiled programs ---------------------------------------------------

def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def custom_calls(hlo: str) -> Dict[str, Dict]:
    """Every ``tpu_custom_call`` of an optimised HLO module: instruction name
    → kernel family, operand shapes and result shapes. The family is the
    innermost ``jit(...)`` around the ``pallas_call`` in the instruction's
    ``op_name``: the function the kernel is launched from."""
    out = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s*custom-call\(",
                     line)
        ops = re.search(r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}",
                        line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if not (m and ops):
            raise ValueError(f"unreadable tpu_custom_call: {line[:300]}")
        jits = re.findall(r"jit\(([\w.\-]+)\)",
                          op_name.group(1).split("pallas_call")[0]
                          if op_name else "")
        out[m.group(1)] = {"family": jits[-1] if jits else None,
                           "operands": shapes(ops.group(1)),
                           "results": shapes(m.group(2))}
    return out


# -- the reduction -------------------------------------------------------------

def program_calls(programs: Sequence[str]) -> Dict[str, List[Dict]]:
    """The ``tpu_custom_call``s of every program that ran, by instruction
    name. Programs of different buckets reuse names with other shapes, so
    a name maps to each distinct call that carries it."""
    calls: Dict[str, List[Dict]] = defaultdict(list)
    for text in programs:
        for name, call in custom_calls(text).items():
            if call not in calls[name]:
                calls[name].append(call)
    return dict(calls)


def _match(calls: Dict[str, List[Dict]], event_name: str) -> Optional[Dict]:
    """The compiled call an op event ran: by instruction name, and where
    programs share the name, by the operand and result shapes the event's
    own text gives."""
    found = calls.get(instruction(event_name))
    if not found:
        return None
    if len(found) == 1:
        return found[0]
    ev = custom_calls(event_name).get(instruction(event_name))
    for call in found:
        if ev and (call["operands"], call["results"]) == (ev["operands"],
                                                          ev["results"]):
            return call
    raise ValueError(f"no compiled kernel has the shapes of {event_name[:200]}")


def reduce(tr: Dict, calls: Dict[str, List[Dict]], work, peak_flops: float,
           peak_bytes_per_s: float) -> Dict:
    """Device busy time, kernel time and roofline bounds of a trace.

    ``calls``: ``program_calls`` of the programs that ran in the window.
    ``work(family)`` returns the family's ``work(operands, results)`` →
    ``(flops, bytes)``, and raises ``LookupError`` for a family without one.
    Times are in seconds, averaged over the device planes."""
    planes = device_planes(tr)
    if not planes:
        raise ValueError("the trace has no TPU device plane")
    busy = kernel = bound = 0.0
    calls_run = 0
    bases: Dict[str, str] = {}                 # per distinct event name
    matched: Dict[str, Optional[Tuple[str, float, float]]] = {}

    def _work(call: Optional[Dict], ev_name: str):
        """(family, FLOPs, least seconds) of a kernel call, or None."""
        if call is None:
            return None
        if call["family"] is None:
            raise LookupError(f"kernel {instruction(ev_name)} has no family "
                              f"in its op_name")
        flops, nb = work(call["family"])(call["operands"], call["results"])
        return (call["family"], flops,
                max(flops / peak_flops, nb / peak_bytes_per_s))

    by_op: Counter = Counter()
    by_family: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0, 0.0])
    spans: List[Tuple[int, int]] = []
    for plane in planes:
        evs = op_events(plane)
        iv = [(ev[1], ev[2]) for ev in evs]
        busy += union_ns(iv) * 1e-9
        spans += iv
        for ev in evs:
            ev_name, d = ev[0], ev[2]
            base = bases.get(ev_name)
            if base is None:
                base = bases[ev_name] = re.sub(r"\.\d+$", "",
                                               instruction(ev_name))
            by_op[base] += d * 1e-9
            if ev_name not in matched:
                matched[ev_name] = _work(_match(calls, ev_name), ev_name)
            if matched[ev_name] is None:
                continue
            family, flops, least = matched[ev_name]
            calls_run += 1
            kernel += d * 1e-9
            bound += least
            fam = by_family[family]
            fam[0] += d * 1e-9
            fam[1] += least
            fam[2] += flops
    n = len(planes)
    return {"busy_s": busy / n, "kernel_s": kernel / n, "bound_s": bound / n,
            "kernel_calls": calls_run // n,
            "families": {k: {"time_s": v[0] / n, "bound_s": v[1] / n,
                             "flops": v[2] / n}
                         for k, v in by_family.items()},
            "device_ops": [[k, v / n] for k, v in by_op.most_common(10)],
            "gaps": sorted(gaps_ns(spans), key=lambda g: -g[1])[:10]}


_WAITING = re.compile(r"\b(wait|sleep|acquire|select|poll|futex)\b", re.I)


def host_activity(tr: Dict, start_ns: int, length_ns: int) -> str:
    """What the host threads were doing in a gap: the names of the host
    events that overlap it most, longest overlap first, leaving out events
    that only wait (a thread blocked on a lock or a sleep)."""
    over: Counter = Counter()
    end = start_ns + length_ns
    for plane in tr["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                name, s, d = ev[0], ev[1], ev[2]
                o = min(end, s + d) - max(start_ns, s)
                if (o > 0 and d < 10 * length_ns + 1_000_000
                        and not _WAITING.search(name)):
                    over[name] += o
    top = [n for n, _ in over.most_common(2)]
    return " | ".join(top) if top else "no host event"

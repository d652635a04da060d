"""The program's own spans and scopes in a traced window.

The served path writes host spans ``serve.*`` into the profiler's trace
(``OptimisedServer``), on the clock of the device's ops, and the plan runs
each step under a ``jax.named_scope`` that reaches every compiled
instruction's ``op_name``: ``conv<node>``, ``join<node>`` or
``eltwise<node>``, and inside a step the role of its glue, ``dlt``,
``pack`` or ``wpack`` (``plan._emit``). Three readers:

- ``host_spans``: per ``serve.execute`` span, its duration and its
  children's; the time the worker waited for a batch window
  (``serve.window``);
- ``scope_map``: instruction name → (result shapes, step, role) from the
  ``op_name`` of each compiled program that ran. An instruction whose own
  ``op_name`` names no step (a copy that layout assignment added has none)
  takes the step and role of its first operand that has them, else of its
  first user that has them, else (in a called computation, such as a
  loop's body) of the op that calls it. Taken from a kernel, the role is
  ``pack`` for an op that reads the kernel's result and ``wpack`` for one
  that carries an argument of the program into it: the plan's activations
  reach a kernel through ``pack`` ops of their own, its weights may not;
- ``step_roles``: device seconds per (step, role), an event matched to its
  instruction by name and, where programs share the name, by the result
  shapes its own text gives (as ``trace._match`` matches kernels).
  ``tpu_custom_call`` instructions take the role ``kernel``; an op of a
  step under no role takes ``other``.

``of(run)`` reduces a traced run once and keeps the result on the run.
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace as T

STEP = re.compile(r"(conv|join|eltwise)\d+$")
ROLES = ("dlt", "pack", "wpack")
KERNEL = 'custom_call_target="tpu_custom_call"'
PHASES = ("serve.assemble", "serve.call", "serve.device", "serve.fetch",
          "serve.validate", "serve.deliver")
_INSTR = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s+=\s+(.*)$")
_RESULT = re.compile(r"(.*?)\s[\w\-]+\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


# -- host spans --------------------------------------------------------------

def host_spans(tr: Dict) -> Dict:
    """``serve.*`` spans of the host planes: one entry per ``serve.execute``
    span, ``{"ms": duration, "phases": {child name: ms}}`` (a retried
    dispatch sums its attempts), and ``window_ms``, the sum of
    ``serve.window`` spans."""
    dispatches: List[Dict] = []
    window_ns = 0
    for plane in tr["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            evs = sorted((ev for ev in line["events"]
                          if ev[0].startswith("serve.")),
                         key=lambda ev: ev[1])
            window_ns += sum(ev[2] for ev in evs if ev[0] == "serve.window")
            for ev in evs:
                if ev[0] != "serve.execute":
                    continue
                s, e = ev[1], ev[1] + ev[2]
                phases: Dict[str, float] = defaultdict(float)
                for ch in evs:
                    if (ch[0] in PHASES and s <= ch[1]
                            and ch[1] + ch[2] <= e):
                        phases[ch[0]] += ch[2] * 1e-6
                dispatches.append({"ms": ev[2] * 1e-6, "phases": dict(phases)})
    return {"dispatches": dispatches, "window_ms": window_ns * 1e-6}


# -- scopes ------------------------------------------------------------------

def _step_role(op_name: str) -> Optional[Tuple[str, str]]:
    """(step, innermost role or ``other``) of a scope path, or None where
    the path names no plan step."""
    parts = op_name.split("/")
    for i, p in enumerate(parts):
        if STEP.fullmatch(p):
            roles = [q for q in parts[i + 1:] if q in ROLES]
            return p, roles[-1] if roles else "other"
    return None


def scope_map(programs: Sequence[str]) -> Dict[str, List[Tuple]]:
    """Instruction name → distinct ``(result shapes, step, role)`` over the
    optimised programs' texts; ``step`` and ``role`` are None for an
    instruction that no fallback charges to a step."""
    out: Dict[str, List[Tuple]] = defaultdict(list)
    for text in programs:
        instrs: Dict[str, Tuple] = {}
        comp_of: Dict[str, str] = {}           # instruction → computation
        comp = ""
        for line in text.splitlines():
            m = _INSTR.match(line)
            if not m:
                head = _REF.search(line)
                if (head and line.startswith(("%", "ENTRY"))
                        and line.rstrip().endswith("{")):
                    comp = head.group(1)          # a computation's header
                continue
            name, rest = m.groups()
            comp_of[name] = comp
            head = _RESULT.match(rest)
            op = _OP_NAME.search(rest)
            own = _step_role(op.group(1)) if op else None
            if KERNEL in rest and own is not None:
                own = (own[0], "kernel")
            instrs[name] = (tuple(T.shapes(head.group(1)) if head else ()),
                            own, [r for r in _REF.findall(rest) if r != name])
        users: Dict[str, List[str]] = defaultdict(list)
        callers: Dict[str, List[str]] = defaultdict(list)   # of computations
        for name, (_, _, refs) in instrs.items():
            for r in refs:
                (users if r in instrs else callers)[r].append(name)
        memo: Dict[str, Optional[Tuple[str, str]]] = {}

        def via_operands(name: str, seen: set) -> Optional[Tuple[str, str]]:
            if name in memo:
                return memo[name]
            if name in seen:
                return None
            seen.add(name)
            _, own, refs = instrs[name]
            found = own
            for r in refs:
                if found is not None:
                    break
                if r in instrs:
                    found = via_operands(r, seen)
            memo[name] = found
            return found

        def via_users(name: str, seen: set) -> Optional[Tuple[str, str]]:
            seen.add(name)
            for u in users[name]:
                if u not in seen:
                    found = via_operands(u, set()) or via_users(u, seen)
                    if found is not None:
                        return found
            return None

        def resolve(name: str, seen: set) -> Optional[Tuple[str, str]]:
            own = instrs[name][1]
            sr = via_operands(name, set())
            if sr is not None and sr[1] == "kernel" and own is None:
                sr = (sr[0], "pack")           # reads a kernel's result
            if sr is None:
                sr = via_users(name, set())
                if sr is not None and sr[1] == "kernel":
                    sr = (sr[0], "wpack")      # an argument into a kernel
            # an op of a called computation (a loop's body) and nothing
            # around it that names a step: the op that calls it
            for c in callers[comp_of[name]] if sr is None else ():
                if c not in seen:
                    seen.add(c)
                    sr = resolve(c, seen)
                    if sr is not None:
                        break
            return sr

        for name, (shapes, _, _) in instrs.items():
            sr = resolve(name, {name})
            entry = (shapes,) + (sr or (None, None))
            if entry not in out[name]:
                out[name].append(entry)
    return dict(out)


def _lookup(scopes: Dict[str, List[Tuple]], event_name: str
            ) -> Optional[Tuple[Optional[str], Optional[str]]]:
    found = scopes.get(T.instruction(event_name))
    if not found:
        return None
    if len({f[1:] for f in found}) == 1:
        return found[0][1:]
    m = _RESULT.match(event_name.split("=", 1)[-1])
    shapes = tuple(T.shapes(m.group(1))) if m else ()
    same = {f[1:] for f in found if f[0] == shapes}
    return same.pop() if len(same) == 1 else None


def step_roles(tr: Dict, scopes: Dict[str, List[Tuple]]) -> Dict:
    """Device seconds of the ``XLA Ops`` events per step and role, averaged
    over the device planes: ``{"steps": {step: {role: s}}, "roles": {role:
    s}, "unscoped": {op: s}}``, an op of no step under ``unscoped`` by its
    instruction's base name."""
    planes = T.device_planes(tr)
    steps: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    roles: Dict[str, float] = defaultdict(float)
    unscoped: Dict[str, float] = defaultdict(float)
    seen: Dict[str, Optional[Tuple]] = {}
    n = max(len(planes), 1)
    for plane in planes:
        for ev in T.op_events(plane):
            if ev[0] not in seen:
                seen[ev[0]] = _lookup(scopes, ev[0])
            sr = seen[ev[0]]
            d = ev[2] * 1e-9 / n
            if sr is None or sr[0] is None:
                unscoped[re.sub(r"\.\d+$", "", T.instruction(ev[0]))] += d
                continue
            steps[sr[0]][sr[1]] += d
            roles[sr[1]] += d
    return {"steps": {k: dict(v) for k, v in steps.items()},
            "roles": dict(roles), "unscoped": dict(unscoped)}


# -- a run -------------------------------------------------------------------

def of(run) -> Optional[Dict]:
    """The spans and scopes of a traced run: ``host_spans`` and
    ``step_roles`` with the busy time they are shares of, reduced once and
    kept as ``run.spans``. None for an untraced run, or one that carries no
    loaded trace and program texts (``run.trace_events``, ``run.programs``,
    which ``run.run_cell`` keeps)."""
    if getattr(run, "spans", None) is not None:
        return run.spans
    if not run.trace or run.trace_events is None or run.programs is None:
        return None
    tr = run.trace_events
    run.spans = {"host": host_spans(tr),
                 "device": step_roles(tr, scope_map(run.programs)),
                 "busy_s": run.trace["busy_s"]}
    _log(run.spans)
    return run.spans


def _log(sp: Dict) -> None:
    """The dispatch phases and the ten steps with most device time, on
    standard error."""
    ds = sp["host"]["dispatches"]
    if ds:
        mean = {p: sum(d["phases"].get(p, 0.0) for d in ds) / len(ds)
                for p in PHASES}
        print(f"[bench] spans: {len(ds)} serve.execute, mean "
              f"{sum(d['ms'] for d in ds) / len(ds):.4f} ms; phases (mean ms) "
              + ", ".join(f"{p[6:]} {v:.4f}" for p, v in mean.items())
              + f"; serve.window {sp['host']['window_ms']:.3f} ms in all",
              file=sys.stderr, flush=True)
    dev = sp["device"]
    top = sorted(dev["steps"].items(), key=lambda kv: -sum(kv[1].values()))
    print("[bench] scopes: roles (s) "
          + ", ".join(f"{r} {v:.6f}" for r, v in sorted(dev["roles"].items()))
          + f"; unscoped {sum(dev['unscoped'].values()):.6f} s, top "
          + ", ".join(f"{k} {v:.6f}" for k, v in sorted(
              dev["unscoped"].items(), key=lambda kv: -kv[1])[:5])
          + "; top steps " + "; ".join(
              f"{k} " + " ".join(f"{r} {v:.6f}" for r, v in sorted(rv.items()))
              for k, rv in top[:10]),
          file=sys.stderr, flush=True)


def role_share(run, role: str) -> Optional[float]:
    """Percent of device busy time in ops of ``role`` under a plan step;
    None where no op ran under a plan step."""
    sp = of(run)
    if sp is None or not sp["busy_s"] or not sp["device"]["steps"]:
        return None
    return 100.0 * sp["device"]["roles"].get(role, 0.0) / sp["busy_s"]

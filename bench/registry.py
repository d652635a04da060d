"""Find the benchmark's parts by name: one file each.

- ``BENCHMARK.json`` at the checkout's root: cells and metrics;
- ``configs/<config>.json``: a network's sizes, and under ``"reference"``
  the name of its plain reference;
- ``references/<reference>.py``: that network built on a given
  convolution, ``convs(cfg)`` and ``run(cfg, weights, x, conv)``, which
  ``reference.py`` dispatches to;
- ``traffic/<mix>.json``: a traffic mix's parameters, and the generator
  ``traffic/<kind>.py`` that its ``"kind"`` names;
- ``metrics/<metric>.py``: the reader of one metric, ``read(run)``;
- ``work/<family>.py``: operations and bytes of one Pallas kernel family,
  ``work(operands, result)``.

A later configuration, cell, mix, metric or kernel family is a new file
here, found by the name that ``BENCHMARK.json``, a configuration or the
compiled program gives it.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Dict

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
_MODULES: Dict[Path, ModuleType] = {}


class Missing(LookupError):
    """No file of that kind carries that name."""


def _path(kind: str, name: str, suffix: str) -> Path:
    if not _NAME.match(name):
        raise Missing(f"{kind} name {name!r} is not a benchmark name")
    path = BENCH / kind / f"{name}{suffix}"
    if not path.is_file():
        raise Missing(f"no {kind} file {path.relative_to(ROOT)}")
    return path


def load_json(kind: str, name: str) -> Dict:
    return json.loads(_path(kind, name, ".json").read_text())


def load_module(kind: str, name: str) -> ModuleType:
    """Import ``<kind>/<name>.py`` once; dots in a name are allowed."""
    path = _path(kind, name, ".py")
    mod = _MODULES.get(path)
    if mod is None:
        key = "bench_" + re.sub(r"\W", "_", f"{kind}_{name}")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return mod


def benchmark(root: Path = ROOT) -> Dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise Missing(f"no {path.name} at {root}")
    return json.loads(path.read_text())


def cell(bench: Dict, workload: str) -> Dict:
    """The workload entry, with its config (whose reference must resolve)
    and traffic mix loaded, and the metrics it reports: ``end_to_end`` and
    ``per_layer`` lists of entries whose ``workloads`` (where given) name
    this cell."""
    for w in bench["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise Missing(f"no workload {workload!r} in BENCHMARK.json")

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    config = load_json("configs", w["config"])
    load_module("references", config["reference"])
    return {**w,
            "config_data": config,
            "traffic_data": load_json("traffic", w["traffic"]),
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}

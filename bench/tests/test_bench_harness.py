"""The harness end to end on the CPU, at a small size: a sound run is
correct, and a run whose served path is broken underneath is not."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import registry
from bench import run as R
from bench.tests import tiny

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def system():
    from repro.service.pipeline import optimise
    from repro.service.platforms import PallasPlatform
    limit = registry.load_json("configs", "resnet50-valid")["max_rel_err_limit"]
    cfg = tiny.config("bottleneck", limit=limit)
    spec = tiny.spec(cfg)
    opt = optimise(spec, PallasPlatform(), executable=True, seed=0)
    return cfg, spec, opt


def _run(system, mix="offline", seed=2 ** 31 + 7, **over):
    cfg, spec, opt = system
    cell = tiny.cell(cfg, mix, **over)
    full = registry.cell(registry.benchmark(), f"resnet50-valid.{mix}")
    cell["end_to_end"] = full["end_to_end"]
    import time
    return R.run_cell(cell, seed, 1.0, False, PEAKS, spec=spec,
                      optimise_fn=lambda s: opt, t_start=time.perf_counter())


@pytest.mark.parametrize("mix", ["offline", "single", "server"])
def test_sound_run_is_correct(system, mix):
    out = _run(system, mix, **({"rate_per_s": 40.0} if mix == "server" else {}))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    listed = registry.cell(registry.benchmark(),
                           f"resnet50-valid.{mix}")["end_to_end"]
    assert set(out["metrics"]) == {m["name"] for m in listed}


def _break(monkeypatch, alter):
    from repro.service.serving.server import OptimisedServer
    real = OptimisedServer._run_plan

    def broken(self, opt, xs, weights):
        return alter(np.array(real(self, opt, xs, weights)))
    monkeypatch.setattr(OptimisedServer, "_run_plan", broken)


def _alter_one(out):
    out[0].flat[0] += 1e-3 * np.abs(out[0]).max()
    return out


def _poison(out):
    out[-1].flat[0] = np.nan
    return out


def _fail(out):
    raise RuntimeError("injected")


@pytest.mark.parametrize("fault,check", [
    (_alter_one, "max_rel_err"),                 # an answer altered
    (lambda out: out[::-1].copy(), "max_rel_err"),  # answers to the wrong requests
    (_poison, "unanswered"),     # not finite: the server refuses its batch
    (_fail, "unanswered"),                       # a dispatch that raises
], ids=["altered", "swapped", "not-finite", "raised"])
def test_a_broken_served_path_is_caught(system, monkeypatch, fault, check):
    """The plan's output is broken where it is produced, underneath the
    server; the run completes and ``correct`` comes out false on the
    number that sees it."""
    _break(monkeypatch, fault)
    out = _run(system)
    assert not out["correct"]
    c = out["checks"][check]
    assert c["value"] > c["limit"], out["checks"]


def _serve_the_control(monkeypatch, cfg, spec, opt):
    """The reference one precision below the configuration's (three
    bfloat16 passes) serves every batch in the plan's place, at the batch
    the server assembled."""
    from bench import reference
    from repro.primitives import layouts as L
    from repro.primitives.plan import compile_plan
    from repro.service.serving.server import OptimisedServer
    n0 = spec.nodes[0]
    plan = compile_plan(spec, opt.assignment, (1, n0.c, n0.im, n0.im))
    sink = plan.layouts[plan.sinks[-1]]

    def control(self, opt_, xs, weights):
        out = reference.forward(cfg, [weights[k] for k in sorted(weights)],
                                np.asarray(xs), "bf16x3")
        return np.asarray(L.from_chw(out, sink))
    monkeypatch.setattr(OptimisedServer, "_run_plan", control)


def _wrong_by_max_rel_err_alone(out):
    checks = out["checks"]
    assert not out["correct"]
    assert checks["max_rel_err"]["value"] > checks["max_rel_err"]["limit"]
    assert checks["malformed"]["value"] == checks["unanswered"]["value"] == 0


def test_the_control_in_the_programs_place_is_caught(system, monkeypatch):
    """The control in the plan's place: ``correct`` comes out false on
    ``max_rel_err`` under the configuration's own limit, and on nothing
    else."""
    _serve_the_control(monkeypatch, *system)
    _wrong_by_max_rel_err_alone(_run(system))


@pytest.fixture(scope="module")
def fire_bench(tmp_path_factory):
    """A copy of the benchmark plus two new files, and no other change: the
    fire module's configuration and its plain reference."""
    assert not (registry.BENCH / "references" / "fire.py").exists()
    bench = tmp_path_factory.mktemp("checkout") / "bench"
    shutil.copytree(registry.BENCH, bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    cfg = tiny.fire_config()
    (bench / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    (bench / "references" / "fire.py").write_text(tiny.FIRE_REFERENCE)
    return bench


@pytest.fixture(scope="module")
def fire_plans():
    """Selections for the fire module as the reference wires it, and with
    its two expand branches joined in the other order."""
    from repro.service.pipeline import optimise
    from repro.service.platforms import PallasPlatform
    cfg = tiny.fire_config()
    sound = optimise(tiny.fire_spec(cfg), PallasPlatform(), executable=True,
                     seed=0)
    swapped = optimise(tiny.fire_spec(cfg, swap=True), sound.platform,
                       models=sound.models, executable=True, seed=0)
    return {"sound": sound, "swapped": swapped}


@pytest.mark.parametrize("case", ["sound", "branches-swapped", "control"])
def test_a_concat_network_enters_as_new_files(fire_bench, fire_plans,
                                              monkeypatch, case):
    """A network with a concat join, found through its configuration's
    ``"reference"`` alone, runs through ``run_cell`` and is correct; with
    its branches joined in the other order (the same convolutions, so set-up
    takes it) or the control in the plan's place, it is not."""
    monkeypatch.setattr(registry, "BENCH", fire_bench)
    cfg = registry.load_json("configs", "fire-tiny")
    opt = fire_plans["swapped" if case == "branches-swapped" else "sound"]
    if case == "control":
        _serve_the_control(monkeypatch, cfg, opt.spec, opt)
    cell = tiny.cell(cfg, "offline")
    cell["end_to_end"] = registry.cell(registry.benchmark(),
                                       "resnet50-valid.offline")["end_to_end"]
    import time
    out = R.run_cell(cell, 2 ** 31 + 11, 1.0, False, PEAKS, spec=opt.spec,
                     optimise_fn=lambda s: opt, t_start=time.perf_counter())
    if case == "sound":
        assert out["correct"], out["checks"]
        assert out["attempted"] > 0 and out["failed"] == 0
        assert {"img_per_s", "setup_s"} <= set(out["metrics"])
    else:
        _wrong_by_max_rel_err_alone(out)


def _run_py(root, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *args], capture_output=True, text=True, env=env,
                          cwd=root, timeout=120)


def test_run_refuses_a_host_without_a_tpu():
    p = _run_py(registry.ROOT, "--workload", "resnet50-valid.offline",
                "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode == 3 and not p.stdout.strip()
    assert "not a TPU" in p.stderr


def test_run_refuses_a_directory_holding_only_the_benchmark(tmp_path):
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(registry.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run_py(tmp_path, "--workload", "resnet50-valid.offline",
                "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and not p.stdout.strip()

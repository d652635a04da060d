"""The benchmark finds its parts by name, and BENCHMARK.json names only
parts that exist."""
import json

import pytest

from bench import registry


@pytest.fixture(scope="module")
def bench():
    return registry.benchmark()


def test_every_cell_resolves_to_its_files(bench):
    for w in bench["workloads"]:
        cell = registry.cell(bench, w["name"])
        assert cell["config_data"]["name"] == w["config"]
        assert registry.load_module("traffic", cell["traffic_data"]["kind"])
        assert cell["end_to_end"] and cell["per_layer"]


def test_every_metric_has_a_reader(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(registry.load_module("metrics", m["name"]).read)


def test_per_layer_metrics_move_a_metric_their_cells_report(bench):
    for m in bench["per_layer"]:
        for name in m.get("workloads", [w["name"] for w in bench["workloads"]]):
            reported = {e["name"] for e in registry.cell(bench, name)["end_to_end"]}
            assert m["moves"] in reported, (m["name"], name)


def test_configs_files_are_the_benchmarks(bench):
    for c in bench["configs"]:
        data = json.loads((registry.ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        net = registry.load_module("references", data["reference"])
        assert callable(net.run) and net.convs(data)


def test_a_new_file_is_found_by_its_name(tmp_path, monkeypatch):
    """A later cell adds files only: a mix, a metric, a kernel family and a
    network's reference dropped into their directories are found by name."""
    for kind, name, text in (
            ("traffic", "burst", '{"kind": "poisson", "rate_per_s": 5}'),
            ("metrics", "answers.total", "def read(run):\n    return 7\n"),
            ("work", "new_kernel", "def work(o, r):\n    return 1, 2\n"),
            ("references", "new_net",
             "def convs(cfg):\n    return [(8, 3, 1, 1)]\n")):
        (tmp_path / kind).mkdir()
        suffix = ".json" if kind == "traffic" else ".py"
        (tmp_path / kind / f"{name}{suffix}").write_text(text)
    monkeypatch.setattr(registry, "BENCH", tmp_path)
    assert registry.load_json("traffic", "burst")["rate_per_s"] == 5
    assert registry.load_module("metrics", "answers.total").read(None) == 7
    assert registry.load_module("work", "new_kernel").work(None, None) == (1, 2)
    assert registry.load_module("references", "new_net").convs({}) == [
        (8, 3, 1, 1)]


def test_a_missing_or_unsafe_name_is_an_error():
    with pytest.raises(registry.Missing):
        registry.load_module("work", "no_such_family")
    with pytest.raises(registry.Missing):
        registry.load_json("configs", "../BENCHMARK")
    with pytest.raises(registry.Missing):
        registry.cell(registry.benchmark(), "no.such.cell")
    with pytest.raises(registry.Missing):
        registry.load_module("references", "no_such_network")

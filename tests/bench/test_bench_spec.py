"""Every name in BENCHMARK.json finds its file by name, and the file
keeps to the benchmark's shape."""
import json
import os
import re

import pytest

from bench import spec

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_every_cell_loads_with_its_files(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert "loop" in cell.traffic
        assert cell.limits["max_logit_gap"]["limit"] > 0
        assert cell.limits["mean_logit_gap"]["limit"] > 0
        spec.reference(cell.config["reference"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_every_metric_has_its_reader(bench):
    for m in bench["end_to_end"]:
        assert callable(spec.metric_reader(m["name"], "e2e"))
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_names_units_and_bounds(bench):
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], m["layer"])
        for w in m.get("workloads", cells):
            moved = next(x for x in bench["end_to_end"]
                         if x["name"] == m["moves"])
            assert w in moved.get("workloads", cells)
    for c in bench["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert sorted(c["reduced"]) == sorted(conf["reduced"])


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-model.no-such-mix")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric")


def test_discovery_by_name_in_another_root(tiny_root):
    cell = spec.load_cell("tiny.closed", root=tiny_root)
    assert cell.traffic["loop"] == "closed"
    assert cell.config["hidden_size"] == 64

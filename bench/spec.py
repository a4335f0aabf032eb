"""Finds a cell's files by name.

``BENCHMARK.json`` names cells, configurations and metrics; each has a
file of its own under ``bench/``:

* ``configs/<config>.json``   model sizes as run, serving parameters
* ``traffic/<mix>.json``      one traffic mix (read by ``traffic/generator``)
* ``cells/<cell>.json``       the cell's correctness limits
* ``metrics/<metric>.py``     one reader per per-layer metric
* ``e2e/<metric>.py``         one reader per end-to-end metric
* ``references/<name>.py``    the plain reference a configuration names

Adding a cell, configuration, mix or metric adds files; nothing here
changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """A cell, configuration, mix or metric that cannot be found."""


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple  # BENCHMARK.json entries this cell reports
    per_layer: tuple


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    here = os.path.join(root, "bench")
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}
    if w["config"] not in cfg_file:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    return Cell(
        name=name,
        config_name=w["config"],
        traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=_read_json(os.path.join(root, cfg_file[w["config"]])),
        traffic=_read_json(os.path.join(here, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(here, "cells", name + ".json")),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _applies(m, name)),
    )


def _load_module(path: str, modname: str):
    if not os.path.isfile(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, kind: str = "metrics"):
    """``read(ctx)`` of ``<kind>/<name>.py`` (names may hold dots);
    ``kind`` is ``metrics`` (per-layer) or ``e2e`` (end-to-end)."""
    mod = _load_module(os.path.join(BENCH_DIR, kind, name + ".py"),
                       f"bench_{kind}_" + name.replace(".", "_"))
    return mod.read


def reference(name: str):
    """The plain reference module ``references/<name>.py``."""
    return _load_module(os.path.join(BENCH_DIR, "references", name + ".py"),
                        "bench_reference_" + name)

"""Finds everything a cell needs by name: ``BENCHMARK.json`` at the
checkout's root names the cell's configuration and traffic mix; the
files live at ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/limits/<cell>.json``; code that
belongs to one entry, reference or per-layer metric is a module of its own
under ``bench/entries/``, ``bench/references/`` and ``bench/metrics/``.
A new cell, mix or metric is new files and entries, never an edit."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict             # bench/configs/<config>.json
    traffic: dict            # bench/traffic/<traffic>.json
    limits: dict             # bench/limits/<cell>.json
    end_to_end: list         # BENCHMARK.json entries reported with --trace 0
    per_layer: list          # BENCHMARK.json entries reported with --trace 1


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = _json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench = root / "bench"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(root / conf["file"]),
        traffic=_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=_json(bench / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric(entry: dict):
    """The reader of a per-layer metric, checked against its entry."""
    mod = module("metrics", entry["name"])
    for key in ("unit", "better", "source", "layer", "moves"):
        if getattr(mod, key.upper()) != entry[key]:
            raise ValueError(f"metric {entry['name']}: {key} is "
                             f"{entry[key]!r} in BENCHMARK.json but "
                             f"{getattr(mod, key.upper())!r} in its reader")
    return mod

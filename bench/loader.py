"""Finds everything a cell needs by name: ``BENCHMARK.json`` at the
checkout's root names the cell's configuration and traffic mix; the
files live at ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/limits/<cell>.json``; code that
belongs to one entry, reference or per-layer metric is a module of its own
under ``bench/entries/``, ``bench/references/`` and ``bench/metrics/``.
A new cell, mix or metric is new files and entries, never an edit.

The contract of an entry, ``bench/entries/<entry>.py`` (named by the
traffic mix's ``entry``), is all the harness knows of a kind of cell:

- ``setup(cell, seed)`` builds the inputs from the seed, compiles, warms
  up and returns a runner with
  ``call()`` (one timed call; its output is waited for),
  ``counts(out)`` (plain counts of one call's output, at least
  ``events``, summed by the cell's rate, and ``steps``, the call's loop
  iterations, by which the traced call's regions are divided),
  ``failed(counts)`` (the call retired other work than set-up fixed),
  ``outputs(out)`` (the compared state, read back to the host),
  ``check(outputs)`` (the compared numbers: exactly the names of
  ``bench/limits/<cell>.json``),
  ``program_text()`` (the compiled HLO text of what ``call`` runs),
  ``build_s`` and ``compile_s`` (seconds; ``compile_s`` None where the
  first call compiled, with its seconds in ``first_call_s``);
- ``control(cell, seed, time_dtype=None, alter=None)``: the compared
  numbers of the reference put in the program's place
  (``bench/control.py``).

``bench/tests/counts/<entry>.json`` holds one call's counts of such a
cell, from which the self-tests build what its per-layer readers read.
Every end-to-end metric whose ``workloads`` list holds a cell is that
cell's rate: the ``events`` of each call of the window per second."""
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
    root: pathlib.Path = ROOT    # the checkout that holds the cell's files


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
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        root=root)


def module(kind: str, name: str, root: pathlib.Path = ROOT):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def entry(c: Cell):
    """The entry module that drives the cell's calls."""
    return module("entries", c.traffic["entry"], c.root)


def metric(m: dict, root: pathlib.Path = ROOT):
    """The reader of a per-layer metric, checked against its entry ``m``
    in BENCHMARK.json."""
    mod = module("metrics", m["name"], root)
    for key in ("unit", "better", "source", "layer", "moves"):
        if getattr(mod, key.upper()) != m[key]:
            raise ValueError(f"metric {m['name']}: {key} is "
                             f"{m[key]!r} in BENCHMARK.json but "
                             f"{getattr(mod, key.upper())!r} in its reader")
    return mod

"""Everything a cell needs is found by name, and every name in
BENCHMARK.json has its file."""
import json

import pytest

from bench import loader
from bench.window import Call

SPEC = json.loads((loader.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = loader.cell(name)
    w = {x["name"]: x for x in SPEC["workloads"]}[name]
    conf = {c["name"]: c for c in SPEC["configs"]}[w["config"]]
    assert cell.config == json.loads((loader.ROOT / conf["file"]).read_text())
    assert cell.traffic == json.loads(
        (loader.BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    assert set(cell.limits) >= {"placement_mismatch", "energy_gap"}
    assert loader.module("entries", cell.traffic["entry"]).setup
    assert loader.module("references", cell.config["reference"]).simulate
    rate = "events_per_s" if cell.traffic["entry"] == "farm" \
        else "replica_events_per_s"
    assert [m["name"] for m in cell.end_to_end] == [rate, "setup_s"]
    # every per-layer metric moves an end-to-end metric its cell reports
    assert {m["moves"] for m in cell.per_layer} <= {rate, "setup_s"}


@pytest.mark.parametrize("entry", SPEC["per_layer"],
                         ids=[m["name"] for m in SPEC["per_layer"]])
def test_metric_reader_matches_its_entry(entry):
    mod = loader.metric(entry)
    assert callable(mod.read)


def _context(entry: str, trace: bool) -> dict:
    """What the harness hands a reader after a window of two calls."""
    counts = {"events": 800, "steps": 100, "done": False}
    if entry == "sweep":
        counts = {"events": 3000, "steps": 150, "done": True,
                  "replica_steps": [150, 120, 100],
                  "replica_events": [1200, 1000, 800]}
    tr = {"busy_s": 3.0, "window_s": 4.0} if trace else None
    return {"build_s": 1.0, "compile_s": 2.0, "trace": tr,
            "calls": [Call(0.0, 2.0, counts), Call(2.0, 4.0, counts)]}


@pytest.mark.parametrize("entry", SPEC["per_layer"],
                         ids=[m["name"] for m in SPEC["per_layer"]])
def test_metric_reads_a_number_in_each_of_its_cells(entry):
    reader = loader.metric(entry)
    cells = entry.get("workloads", CELLS)
    for name in cells:
        kind = loader.cell(name).traffic["entry"]
        value = reader.read(_context(kind, trace=True))
        assert value is not None and value > 0, name
    if entry["source"] == "device_trace":
        assert reader.read(_context("farm", trace=False)) is None


def test_metric_reader_disagreeing_with_entry_is_refused():
    entry = dict(SPEC["per_layer"][0], unit="furlongs")
    with pytest.raises(ValueError, match="unit"):
        loader.metric(entry)


def test_metric_restricted_to_its_workloads():
    sweep = [c for c in CELLS if c.startswith("caseB")][0]
    farm = [c for c in CELLS if c.startswith("farm")][0]
    names = lambda c: [m["name"] for m in loader.cell(c).per_layer]  # noqa: E731
    assert "replica_lane_share" in names(sweep)
    assert "replica_lane_share" not in names(farm)
    assert "step_ms.sweep" in names(sweep) and "step_ms" in names(farm)
    assert "step_ms" not in names(sweep)


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        loader.cell("no_such.cell")


def test_config_files_are_under_paths():
    for conf in SPEC["configs"]:
        assert any(conf["file"].startswith(p + "/") for p in SPEC["paths"])


def test_peaks_keyed_by_device_kind():
    from bench import peaks
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peak("TPU v9 imaginary")

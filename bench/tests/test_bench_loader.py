"""Everything a cell needs is found by name, and every name in
BENCHMARK.json has its file."""
import json

import pytest

from bench import loader

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
    assert cell.limits and all(isinstance(v, (int, float))
                               for v in cell.limits.values())
    assert loader.entry(cell).setup
    assert loader.module("references", cell.config["reference"]).simulate
    rate, = _rates(name)
    assert [m["name"] for m in cell.end_to_end] == [rate, "setup_s"]
    # every per-layer metric moves an end-to-end metric its cell reports
    assert {m["moves"] for m in cell.per_layer} <= {rate, "setup_s"}


def _rates(cell: str) -> list:
    """The end-to-end metrics whose workloads list holds the cell."""
    return [m["name"] for m in SPEC["end_to_end"]
            if cell in m.get("workloads", ())]


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_is_on_exactly_one_rates_list(name):
    assert len(_rates(name)) == 1, _rates(name)


@pytest.mark.parametrize("entry", SPEC["per_layer"],
                         ids=[m["name"] for m in SPEC["per_layer"]])
def test_metric_reader_matches_its_entry(entry):
    mod = loader.metric(entry)
    assert callable(mod.read)


# the metrics of the traced call's regions, program spans and jit
# counters, which a run has only with --trace 1
TRACED = {m["name"] for m in SPEC["per_layer"]
          if m["source"] == "device_trace"
          or m["name"].split(".")[0] == "jit_traces_per_call"}


@pytest.mark.parametrize("entry", SPEC["per_layer"],
                         ids=[m["name"] for m in SPEC["per_layer"]])
def test_metric_reads_a_number_in_each_of_its_cells(entry, reader_context):
    reader = loader.metric(entry)
    cells = entry.get("workloads", CELLS)
    for name in cells:
        kind = loader.cell(name).traffic["entry"]
        value = reader.read(reader_context(kind, trace=True))
        assert value is not None and value > 0, name
        if entry["name"] in TRACED:
            assert reader.read(reader_context(kind, trace=False)) is None


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

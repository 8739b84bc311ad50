"""A third kind of cell, written into a checkout of its own, runs through
the harness with no edit to it: its own ``BENCHMARK.json``,
configuration, traffic naming the entry ``toy``, entry module,
reference, counts fixture, per-layer readers, and a limits file with a
compared number that no other cell has.  The loader finds it, its rate
comes from the spec, every number of its limits file is judged, and a
number without a limit, or a limit without a number, is an error."""
import json

import jax
import pytest

from bench import compare, loader, regions, run, tracing

CELL = "toycfg.toymix"

SPEC = {
    "command": ["python3", "bench/run.py"], "paths": ["bench"],
    "run_seconds": 1,
    "configs": [{"name": "toycfg", "source": "a toy", "reduced": [],
                 "file": "bench/configs/toycfg.json", "why": "a toy"}],
    "workloads": [{"name": CELL, "config": "toycfg", "traffic": "toymix",
                   "chips": 1, "why": "a toy"}],
    "end_to_end": [
        {"name": "toy_events_per_s", "unit": "events/s", "better": "higher",
         "bound": 0.05, "source": "host_clock", "workloads": [CELL]},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}],
    "per_layer": [
        {"name": "toy_advance_ms", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "engine regions",
         "moves": "toy_events_per_s"},
        {"name": "toy_traces_per_call", "unit": "traces/call",
         "better": "lower", "source": "program_counter",
         "layer": "replica batch", "moves": "toy_events_per_s"}],
}

ENTRY = '''
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import loader


@jax.jit
def _step(x):
    with jax.named_scope("advance"):
        return jnp.sin(x) * 2.0


class Runner:
    def __init__(self, cell, seed):
        self.cell = cell
        n = cell.config["n"]
        self.x = jax.random.normal(jax.random.key(seed % 2**31), (n,))
        self.build_s = 0.0
        t0 = time.perf_counter()
        self.compiled = _step.lower(self.x).compile()
        self.compile_s = time.perf_counter() - t0
        jax.block_until_ready(self.call())

    def call(self):
        return _step(self.x)

    def counts(self, out):
        return {"events": int(out.shape[0]) * self.cell.traffic["repeat"],
                "steps": 1}

    def failed(self, counts):
        return counts["events"] != (self.cell.config["n"]
                                    * self.cell.traffic["repeat"])

    def outputs(self, out):
        return np.asarray(out)

    def program_text(self):
        return self.compiled.as_text()

    def check(self, outputs):
        ref = loader.module("references", self.cell.config["reference"],
                            self.cell.root).simulate(np.asarray(self.x))
        return {"value_gap": float(np.abs(outputs - ref).max()),
                "sign_mismatch": int((np.sign(outputs)
                                      != np.sign(ref)).sum())}


def setup(cell, seed):
    return Runner(cell, seed)
'''

REFERENCE = '''
import numpy as np


def simulate(x):
    return 2.0 * np.sin(x.astype(np.float64))
'''

READERS = {
    "toy_advance_ms": '''
LAYER = "engine regions"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "toy_events_per_s"


def read(run):
    regions = run["regions"]
    return regions["advance"] if regions else None
''',
    "toy_traces_per_call": '''
LAYER = "replica batch"
UNIT = "traces/call"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "toy_events_per_s"


def read(run):
    jit = run["jit_window"]
    return None if jit is None else jit["trace"]["count"] / len(run["calls"])
'''}


@pytest.fixture
def root(tmp_path):
    files = {
        "BENCHMARK.json": json.dumps(SPEC),
        "bench/configs/toycfg.json": json.dumps({"reference": "toyref",
                                                 "n": 64}),
        "bench/traffic/toymix.json": json.dumps({"entry": "toy",
                                                 "repeat": 3}),
        "bench/limits/toycfg.toymix.json": json.dumps(
            {"value_gap": 1e-5, "sign_mismatch": 0}),
        "bench/entries/toy.py": ENTRY,
        "bench/references/toyref.py": REFERENCE,
        "bench/tests/counts/toy.json": json.dumps({"events": 192,
                                                   "steps": 1}),
        **{f"bench/metrics/{n}.py": src for n, src in READERS.items()},
    }
    for name, text in files.items():
        (tmp_path / name).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / name).write_text(text)
    return tmp_path


def _args(trace: int):
    return run.parse(["--workload", CELL, "--seed", str(2**31 + 3),
                      "--seconds", "0.05", "--trace", str(trace)])


def test_loader_finds_the_cell_and_its_rate_from_the_spec(root):
    cell = loader.cell(CELL, root)
    assert cell.root == root and cell.traffic["entry"] == "toy"
    assert loader.entry(cell).setup
    assert [m["name"] for m in cell.end_to_end] == ["toy_events_per_s",
                                                    "setup_s"]
    assert list(cell.limits) == ["value_gap", "sign_mismatch"]


def test_its_readers_read_the_counts_fixture(root, reader_context):
    cell = loader.cell(CELL, root)
    for m in cell.per_layer:
        reader = loader.metric(m, root)
        assert reader.read(reader_context("toy", True, root)) > 0
        assert reader.read(reader_context("toy", False, root)) is None


def test_a_run_reports_its_rate_and_judges_every_number(root):
    cell = loader.cell(CELL, root)
    res = run.run(_args(0), cell, jax.devices())
    assert res["correct"], res["checks"]
    assert list(res["metrics"]) == ["toy_events_per_s", "setup_s"]
    assert res["metrics"]["toy_events_per_s"]["value"] > 0
    assert list(res["checks"]) == ["value_gap", "sign_mismatch"]


def test_the_extra_number_catches_a_broken_answer(root):
    def broken(cell, seed):
        r = loader.entry(cell).setup(cell, seed)
        good = r.call
        r.call = lambda: -good()
        return r

    cell = loader.cell(CELL, root)
    res = run.run(_args(0), cell, jax.devices(), entry_setup=broken)
    assert not res["correct"]
    sign = res["checks"]["sign_mismatch"]
    assert sign["value"] > sign["limit"]


def test_a_traced_run_reads_regions_and_jit_counters(root, monkeypatch):
    # the CPU's profiler writes no device plane: stand the traced call's
    # device ops in for it, on the ops of the toy's own program
    runners = []

    def setup(cell, seed):
        runners.append(loader.entry(cell).setup(cell, seed))
        return runners[-1]

    real = tracing.extract

    def extract(path):
        tr = real(path)
        (_, s, d), = [h for h in tr["host"] if h[0] == "call"]
        module, table = regions.hlo_regions(runners[0].program_text())
        op = [i for i, (r, _) in table.items() if r == "advance"][0]
        tr["device"] = {"/device:TPU:0": [(op, s + d // 4, d // 2)]}
        tr["modules"] = [(f"{module}(1)", s + d // 4, d // 2)]
        return tr

    monkeypatch.setattr(tracing, "extract", extract)
    cell = loader.cell(CELL, root)
    kept = {}
    res = run.run(_args(1), cell, jax.devices(), entry_setup=setup,
                  kept=kept)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert list(m) == ["toy_advance_ms", "toy_traces_per_call"]
    assert m["toy_advance_ms"]["value"] > 0
    assert m["toy_traces_per_call"]["value"] >= 0
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    # bench/regions.py reports the same reduction of the same run
    rep = regions.report(res, kept)
    assert rep["region_ms"]["advance"] == m["toy_advance_ms"]["value"]
    assert rep["busy_ms"] == pytest.approx(sum(rep["region_ms"].values()))
    assert rep["window_calls"] == res["attempted"]
    assert rep["checks"] == res["checks"]


def test_judge_raises_on_a_number_without_a_limit_and_a_limit_without_one(
        root):
    limits = loader.cell(CELL, root).limits
    ok, checks = compare.judge({"sign_mismatch": 0, "value_gap": 0.0},
                               limits)
    assert ok and list(checks) == list(limits)
    with pytest.raises(KeyError, match="without a limit: \\['flows'\\]"):
        compare.judge({"value_gap": 0.0, "sign_mismatch": 0, "flows": 1},
                      limits)
    with pytest.raises(KeyError, match="without a number: \\['sign_"):
        compare.judge({"value_gap": 0.0}, limits)

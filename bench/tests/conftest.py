"""Self-tests of the benchmark harness: CPU, tiny sizes, run by path
(``python -m pytest bench/tests``)."""
import dataclasses
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def _context(entry: str, trace: bool, root: pathlib.Path = ROOT) -> dict:
    """What the harness hands a reader after a window of two calls of a
    cell driven by ``entry``, each with the counts of
    ``bench/tests/counts/<entry>.json``; with ``trace``, a traced call
    with its trace, device ms per region, program spans and the window's
    jit counts, each positive."""
    from bench import regions, tracing
    from bench.window import Call
    from repro.analysis import retrace
    counts = json.loads(
        (root / "bench" / "tests" / "counts" / f"{entry}.json").read_text())
    ctx = {"build_s": 1.0, "compile_s": 2.0,
           "calls": [Call(0.0, 2.0, counts), Call(2.0, 4.0, counts)],
           "trace": None, "traced": None, "regions": None, "spans": None,
           "jit_window": None}
    if trace:
        ctx.update(
            trace={"busy_s": 3.0, "window_s": 4.0}, traced=counts,
            regions={r: 0.5 for r in regions.REGIONS + (regions.OTHER,)},
            spans={s: 1.0 for s in tracing.PROGRAM_SPANS},
            jit_window={k: {"count": 4, "seconds": 1.0, "by_fun": {}}
                        for k in retrace.JIT_EVENTS.values()})
    return ctx


@pytest.fixture
def reader_context():
    return _context


@pytest.fixture(scope="session")
def farm_cell():
    """The farm cell at a CPU's size: 128 servers, 1,500 jobs."""
    from bench import loader
    c = loader.cell("farm20k_c6.websrv_j100k")
    return dataclasses.replace(
        c, config=dict(c.config, sim=dict(c.config["sim"], n_servers=128)),
        traffic=dict(c.traffic, jobs=1500, warm_events=640,
                     call_events=240))


@pytest.fixture(scope="session")
def sweep_cell():
    """The sweep cell at a CPU's size: one seed per point, 40 jobs."""
    from bench import loader
    c = loader.cell("caseB_20srv.fig5_websrv")
    return dataclasses.replace(
        c, config=dict(c.config, seeds_per_point=1, jobs_per_replica=40))

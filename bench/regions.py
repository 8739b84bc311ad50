"""Device time per engine region, and the program's host spans, from one
profiled call.

The engine runs each phase of a step under a ``jax.named_scope``
(``core/engine.py``); the names reach the compiled program's
``op_name`` metadata, though not the TPU's op events, which carry only
their times.  So each top-level HLO instruction of the timed program's
compiled text, fusions included, is mapped to the innermost region its
own ``op_name`` names (``other`` where it names none), and the leaf time
of its device ops is summed per region.  The busy time in fusions whose
fused instructions span more than one region is reported beside, so that
readers know how blurred the attribution is.  ``run_replicas`` writes
the host spans of its stages (``run_replicas.trace``, ``.lower``,
``.compile``, ``.execute``) into the same trace, on the profiler's
clock.

Run as a script on a TPU, it measures one cell region by region: set-up
as the harness does it, with the program's jit counters on
(``analysis.retrace.watch``), a window of calls, one profiled call, and
the program's HLO text; one JSON line on stdout, and with ``--out`` the
same line, the trace and the HLO text in that directory:

    python bench/regions.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import trace_reduce  # noqa: E402

# the engine's regions, in step order (core/engine.py)
REGIONS = ("next_event", "advance", "completions", "admission",
           "drain_start", "power", "flows", "thermal", "trace_flush",
           "telemetry")
OTHER = "other"
# the host spans run_replicas writes around its stages
PROGRAM_SPANS = ("run_replicas.trace", "run_replicas.lower",
                 "run_replicas.compile", "run_replicas.execute")
RETRACE_SPANS = PROGRAM_SPANS[:3]

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FUSION_CALLS = re.compile(r"\bfusion\(.*?\bcalls=%?([\w.\-]+)")
_WRAPPED = re.compile(r"[\w\-]+\((.*)\)")


def region_of(op_name: str) -> str:
    """The innermost region named in an ``op_name`` path (transform
    wrappers such as ``vmap(...)`` looked through), else ``other``."""
    found = OTHER
    for comp in op_name.split("/"):
        while (m := _WRAPPED.fullmatch(comp)):
            comp = m.group(1)
        if comp in REGIONS:
            found = comp
    return found


def hlo_regions(text: str) -> tuple:
    """(module name, {instruction: (region, mixed)}) for every
    instruction outside a fused computation of a compiled module's HLO
    text: the instructions that run as device ops.  A fusion takes the
    region of its own ``op_name``, or, where it has none, the one region
    its fused instructions name.  ``mixed`` is True for a fusion whose
    fused instructions name more than one region."""
    module, comps, current = "", {}, None
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
        elif line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = line.split()[1] if line.startswith("ENTRY ") \
                else line.split()[0]
            current = comps.setdefault(name.lstrip("%"), [])
        elif current is not None and " = " in line:
            head, rest = line.split(" = ", 1)
            instr = head.split()[-1].lstrip("%")
            op = _OP_NAME.search(rest)
            fused = _FUSION_CALLS.search(rest)
            current.append((instr, region_of(op.group(1)) if op else None,
                            fused.group(1) if fused else None))
    fused_comps = {f for body in comps.values() for _, _, f in body if f}

    def regions_in(comp: str) -> set:
        out = set()
        for _, region, fused in comps.get(comp, ()):
            if region is not None:
                out.add(region)
            if fused:
                out |= regions_in(fused)
        return out

    table = {}
    for comp, body in comps.items():
        if comp in fused_comps:
            continue
        for instr, region, fused in body:
            inner = regions_in(fused) if fused else set()
            if region is None and len(inner) == 1:
                region, = inner         # a fusion XLA made, unnamed
            table[instr] = (region or OTHER, len(inner) > 1)
    return module, table


def reduce(ops: dict, table: dict, window: tuple, top: int = 10) -> dict:
    """Leaf time of each device's ops inside ``window`` (start, end ns),
    by region, averaged over devices (seconds): {"seconds": {region: s},
    "busy_s" (union of the leaves), "leaf_s" (their sum), "mixed_s",
    "ops": [[op, s, region, mixed]] (device 0's top ops)}."""
    lo, hi = window
    per, mixed, busy, top_ops = collections.Counter(), 0, 0, []
    for i, events in enumerate(ops.values()):
        leaf = [ev for ev in trace_reduce.leaves(events)
                if ev[1] < hi and ev[1] + ev[2] > lo]
        by_op = collections.Counter()
        for name, s, d in leaf:
            t = min(s + d, hi) - max(s, lo)
            op = trace_reduce.op_name(name)
            region, mix = table.get(op, (OTHER, False))
            per[region] += t
            mixed += t if mix else 0
            by_op[op] += t
        busy += sum(e - s for s, e in trace_reduce.union(
            [(max(s, lo), min(s + d, hi)) for _, s, d in leaf]))
        if i == 0:
            top_ops = [[op, t * 1e-9, *table.get(op, (OTHER, False))]
                       for op, t in by_op.most_common(top)]
    n = max(len(ops), 1)
    return {"seconds": {r: per[r] / n * 1e-9 for r in REGIONS + (OTHER,)},
            "busy_s": busy / n * 1e-9,
            "leaf_s": sum(per.values()) / n * 1e-9,
            "mixed_s": mixed / n * 1e-9, "ops": top_ops}


def extract(path) -> dict:
    """From a profiler trace: {"ops": {plane: [(op, start_ns, dur_ns)]}
    (each device's "XLA Ops" line), "modules": [(module, start_ns,
    dur_ns)] (each device's "XLA Modules" line: ``jit_run(<id>)``) and
    "program": [(span, start_ns, dur_ns)] (``PROGRAM_SPANS``)}.  TPU op
    events carry no ``op_name``, only their times, so the region of an
    op comes from the program's HLO text (:func:`hlo_regions`)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = {"ops": {}, "modules": [], "program": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.duration_ns)
                       for ev in line.events]
                if line.name == "XLA Ops":
                    out["ops"][plane.name] = evs
                elif line.name == "XLA Modules":
                    out["modules"] += evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["program"] += [(ev.name, ev.start_ns, ev.duration_ns)
                                   for ev in line.events
                                   if ev.name in PROGRAM_SPANS]
    out["program"].sort(key=lambda p: p[1])
    return out


def modules_in(modules, window: tuple) -> dict:
    """Seconds of each program module (its name without the id) that ran
    inside ``window`` (start, end ns)."""
    lo, hi = window
    out = collections.Counter()
    for name, s, d in modules:
        if s < hi and s + d > lo:
            out[name.split("(")[0]] += (min(s + d, hi) - max(s, lo)) * 1e-9
    return dict(out)


def span_seconds(spans, window: tuple) -> dict:
    """Seconds of each program span inside ``window`` (start, end ns)."""
    lo, hi = window
    out = collections.Counter()
    for name, s, d in spans:
        if s >= lo and s + d <= hi:
            out[name] += d * 1e-9
    return {name: out[name] for name in PROGRAM_SPANS if name in out}


def _program_text(entry: str, runner) -> str:
    """The compiled HLO text of the program the entry's calls run."""
    if entry == "farm":
        from repro.core import engine
        compiled = engine.run.lower(runner.s_w, runner.cfg,
                                    runner.tc).compile()
    else:
        from repro.core import montecarlo
        compiled = montecarlo.replica_jit(runner.cfg, runner.tc).lower(
            runner.state_b).compile()
    return compiled.as_text()


def measure(cell, seed: int, seconds: float, trace_dir,
            save_dir=None) -> dict:
    """Set-up with the jit counters on, a window, one profiled call and
    its reduction by region and by host span; with ``save_dir``, the
    trace and the HLO text are copied there."""
    from bench import loader, program, tracing, window  # noqa: F401
    # (program puts the repo's src/ on the path)
    from repro.analysis import retrace
    retrace.watch()
    entry = cell.traffic["entry"]
    runner = loader.module("entries", entry).setup(cell, seed)
    setup_s = time.perf_counter() - T0
    j0 = retrace.jit_counts()
    calls, last = window.measure(runner.call, runner.counts, seconds)
    j1 = retrace.jit_counts()
    del last
    out, path = tracing.capture(runner.call, trace_dir)
    j2 = retrace.jit_counts()
    counts = runner.counts(out)
    del out
    text = _program_text(entry, runner)
    module, table = hlo_regions(text)
    if save_dir is not None:
        shutil.copy(path, save_dir / f"{cell.name}.{seed}.xplane.pb")
        (save_dir / f"{cell.name}.{seed}.hlo.txt").write_text(text)

    harness = tracing.extract(path)
    (lo, hi), = [(s, s + d) for n, s, d in harness["host"] if n == "call"]
    tr = extract(path)
    by_region = reduce(tr["ops"], table, (lo, hi))
    idle = trace_reduce.reduce(harness)
    spans = span_seconds(tr["program"], (lo, hi))
    steps = counts["steps"]
    ms = {r: s * 1e3 / steps for r, s in by_region["seconds"].items()}
    dispatch = sum(s for n, s in idle["gaps"] if n == "dispatch")
    retrace_s = sum(spans.get(n, 0.0) for n in RETRACE_SPANS)
    window_jit = retrace.jit_delta(j0, j1)
    return {
        "cell": cell.name, "seed": seed, "setup_s": setup_s,
        "steps": steps, "module": module,
        "region_ms": ms,
        "busy_ms": by_region["busy_s"] * 1e3 / steps,
        "leaf_ms": by_region["leaf_s"] * 1e3 / steps,
        "mixed_share": by_region["mixed_s"] / max(by_region["leaf_s"],
                                                  1e-30),
        "ops": by_region["ops"],
        "program_spans": spans, "retrace_s": retrace_s,
        "dispatch_gap_s": dispatch,
        "window_s": idle["window_s"], "busy_s": idle["busy_s"],
        "gaps": idle["gaps"][:3],
        "window_calls": len(calls),
        "median_call_s": statistics.median(c.end - c.start for c in calls),
        "traces_per_call": window_jit["trace"]["count"] / len(calls),
        "jit_window": window_jit, "jit_traced": retrace.jit_delta(j1, j2),
        # the modules that ran in the call: only the one whose HLO text
        # mapped the ops
        "modules": modules_in(tr["modules"], (lo, hi)),
    }


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", type=pathlib.Path,
                    help="a directory for the result, the trace and the "
                         "HLO text")
    args = ap.parse_args(argv)
    from bench import loader, run
    cell = loader.cell(args.workload)
    if run.device_check(cell.chips) is None:
        return 3
    run.enable_compile_cache()
    if args.out is not None:
        args.out.mkdir(parents=True, exist_ok=True)
    result = measure(cell, args.seed, args.seconds, run.TRACE_DIR,
                     args.out)
    line = json.dumps(result)
    if args.out is not None:
        (args.out / f"{args.workload}.{args.seed}.json").write_text(
            line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())

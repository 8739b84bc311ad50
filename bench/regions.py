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
clock.  The compiled text comes from the entry's runner
(``program_text``); :func:`per_call` is the one reduction of a traced
call, for ``bench/run.py --trace 1`` and so for this script.  It refuses
a call that ran another module than the text's, or device ops that the
text does not hold: such time would land in no region, or in the wrong
one, without a sign.

Run as a script on a TPU, it runs one cell as ``bench/run.py --trace 1``
does and reports the traced call region by region, with the regions
that have no metric, the busy, leaf and mixed time, the top ops, the
program spans against the dispatch gap and the window's jit counts; one
JSON line on stdout, and with ``--out`` the same line, the trace and the
HLO text in that directory:

    python bench/regions.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import collections
import json
import os
import pathlib
import re
import shutil
import statistics
import sys

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import trace_reduce, tracing  # noqa: E402

# the engine's regions, in step order (core/engine.py)
REGIONS = ("next_event", "advance", "completions", "admission",
           "drain_start", "power", "flows", "thermal", "trace_flush",
           "telemetry")
OTHER = "other"
# the program spans of a replica sweep's per-call re-trace
RETRACE_SPANS = tracing.PROGRAM_SPANS[:3]

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
    "ops": [[op, s, region, mixed]] (device 0's top ops), "unmapped":
    {op: s} (ops that ``table`` does not hold, in no region)}."""
    lo, hi = window
    per, mixed, busy, top_ops = collections.Counter(), 0, 0, []
    unmapped = collections.Counter()
    for i, events in enumerate(ops.values()):
        leaf = [ev for ev in trace_reduce.leaves(events)
                if ev[1] < hi and ev[1] + ev[2] > lo]
        by_op = collections.Counter()
        for name, s, d in leaf:
            t = min(s + d, hi) - max(s, lo)
            op = trace_reduce.op_name(name)
            if op not in table:
                unmapped[op] += t
                continue
            region, mix = table[op]
            per[region] += t
            mixed += t if mix else 0
            by_op[op] += t
        busy += sum(e - s for s, e in trace_reduce.union(
            [(max(s, lo), min(s + d, hi)) for _, s, d in leaf]))
        if i == 0:
            top_ops = [[op, t * 1e-9, *table[op]]
                       for op, t in by_op.most_common(top)]
    n = max(len(ops), 1)
    return {"seconds": {r: per[r] / n * 1e-9 for r in REGIONS + (OTHER,)},
            "busy_s": busy / n * 1e-9,
            "leaf_s": sum(per.values()) / n * 1e-9,
            "mixed_s": mixed / n * 1e-9, "ops": top_ops,
            "unmapped": {op: t / n * 1e-9 for op, t in unmapped.items()}}


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
    return {name: out[name] for name in tracing.PROGRAM_SPANS
            if name in out}


def per_call(runner, trace: dict, counts: dict) -> dict:
    """The traced call region by region: the device ops of ``trace``
    (``tracing.extract``) inside the harness's ``call`` span, mapped to
    regions through ``runner.program_text()``, and the program spans
    inside that span.  {"module", "region_ms": {region: ms per loop
    iteration}, ``other`` included, "busy_ms", "leaf_ms", "mixed_ms" (per
    iteration, ``counts["steps"]`` of the traced call), "spans": {span:
    s}, "ops": device 0's top ops [[op, s, region, mixed]], "call":
    (start, end ns)}.  Raises ValueError where the call ran a module
    other than the text's, or none, or an op the text does not hold."""
    module, table = hlo_regions(runner.program_text())
    (lo, hi), = [(s, s + d) for n, s, d in trace["host"] if n == "call"]
    ran = modules_in(trace["modules"], (lo, hi))
    if set(ran) != {module}:
        raise ValueError(f"the traced call ran the modules {ran}, not "
                         f"{module!r} alone, whose HLO text maps its ops")
    r = reduce(trace["device"], table, (lo, hi))
    if r["unmapped"]:
        worst = sorted(r["unmapped"].items(), key=lambda o: -o[1])[:5]
        raise ValueError(f"{sum(r['unmapped'].values())!r} s of device "
                         f"ops missing from the HLO text of {module!r}, "
                         f"e.g. {worst}")
    per_ms = 1e3 / counts["steps"]
    return {"module": module,
            "region_ms": {k: s * per_ms for k, s in r["seconds"].items()},
            "busy_ms": r["busy_s"] * per_ms, "leaf_ms": r["leaf_s"] * per_ms,
            "mixed_ms": r["mixed_s"] * per_ms,
            "spans": span_seconds(trace["program"], (lo, hi)),
            "ops": r["ops"], "call": (lo, hi)}


def report(result: dict, kept: dict) -> dict:
    """What the script adds to a ``bench/run.py --trace 1`` result
    (``result``; ``kept`` from ``run.run``): every region, the busy,
    leaf and mixed time, the program spans against the dispatch gap."""
    by, reduced, calls = kept["regions"], kept["trace"], kept["calls"]
    jit = kept["jit_window"]
    return {
        "cell": kept["cell"], "seed": kept["seed"],
        "correct": result["correct"], "setup_s": kept["setup_s"],
        "steps": kept["traced"]["steps"], "module": by["module"],
        "region_ms": by["region_ms"], "busy_ms": by["busy_ms"],
        "leaf_ms": by["leaf_ms"],
        "mixed_share": by["mixed_ms"] / max(by["leaf_ms"], 1e-30),
        "ops": by["ops"], "program_spans": by["spans"],
        "retrace_s": sum(by["spans"].get(n, 0.0) for n in RETRACE_SPANS),
        "dispatch_gap_s": sum(s for n, s in reduced["gaps"]
                              if n == "dispatch"),
        "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
        "gaps": reduced["gaps"][:3], "window_calls": len(calls),
        "median_call_s": statistics.median(c.end - c.start for c in calls),
        "traces_per_call": jit["trace"]["count"] / len(calls),
        "jit_window": jit, "metrics": result["metrics"],
        "checks": result["checks"],
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
    devices = run.device_check(cell.chips)
    if devices is None:
        return 3
    run.enable_compile_cache()
    kept = {}
    result = run.run(argparse.Namespace(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=1), cell, devices, kept=kept)
    line = json.dumps(report(result, kept))
    if args.out is not None:
        args.out.mkdir(parents=True, exist_ok=True)
        stem = args.out / f"{args.workload}.{args.seed}"
        shutil.copy(kept["trace_path"], f"{stem}.xplane.pb")
        pathlib.Path(f"{stem}.hlo.txt").write_text(
            kept["runner"].program_text())
        pathlib.Path(f"{stem}.json").write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())

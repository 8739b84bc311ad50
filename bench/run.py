"""Run one benchmark cell once and print its result as one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``<config>.<traffic>`` in ``BENCHMARK.json``) names its
configuration and traffic mix; its traffic names the entry of the program
that the window drives (``bench/entries/<entry>.py``).  Set-up builds the
inputs from ``--seed``, compiles and warms up; the window then calls the
entry back to back for ``--seconds`` (``bench/window.py``).  Each
end-to-end metric but ``setup_s`` is the cell's rate: the events of every
call of the window per second of it.  With ``--trace 1`` the program's
jit counters are on (``analysis.retrace.watch``), one more call runs
under the profiler, and the cell's per-layer metrics are reported
instead of its end-to-end ones: each reader (``bench/metrics/<name>.py``)
reads the set-up's times, the window's calls and jit counts
(``jit_window``), and the traced call's counts (``traced``), trace
reduction (``trace``), device ms per engine region and loop iteration
(``regions``) and program spans (``spans``).  Once the window has
closed, the program's outputs are compared with the plain reference
(``bench/compare.py``); each number compared is printed beside its
limit, last on standard error and last in the result line.

Needs a TPU with as many chips as the cell asks for: anywhere else it
exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the compile cache and the trace stay inside the checkout, at fixed paths
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_check(chips: int):
    """The devices to report, or None (with the reason on stderr) when
    JAX finds no TPU or fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None
    return devices[:chips]


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def memory_peak(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run(args, cell, devices, entry_setup=None, kept=None) -> dict:
    """Set-up, window, optional traced call and the check; the result
    object.  ``entry_setup`` replaces the entry's set-up (tests); a
    traced run fills ``kept``, where given, with what its readers read,
    the runner and the trace's path (``bench/regions.py``)."""
    from bench import compare, loader, regions, tracing, trace_reduce, \
        window
    if args.trace:
        from bench import program  # noqa: F401  (the program's src/)
        from repro.analysis import retrace
        retrace.watch()
    entry = loader.entry(cell)
    runner = (entry_setup or entry.setup)(cell, args.seed)
    setup_s = time.perf_counter() - T0

    if args.trace:
        j0 = retrace.jit_counts()
    calls, last = window.measure(runner.call, runner.counts, args.seconds)
    if args.trace:
        jit_window = retrace.jit_delta(j0, retrace.jit_counts())
    failed = sum(runner.failed(c.counts) for c in calls)
    if runner.compile_s is None:         # first call compiled, in set-up
        runner.compile_s = runner.first_call_s - (calls[0].end
                                                  - calls[0].start)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak(devices)}
    outputs = runner.outputs(last)
    del last

    reduced = None
    if args.trace:
        out, path = tracing.capture(runner.call, TRACE_DIR)
        traced = runner.counts(out)
        failed += runner.failed(traced)
        del out
        trace = tracing.extract(path)
        reduced = trace_reduce.reduce(trace)
        by_region = regions.per_call(runner, trace, traced)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])

    numbers = runner.check(outputs)
    ok, checks = compare.judge(numbers, cell.limits)

    if args.trace:
        ctx = {"build_s": runner.build_s, "compile_s": runner.compile_s,
               "calls": calls, "trace": reduced, "traced": traced,
               "regions": by_region["region_ms"],
               "spans": by_region["spans"], "jit_window": jit_window}
        if kept is not None:
            kept.update(ctx, regions=by_region, runner=runner,
                        trace_path=path, cell=cell.name, seed=args.seed,
                        setup_s=setup_s)
        metrics = {}
        for m in cell.per_layer:
            value = loader.metric(m, cell.root).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        # the rate under the cell's own name for it, so that each kind of
        # cell has a bound of its own
        rate = window.rate(calls)
        metrics = {m["name"]: {"value": setup_s if m["name"] == "setup_s"
                               else rate, "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": bool(ok and not failed), "attempted": len(calls),
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["ops"],
                               "idle_gaps": reduced["gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(ROOT))
    from bench import loader
    cell = loader.cell(args.workload)
    devices = device_check(cell.chips)
    if devices is None:
        return 3
    enable_compile_cache()
    result = run(args, cell, devices)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())

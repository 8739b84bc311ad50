"""The traced call: one more call of the entry under the JAX profiler,
with host spans around its dispatch and its wait, and the trace read back
as plain intervals for ``bench/trace_reduce.py`` and ``bench/regions.py``."""
from __future__ import annotations

import pathlib
import shutil

import jax

# host spans the harness writes into the profiler's trace
SPANS = ("call", "dispatch", "wait")
# host spans the program writes around the stages of a replica sweep's
# call (``montecarlo.run_replicas``)
PROGRAM_SPANS = ("run_replicas.trace", "run_replicas.lower",
                 "run_replicas.compile", "run_replicas.execute")


def capture(call, trace_dir: pathlib.Path):
    """Run ``call`` once under the profiler; (its output, xplane path)."""
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("call"):
            with jax.profiler.TraceAnnotation("dispatch"):
                out = call()
            with jax.profiler.TraceAnnotation("wait"):
                out = jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    paths = sorted(trace_dir.glob("**/*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return out, paths[-1]


def extract(path: pathlib.Path) -> dict:
    """{"device": {plane: [(op, start_ns, dur_ns)]}, "modules":
    [(module, start_ns, dur_ns)], "host": [(span, start_ns, dur_ns)],
    "program": [...]}: the events of each device's "XLA Ops" line and
    "XLA Modules" line (``jit_run(<id>)``), the harness's host spans
    (``SPANS``) and the program's (``PROGRAM_SPANS``, in time order).
    TPU op events carry no ``op_name``, only their times."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = {"device": {}, "modules": [], "host": [], "program": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.duration_ns)
                       for ev in line.events]
                if line.name == "XLA Ops":
                    out["device"][plane.name] = evs
                elif line.name == "XLA Modules":
                    out["modules"] += evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    kind = "host" if ev.name in SPANS else \
                        "program" if ev.name in PROGRAM_SPANS else None
                    if kind:
                        out[kind].append((ev.name, ev.start_ns,
                                          ev.duration_ns))
    out["program"].sort(key=lambda p: p[1])
    return out

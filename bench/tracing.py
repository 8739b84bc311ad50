"""The traced call: one more call of the entry under the JAX profiler,
with host spans around its dispatch and its wait, and the trace read back
as plain intervals for ``bench/trace_reduce.py``."""
from __future__ import annotations

import pathlib
import shutil

import jax

# host spans the harness writes into the profiler's trace
SPANS = ("call", "dispatch", "wait")


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
    """{"device": {plane: [(op, start_ns, dur_ns)]}, "host":
    [(span, start_ns, dur_ns)]}: the events of each device's "XLA Ops"
    line, and the harness's host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device[plane.name] = [
                        (ev.name, ev.start_ns, ev.duration_ns)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.duration_ns)
                         for ev in line.events if ev.name in SPANS]
    return {"device": device, "host": host}

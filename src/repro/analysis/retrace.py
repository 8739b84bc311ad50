"""Retrace sentinel: a second trace for an identical program key is a bug.

PR 7 made ``farm.simulate`` / ``shard_sim.run_sharded`` reuse compiled
programs across calls (jit cache keyed on ``(cfg, state layout)``, an
``lru_cache`` over ``(cfg, mesh, axis, layout, specs)`` for the sharded
loop).  A silent cache miss — e.g. a config object that stops hashing
stably, or a state layout that drifts between calls — costs a full retrace
+ recompile per call and the benchmarks only see it as noise.

The engine's traced entry points call :func:`note_trace` at *trace time*
(a Python side effect inside the jitted body runs only when XLA actually
retraces).  :func:`retrace_guard` scopes the bookkeeping: run the same
simulation twice inside the guard and any key traced more than once is a
named violation.

:func:`watch` counts the jit work of the whole process instead: every
top-level jaxpr trace, lowering and backend compile, and every read from
the persistent compile cache, by ``jax.monitoring`` event and by the
traced function's name.  :func:`jit_counts` snapshots the tallies and
:func:`jit_delta` subtracts two snapshots.  Nothing is registered until
:func:`watch` is called, so a process that never calls it pays nothing.
"""

from __future__ import annotations

import collections
import contextlib

from jax import monitoring

# (tag, key) -> number of traces observed. Module-level so engine/shard_sim
# can call note_trace without importing analysis machinery at trace time.
_TRACE_COUNTS: collections.Counter = collections.Counter()
_ENABLED = False


def note_trace(tag: str, key) -> None:
    """Record one trace of ``tag`` for program ``key``.  Call from inside
    a jitted body (runs only when the tracer actually runs)."""
    if _ENABLED:
        _TRACE_COUNTS[(tag, _freeze(key))] += 1


def _freeze(key):
    if isinstance(key, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in key.items()))
    if isinstance(key, (list, tuple)):
        return tuple(_freeze(v) for v in key)
    return key


def retraced_keys() -> list:
    """Keys traced more than once since the guard was entered."""
    return [
        {"tag": tag, "key": repr(key), "traces": n}
        for (tag, key), n in sorted(_TRACE_COUNTS.items(), key=lambda kv: repr(kv[0]))
        if n > 1
    ]


def trace_events() -> list:
    return [
        {"tag": tag, "key": repr(key), "traces": n}
        for (tag, key), n in sorted(_TRACE_COUNTS.items(), key=lambda kv: repr(kv[0]))
    ]


@contextlib.contextmanager
def retrace_guard():
    """Enable trace counting within the block; yields a callable that
    returns the retraced keys observed so far."""
    global _ENABLED
    _TRACE_COUNTS.clear()
    _ENABLED = True
    try:
        yield retraced_keys
    finally:
        _ENABLED = False


# jax.monitoring events of a jit program's host work, by short name
JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
    "/jax/compilation_cache/cache_hits": "cache_hit",
}
# kind -> fun_name -> [count, seconds]
_JIT: dict = {kind: {} for kind in JIT_EVENTS.values()}
# kind -> spans open now: jax reports a scalar when a span opens and its
# duration when it closes, so a trace nested in another (an inner jit
# traced while its caller traces) is counted inside the outer one only
_OPEN: collections.Counter = collections.Counter()
_WATCHING = False


def watch() -> None:
    """Start counting jit work in this process (idempotent)."""
    global _WATCHING
    if _WATCHING:
        return
    monitoring.register_scalar_listener(_on_open)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _WATCHING = True


def _on_open(event, value, **kwargs) -> None:
    kind = JIT_EVENTS.get(event)
    if kind:
        _OPEN[kind] += 1


def _on_duration(event, duration_secs, fun_name="", **kwargs) -> None:
    kind = JIT_EVENTS.get(event)
    if kind is None:
        return
    if _OPEN[kind]:
        _OPEN[kind] -= 1
        if _OPEN[kind]:
            return                      # nested in an open span of its kind
    slot = _JIT[kind].setdefault(fun_name, [0, 0.0])
    slot[0] += 1
    slot[1] += duration_secs


def _on_event(event, fun_name="", **kwargs) -> None:
    kind = JIT_EVENTS.get(event)
    if kind:
        _JIT[kind].setdefault(fun_name, [0, 0.0])[0] += 1


def jit_counts() -> dict:
    """``{kind: {"count", "seconds", "by_fun": {fun_name: count}}}`` for
    kind in trace, lower, compile, cache_read, cache_hit: what
    :func:`watch` has counted so far (zeros before it is called)."""
    return {kind: {"count": sum(c for c, _ in funs.values()),
                   "seconds": sum((s for _, s in funs.values()), 0.0),
                   "by_fun": {f: c for f, (c, _) in sorted(funs.items())}}
            for kind, funs in _JIT.items()}


def jit_delta(before: dict, after: dict) -> dict:
    """``after - before`` of two :func:`jit_counts` snapshots."""
    out = {}
    for kind, a in after.items():
        b = before[kind]
        by_fun = {f: n - b["by_fun"].get(f, 0)
                  for f, n in a["by_fun"].items()}
        out[kind] = {"count": a["count"] - b["count"],
                     "seconds": a["seconds"] - b["seconds"],
                     "by_fun": {f: n for f, n in by_fun.items() if n}}
    return out

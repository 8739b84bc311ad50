"""Engine regions: every phase of a step runs under a named scope that
reaches the compiled program's ``op_name`` metadata, where a profile's
device time is attributed to it.  A refactor that drops a scope fails
here."""
import dataclasses
import re

import numpy as np
import pytest

from repro.core import engine, topology
from repro.core.jobs import build_jobs, dag_chain, dag_single
from repro.core.types import (SchedPolicy, SimConfig, SleepPolicy,
                              TelemetryConfig, ThermalConfig, TraceConfig)

STEP = ("next_event", "advance", "completions", "admission", "drain_start",
        "power", "telemetry")

BASE = SimConfig(n_servers=8, n_cores=2, max_jobs=16, tasks_per_job=1,
                 local_q=16, sleep_policy=SleepPolicy.SINGLE_TIMER,
                 telemetry=TelemetryConfig(enabled=True), max_events=400)
NET = SimConfig(n_servers=6, n_cores=2, max_jobs=16, tasks_per_job=2,
                max_children=2, max_flows=16, local_q=16,
                sched_policy=SchedPolicy.ROUND_ROBIN,
                sleep_policy=SleepPolicy.ALWAYS_ON, has_network=True,
                comm_model=0, max_events=400)


def _scopes(cfg, specs, topo=None) -> set:
    """Every path component of every ``op_name`` in ``engine.run``'s
    compiled HLO text."""
    arr = np.linspace(0.0, 1.0, len(specs))
    state, tc = engine.init_state(cfg, build_jobs(cfg, arr, specs), topo)
    text = engine.run.lower(state, cfg, tc).compile().as_text()
    return {c for name in re.findall(r'op_name="([^"]*)"', text)
            for c in name.split("/")}


@pytest.mark.parametrize("case,want", [
    ("farm", STEP),
    ("thermal_trace", STEP + ("thermal", "trace_flush")),
    ("network", ("advance", "completions", "flows")),
])
def test_engine_run_names_every_region(case, want):
    if case == "network":
        specs = [dag_chain([0.02, 0.02], edge_bytes=4e6)] * 8
        scopes = _scopes(NET, specs, topology.star(NET.n_servers, 1e8))
    else:
        cfg = BASE if case == "farm" else dataclasses.replace(
            BASE, thermal=ThermalConfig(enabled=True, t_throttle=40.0),
            trace=TraceConfig(enabled=True))
        scopes = _scopes(cfg, [dag_single(0.1)] * 8)
    assert set(want) <= scopes, sorted(set(want) - scopes)
    # the audit scopes simlint keys on are still there
    assert {"cheap_core", "full_step"} <= scopes

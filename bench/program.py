"""The benchmark's only door into the program under test: a configuration
file's ``sim`` block made into the program's ``SimConfig``, and the
program's state read back as the plain arrays ``bench/compare.py``
compares.  Nothing here computes a result of its own."""
from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import types as T  # noqa: E402

_ENUMS = {"sched_policy": T.SchedPolicy, "sleep_policy": T.SleepPolicy,
          "sleep_state": T.SrvState}
_GROUPS = {"server_power": T.ServerPowerProfile,
           "telemetry": T.TelemetryConfig, "thermal": T.ThermalConfig,
           "trace": T.TraceConfig}


def sim_config(sim: dict, **overrides) -> T.SimConfig:
    """``SimConfig`` from a configuration's ``sim`` block (enum values by
    name, nested groups as dicts, ``time_dtype`` by dtype name)."""
    kw = {}
    for key, val in {**sim, **overrides}.items():
        if key in _ENUMS:
            val = getattr(_ENUMS[key], val)
        elif key in _GROUPS:
            val = _GROUPS[key](**val)
        elif key == "time_dtype":
            val = jnp.dtype(val).type
        kw[key] = val
    return T.SimConfig(**kw)


def outputs(state, n_jobs: int, replica=None) -> dict:
    """The state's compared leaves as numpy arrays (one replica of a
    batch when ``replica`` is given)."""
    pick = (lambda x: np.asarray(x)) if replica is None \
        else (lambda x: np.asarray(x[replica]))
    jobs, farm, telem = state.jobs, state.farm, state.telem
    return {
        "t": float(pick(state.t)),
        "events": int(pick(state.events)),
        "arr_ptr": int(pick(jobs.arr_ptr)),
        "server": pick(jobs.server)[:n_jobs],
        "job_finish": pick(jobs.job_finish)[:n_jobs].astype(np.float64),
        "energy": pick(farm.energy).astype(np.float64),
        "residency": pick(farm.residency).astype(np.float64),
        "wake_count": pick(farm.wake_count),
        "job_hist": pick(telem.job_hist).astype(np.float64),
        "task_hist": pick(telem.task_hist).astype(np.float64),
        "tail_viol": int(pick(telem.tail_viol)),
        "dropped": int(pick(farm.dropped)),
        "win": pick(telem.win).astype(np.float64),
    }

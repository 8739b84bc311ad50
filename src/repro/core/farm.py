"""High-level simulation entry points + result summarization."""
from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import engine, jobs as jobs_mod, telemetry as telemetry_mod
from . import traceio
from .types import INF, SimConfig, SimState


@dataclasses.dataclass
class RunInfo:
    """Run provenance: host wall clock + the exact config that produced
    the result, for BENCH/CI artifacts and trace headers."""
    wall_s: float                   # wall time of the engine run, less
                                    # its jit work with profile=True
    steps: int                      # while-loop iterations (macro-steps)
    events: int                     # events retired
    events_per_s: float             # events / wall_s
    backend: str                    # jax.default_backend()
    config: dict                    # recursive SimConfig dump
    jit_compile_s: float = float("nan")  # only with simulate(profile=True)
    # execution-mesh provenance: how the state was laid out, NOT part of
    # the scenario — config_digest deliberately excludes it so the same
    # scenario run on 1 or 8 devices compares equal
    devices: int = 1                # devices the run executed on
    mesh_shape: tuple = ()          # e.g. (8,)
    mesh_axes: tuple = ()           # e.g. ("racks",)
    sharding: str = ""              # PartitionSpec of the server axis
    config_digest: str = ""         # sha1 over the device-count-free config


def _config_dict(obj):
    """Recursive dataclass -> plain-JSON dump (dtypes etc. stringified)."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: _config_dict(getattr(obj, f.name))
                for f in dataclasses.fields(obj)}
    if isinstance(obj, (list, tuple)):
        return [_config_dict(v) for v in obj]
    if isinstance(obj, (bool, int, float, str)) or obj is None:
        return obj
    try:
        return np.dtype(obj).name
    except TypeError:
        return str(obj)


def config_digest(cfg: SimConfig) -> str:
    """Stable sha1 of the scenario config, EXCLUDING the partition block
    (shard/device count is an execution choice, not a scenario): the same
    farm run unsharded and on an 8-device mesh digests identically."""
    d = _config_dict(cfg)
    d.pop("partition", None)
    return hashlib.sha1(
        json.dumps(d, sort_keys=True).encode()).hexdigest()


def pad_to_racks(cfg: SimConfig, n_shards: Optional[int] = None) -> SimConfig:
    """Round the farm up to whole racks (and to a rack count divisible by
    ``n_shards``) with inert filler rows.

    The returned config has ``n_servers`` padded and ``n_present`` holding
    the real server count.  Padded rows boot OFF/disabled: they draw zero
    power, emit no events, are never scheduler-eligible, and are masked
    out of the telemetry temperature/state columns — so results match the
    unpadded farm while every rack is full and the rack-major partition
    cuts cleanly.  ``n_shards`` defaults to ``cfg.partition.n_shards``."""
    K = max(n_shards if n_shards is not None else cfg.partition.n_shards, 1)
    rs = max(cfg.thermal.rack_size, 1) if cfg.thermal.enabled else 1
    block = rs * K
    real = cfg.present
    n = -(-real // block) * block
    kw = {}
    if n_shards is not None and n_shards != cfg.partition.n_shards:
        kw["partition"] = dataclasses.replace(cfg.partition,
                                              n_shards=n_shards)
    if n == cfg.n_servers and not kw:
        return cfg
    return dataclasses.replace(cfg, n_servers=n,
                               n_present=real if n > real else 0, **kw)


@dataclasses.dataclass
class SimResult:
    """Host-side summary of one simulation run."""
    sim_time: float
    events: int
    n_jobs: int
    n_finished: int
    mean_latency: float
    p50_latency: float
    p90_latency: float
    p95_latency: float
    p99_latency: float
    server_energy: float            # joules, total
    switch_energy: float
    energy_per_server: np.ndarray   # (N,)
    residency: np.ndarray           # (N, SrvState.NUM) seconds
    wake_count: np.ndarray          # (N,)
    busy_core_seconds: float
    utilization: float              # busy core-seconds / (N*C*T)
    dropped: int
    latencies: np.ndarray           # (J,) finished-job latencies (sec)
    # device-side telemetry summary (None when cfg.telemetry.enabled=False)
    telemetry: Optional[telemetry_mod.TelemetrySummary] = None
    # network: flow spawns refused by a full FlowTable (drop-resolved)
    flows_dropped: int = 0
    # thermal/carbon-cost subsystem (zeros/NaN when thermal disabled)
    cooling_energy: float = 0.0     # CRAC joules
    carbon_g: float = 0.0           # grams CO2 (IT + cooling)
    energy_cost: float = 0.0        # $ at the diurnal tariff
    peak_temp: float = float("nan")  # °C, hottest server over the run
    mean_temp: float = float("nan")  # °C, final farm mean
    throttle_seconds: float = 0.0   # summed over servers
    temps: Optional[np.ndarray] = None       # (N,) final temperatures
    peak_temps: Optional[np.ndarray] = None  # (N,) per-server peaks
    setpoints: Optional[np.ndarray] = None   # (R,) final CRAC setpoints
    # carbon-aware control plane (SchedPolicy.CARBON_AWARE)
    deferred_jobs: int = 0          # jobs released after a deferral
    deferred_seconds: float = 0.0   # summed deferral wait
    carbon_g_avoided_est: float = 0.0  # first-order grams-avoided estimate
    # flight recorder (None when cfg.trace.enabled=False)
    trace_events: Optional[np.ndarray] = None  # EVENT_DTYPE, chronological
    trace_dropped: int = 0          # records evicted by ring wrap-around
    run_info: Optional[RunInfo] = None

    @property
    def mean_power(self) -> float:
        return (self.server_energy + self.switch_energy
                + self.cooling_energy) / max(self.sim_time, 1e-12)

    @property
    def total_energy(self) -> float:
        return self.server_energy + self.switch_energy + self.cooling_energy


def summarize(state: SimState, cfg: SimConfig) -> SimResult:
    arr = np.asarray(state.jobs.arrival)
    fin = np.asarray(state.jobs.job_finish)
    ok = (fin < INF / 2) & (arr < INF / 2)
    lat = (fin - arr)[ok]
    t = float(state.t)
    # utilization is over REAL servers: inert filler rows (pad_to_racks)
    # own no cores anyone could have used
    N, C = cfg.present, cfg.n_cores
    pct = (lambda q: float(np.percentile(lat, q))) if lat.size else \
        (lambda q: float("nan"))
    thermal_kw = {}
    if cfg.thermal.enabled:
        th = state.thermal
        temps = np.asarray(th.t_srv)
        peaks = np.asarray(th.t_peak)
        thermal_kw = dict(
            cooling_energy=float(th.cool_energy),
            carbon_g=float(th.carbon_g),
            energy_cost=float(th.cost),
            peak_temp=float(peaks.max()),
            mean_temp=float(temps.mean()),
            throttle_seconds=float(np.asarray(th.throttle_seconds).sum()),
            temps=temps,
            peak_temps=peaks,
            setpoints=np.asarray(th.t_set),
            deferred_jobs=int(th.defer_count),
            deferred_seconds=float(th.defer_seconds),
            carbon_g_avoided_est=float(th.grams_avoided),
        )
    trace_kw = {}
    if cfg.trace.enabled:
        ev, n_drop = traceio.decode(state.trace, cfg)
        trace_kw = dict(trace_events=ev, trace_dropped=n_drop)
    return SimResult(
        sim_time=t,
        events=int(state.events),
        n_jobs=int((arr < INF / 2).sum()),
        n_finished=int(ok.sum()),
        mean_latency=float(lat.mean()) if lat.size else float("nan"),
        p50_latency=pct(50), p90_latency=pct(90),
        p95_latency=pct(95), p99_latency=pct(99),
        server_energy=float(np.asarray(state.farm.energy).sum()),
        switch_energy=float(np.asarray(state.net.sw_energy).sum()),
        energy_per_server=np.asarray(state.farm.energy),
        residency=np.asarray(state.farm.residency),
        wake_count=np.asarray(state.farm.wake_count),
        busy_core_seconds=float(np.asarray(
            state.farm.busy_core_seconds).sum()),
        utilization=float(np.asarray(state.farm.busy_core_seconds).sum()
                          / max(N * C * t, 1e-12)),
        dropped=int(state.farm.dropped),
        latencies=lat,
        telemetry=(telemetry_mod.summarize(state, cfg)
                   if cfg.telemetry.enabled else None),
        flows_dropped=int(state.flows.flows_dropped),
        **thermal_kw,
        **trace_kw,
    )


def simulate(cfg: SimConfig, arrivals, specs, topo=None, tau=None,
             pools=None, racks=None, profile: bool = False,
             mesh=None) -> SimResult:
    """Build the job table, run the engine to completion, summarize.

    tau   — scalar or (N,) delay-timer values (seconds; INF = never sleep)
    pools — (N,) 0/1 pool assignment (dual-timer low/high, WASP active/sleep)
    racks — (N,) rack ids for the thermal recirculation grouping (defaults
            to the topology's top-of-rack grouping, else i // rack_size)
    profile — split the run's jit work (trace, lowering, compile) out of
            its wall clock into result.run_info.jit_compile_s, from the
            counters of analysis.retrace.watch()
    mesh  — run rack-sharded on this device mesh (core/shard_sim.py);
            ``cfg.partition.n_shards > 1`` with mesh=None builds one from
            the visible devices.  Results are bit-identical either way.
    """
    jt = jobs_mod.build_jobs(cfg, np.asarray(arrivals), specs)
    state, tc = engine.init_state(cfg, jt, topo, racks)
    if tau is not None:
        tau_arr = jnp.broadcast_to(jnp.asarray(tau, cfg.time_dtype),
                                   (cfg.n_servers,))
        state = dataclasses.replace(
            state, farm=dataclasses.replace(state.farm, srv_tau=tau_arr))
    if pools is not None:
        state = dataclasses.replace(
            state, farm=dataclasses.replace(
                state.farm,
                srv_pool=jnp.asarray(pools, jnp.int32)))

    sharded = mesh is not None or cfg.partition.sharded
    if sharded:
        from . import shard_sim
        if mesh is None:
            mesh = shard_sim.make_mesh(cfg.partition.n_shards,
                                       cfg.partition.axis)

        def runner():
            return shard_sim.run_sharded(state, cfg, tc, mesh)
    else:
        def runner():
            return engine.run(state, cfg, tc)
    if profile:
        from ..analysis import retrace
        retrace.watch()
        before = retrace.jit_counts()
    t0 = time.perf_counter()
    final = jax.block_until_ready(runner())
    wall = time.perf_counter() - t0
    compile_s = float("nan")
    if profile:
        jit = retrace.jit_delta(before, retrace.jit_counts())
        compile_s = sum(jit[k]["seconds"] for k in ("trace", "lower",
                                                    "compile"))
        wall -= compile_s
    res = summarize(final, cfg)
    n_ev = int(final.events)
    if sharded:
        axis = cfg.partition.axis
        mesh_shape = tuple(int(mesh.shape[a]) for a in mesh.axis_names)
        mesh_axes = tuple(mesh.axis_names)
        devices, sharding = int(np.prod(mesh_shape)), f"P('{axis}',)"
    else:
        mesh_shape, mesh_axes = (), ()
        devices, sharding = 1, ""
    res.run_info = RunInfo(
        wall_s=wall, steps=int(final.steps), events=n_ev,
        events_per_s=n_ev / max(wall, 1e-12),
        backend=jax.default_backend(), config=_config_dict(cfg),
        jit_compile_s=compile_s,
        devices=devices, mesh_shape=mesh_shape, mesh_axes=mesh_axes,
        sharding=sharding, config_digest=config_digest(cfg))
    return res

"""Replica-parallel simulation sweeps (DESIGN.md §3.3).

The paper runs each configuration "100 times" (Fig 5).  Here replicas
(different seeds / τ values / thresholds) are a vmapped batch dimension,
and the batch is shard_mapped across every mesh axis — thousands of
simulated data centers run in parallel with collectives appearing only in
the final statistics reduction.  This is the axis that scales the simulator
to 1000+ nodes; it also hosts the fault-model Monte Carlo used to size
checkpoint cadence (Young/Daly) for the trainer.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import engine, jobs as jobs_mod, telemetry
from .types import INF, SimConfig


def batched_state(cfg: SimConfig, arrivals_b, specs, taus=None, topo=None):
    """Build R replica states.  arrivals_b (R, J); taus (R,) or (R, N);
    topo — network topology, required for has_network configs (threaded to
    engine.init_state so replica sweeps get real TopoConsts, not tc=None)."""
    R = arrivals_b.shape[0]
    tables = [jobs_mod.build_jobs(cfg, arrivals_b[i], specs)
              for i in range(R)]
    jobs = jax.tree.map(lambda *xs: jnp.stack(xs), *tables)
    state0, tc = engine.init_state(cfg, jax.tree.map(lambda a: a[0], jobs),
                                   topo)
    state_b = jax.vmap(lambda j: dataclasses.replace(state0, jobs=j))(jobs)
    if taus is not None:
        taus = jnp.asarray(taus, cfg.time_dtype)
        if taus.ndim == 1:
            taus = jnp.broadcast_to(taus[:, None], (R, cfg.n_servers))
        farm = dataclasses.replace(state_b.farm, srv_tau=taus)
        state_b = dataclasses.replace(state_b, farm=farm)
    return state_b, tc


def replica_jit(cfg: SimConfig, tc=None, mesh=None):
    """The jitted replica program that :func:`run_replicas` runs: the
    engine vmapped over the replica axis, and shard_mapped over ``mesh``
    when one is given.  A new jit each time it is called.

    The replica batch maps onto every mesh axis EXCEPT the rack-sharding
    axis (``cfg.partition.axis``, normally "racks"): on a 2-D
    ("replicas", "racks") mesh, Monte Carlo replicas split over the
    orthogonal "replicas" axis while each replica's farm state stays
    whole (replicated) along "racks" — the two parallelism axes compose
    without interfering."""
    runner = jax.vmap(functools.partial(engine.run.__wrapped__, cfg=cfg,
                                        tc=tc))
    if mesh is None:
        return jax.jit(runner)
    from jax.sharding import PartitionSpec as P

    bax = tuple(a for a in mesh.axis_names if a != cfg.partition.axis)
    # prefix spec: replica dim 0 over the non-rack axes
    spec = P(bax) if bax else P()
    fn = jax.shard_map(runner, mesh=mesh, in_specs=(spec,), out_specs=spec,
                       check_vma=False)
    return jax.jit(fn)


def run_replicas(cfg: SimConfig, state_b, tc=None, mesh=None):
    """Run every replica of the batch to its end (:func:`replica_jit`).

    Each call builds its jit anew, so each call traces, lowers and
    compiles (or reads the compile cache) before it runs.  The four
    stages run under the profiler spans ``run_replicas.trace``,
    ``run_replicas.lower``, ``run_replicas.compile`` and
    ``run_replicas.execute``, so a profile splits a call's host time."""
    fn = replica_jit(cfg, tc, mesh)
    with jax.profiler.TraceAnnotation("run_replicas.trace"):
        traced = fn.trace(state_b)
    with jax.profiler.TraceAnnotation("run_replicas.lower"):
        lowered = traced.lower()
    with jax.profiler.TraceAnnotation("run_replicas.compile"):
        compiled = lowered.compile()
    with jax.profiler.TraceAnnotation("run_replicas.execute"):
        return compiled(state_b)


def replica_stats(state_b, cfg: SimConfig):
    """Host-side per-replica summaries -> dict of numpy arrays.

    Replicas that finish zero jobs get NaN latency stats without tripping
    numpy's all-NaN RuntimeWarnings.  Percentiles come from the device-side
    telemetry histograms (one (R, B) array off-device instead of the (R, J)
    job tables) when telemetry is enabled; otherwise from the exact
    per-job latencies.
    """
    arr = np.asarray(state_b.jobs.arrival)                # (R, J)
    fin = np.asarray(state_b.jobs.job_finish)
    ok = (fin < INF / 2) & (arr < INF / 2)
    finished = ok.sum(axis=1)
    lat_sum = np.where(ok, fin - arr, 0.0).sum(axis=1)
    mean_lat = np.where(finished > 0,
                        lat_sum / np.maximum(finished, 1), np.nan)
    energy = np.asarray(state_b.farm.energy).sum(axis=1)  # (R,)
    sw_energy = np.asarray(state_b.net.sw_energy).sum(axis=1)
    cool = np.asarray(state_b.thermal.cool_energy) if cfg.thermal.enabled \
        else 0.0
    t = np.asarray(state_b.t)

    tcfg = cfg.telemetry
    if tcfg.enabled:
        hist = np.asarray(state_b.telem.job_hist)         # (R, B)
        pct = {q: telemetry.hist_percentile(hist, tcfg.lat_lo,
                                            tcfg.lat_hi, q)
               for q in (50, 95, 99)}
    else:
        def _exact(q):
            return np.asarray([
                np.percentile((fin[r] - arr[r])[ok[r]], q)
                if finished[r] else np.nan
                for r in range(arr.shape[0])])
        pct = {q: _exact(q) for q in (50, 95, 99)}
    out = {
        "mean_latency": mean_lat,
        "p50_latency": pct[50],
        "p95_latency": pct[95],
        "p99_latency": pct[99],
        "energy": energy,
        "sim_time": t,
        # same definition as SimResult.mean_power: IT + switch + cooling
        "mean_power": (energy + sw_energy + cool) / np.maximum(t, 1e-12),
        "events": np.asarray(state_b.events),
        "finished": finished,
        "flows_dropped": np.asarray(state_b.flows.flows_dropped),
    }
    if cfg.trace.enabled:
        # per-replica flight-recorder health: records evicted by wrap
        out["trace_dropped"] = np.asarray(state_b.trace.dropped)
    if cfg.thermal.enabled:
        th = state_b.thermal
        out.update({
            "cooling_energy": np.asarray(th.cool_energy),        # (R,)
            "carbon_g": np.asarray(th.carbon_g),
            "energy_cost": np.asarray(th.cost),
            "peak_temp": np.asarray(th.t_peak).max(axis=1),
            "throttle_seconds": np.asarray(th.throttle_seconds).sum(axis=1),
            "deferred_jobs": np.asarray(th.defer_count),         # (R,)
            "deferred_seconds": np.asarray(th.defer_seconds),
            "carbon_g_avoided_est": np.asarray(th.grams_avoided),
        })
    return out


def poisson_failure_times(mtbf: float, horizon: float, n_nodes: int,
                          seed: int = 0) -> np.ndarray:
    """Fleet-level failure arrivals for checkpoint-cadence studies: a node
    fleet with per-node MTBF produces failures at rate n/mtbf."""
    rng = np.random.default_rng(seed)
    rate = n_nodes / mtbf
    out, t = [], 0.0
    while t < horizon:
        t += rng.exponential(1.0 / rate)
        if t < horizon:
            out.append(t)
    return np.asarray(out)


def young_daly_interval(mtbf_fleet: float, ckpt_cost: float) -> float:
    """Optimal checkpoint interval sqrt(2·δ·MTBF) (Young/Daly)."""
    return float(np.sqrt(2.0 * ckpt_cost * mtbf_fleet))

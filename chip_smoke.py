"""Chip smoke test: drive the simulator's main path on a TPU and check it.

    python chip_smoke.py                # one chip: farm, kernels, replicas, oracle
    python chip_smoke.py --four-chips   # four chips: rack-sharded farm and
                                        # sharded replica batch vs one chip

Each phase prints one line (name, wall seconds, events, finished jobs) and
raises on any failed check.  Only when every phase passed on a TPU does
the last line carry ``{"ok": true, "device": {...}}``.  On any other
platform the script exits non-zero before running a phase.

The phases take their sizes as arguments; ``main`` passes the real ones
(``tests/test_chip_smoke.py`` runs them tiny on CPU).  All inputs are
generated from fixed seeds.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (engine, farm, montecarlo, shard_sim,  # noqa: E402
                        traceio, workload)
from repro.core.jobs import build_jobs, dag_single  # noqa: E402
from repro.core.types import (PartitionConfig, SchedPolicy,  # noqa: E402
                              SimConfig, SleepPolicy, SrvState,
                              TelemetryConfig, ThermalConfig, TraceConfig)

# the paper's web-serving service (§IV-B): exponential, mean 120 ms
WEB_SERVING_SVC = 0.120
UTILIZATION = 0.3
TAU = 0.05                     # single delay timer into package C6 (s)
SEED = 0
# jobs per run.  A macro-step's cost on the chip grows with the job table
# (v5e: ~6.6 ms per step at 10,000 jobs, and 100,000 jobs did not finish
# the farm phase in 800 s), so the 20480-server farm takes 20,000 jobs,
# not the 100,000 that would give every server several sleep/wake cycles
FARM_JOBS = 20_000
REPLICA_JOBS = 2_000
# --four-chips runs each path twice (one chip, then four): fewer jobs
FOUR_CHIP_FARM_JOBS = 5_000
FOUR_CHIP_REPLICA_JOBS = 1_000
# kernel path vs jnp path: counts must match exactly; energy and latency
# percentiles within this relative tolerance (bit-identical on CPU)
KERNEL_RTOL = 1e-4
# telemetry windows vs run totals: f32 sums over ~10^5 intervals
TELEMETRY_RTOL = 1e-3


def check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def report(phase: str, wall: float, events: int, finished: int,
           n_jobs: int) -> None:
    print(f"phase {phase} passed: wall_s={wall:.3f} events={events} "
          f"finished={finished}/{n_jobs}", flush=True)


# ==========================================================================
# scenario: a web-serving farm at 30% utilization with sleep timers
# ==========================================================================

def farm_config(n_servers: int, n_jobs: int, use_kernel: bool = False,
                n_shards: int = 1) -> SimConfig:
    return SimConfig(
        n_servers=n_servers, n_cores=4, tasks_per_job=1, max_jobs=n_jobs,
        sleep_policy=SleepPolicy.SINGLE_TIMER, sleep_state=SrvState.PKG_C6,
        # every job arrives, finishes, and at most wakes and re-sleeps
        # one server: the run ends on `done`, never on this budget
        max_events=8 * n_jobs + 4 * n_servers,
        use_kernel=use_kernel,
        telemetry=TelemetryConfig(enabled=True, use_kernel=use_kernel),
        partition=PartitionConfig(n_shards=n_shards))


def web_serving(n_servers: int, n_jobs: int, seed: int):
    lam = workload.utilization_to_rate(UTILIZATION, WEB_SERVING_SVC,
                                       n_servers, 4)
    arr = workload.poisson_arrivals(lam, n_jobs, seed=seed)
    svc = np.random.default_rng(seed + 1).exponential(WEB_SERVING_SVC,
                                                      n_jobs)
    return arr, [dag_single(s) for s in svc]


def initial_state(cfg: SimConfig, arr, specs):
    """The state ``farm.simulate(cfg, arr, specs, tau=TAU)`` starts from."""
    state, tc = engine.init_state(cfg, build_jobs(cfg, np.asarray(arr),
                                                  specs))
    tau = jax.numpy.full((cfg.n_servers,), TAU, cfg.time_dtype)
    farm_state = dataclasses.replace(state.farm, srv_tau=tau)
    return dataclasses.replace(state, farm=farm_state), tc


def leaf_diffs(a, b) -> list:
    """Paths of the state leaves that are not bit-identical."""
    paths, _ = jax.tree_util.tree_flatten_with_path(a)
    return [jax.tree_util.keystr(p)
            for (p, x), y in zip(paths, jax.tree.leaves(b))
            if not np.array_equal(np.asarray(x), np.asarray(y))]


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(a), abs(b), 1e-30)


# ==========================================================================
# one-chip phases
# ==========================================================================

def farm_phase(n_servers: int, n_jobs: int, seed: int = SEED):
    """``farm.simulate`` on the web-serving farm, telemetry on."""
    t0 = time.perf_counter()
    cfg = farm_config(n_servers, n_jobs)
    arr, specs = web_serving(n_servers, n_jobs, seed)
    res = farm.simulate(cfg, arr, specs, tau=TAU, profile=True)
    check(res.n_finished == n_jobs,
          f"farm: {res.n_finished}/{n_jobs} jobs finished")
    check(res.dropped == 0, f"farm: {res.dropped} tasks dropped")
    ts = res.telemetry
    occ = float(ts.occupancy.sum())
    check(rel_diff(occ, res.sim_time) <= TELEMETRY_RTOL,
          f"farm: window occupancy {occ} != sim_time {res.sim_time}")
    joules = float(np.nansum(ts.server_power * ts.occupancy))
    check(rel_diff(joules, res.server_energy) <= TELEMETRY_RTOL,
          f"farm: window energy {joules} != server_energy "
          f"{res.server_energy}")
    ri = res.run_info
    print(f"farm run_info: backend={ri.backend} steps={ri.steps} "
          f"events={ri.events} wall_s={ri.wall_s:.3f} "
          f"events_per_s={ri.events_per_s:.1f}")
    print(f"farm compile_s={ri.jit_compile_s:.3f} sim_time={res.sim_time} "
          f"wakes={int(res.wake_count.sum())} "
          f"occupancy_rel_err={rel_diff(occ, res.sim_time):.3g} "
          f"energy_rel_err={rel_diff(joules, res.server_energy):.3g}")
    report("farm", time.perf_counter() - t0, res.events, res.n_finished,
           n_jobs)
    return res


def kernels_phase(n_servers: int, n_jobs: int, ref, seed: int = SEED,
                  require_custom_call: bool = True):
    """The same scenario through both Pallas kernels (``use_kernel``)."""
    t0 = time.perf_counter()
    cfg = farm_config(n_servers, n_jobs, use_kernel=True)
    arr, specs = web_serving(n_servers, n_jobs, seed)
    state, tc = initial_state(cfg, arr, specs)
    hlo = engine.run.lower(state, cfg, tc).compile().as_text()
    del state, tc
    if require_custom_call:
        check("tpu_custom_call" in hlo,
              "kernels: no tpu_custom_call in the compiled engine.run")
    res = farm.simulate(cfg, arr, specs, tau=TAU)
    for name in ("events", "n_finished", "dropped"):
        check(getattr(res, name) == getattr(ref, name),
              f"kernels: {name} {getattr(res, name)} != jnp path "
              f"{getattr(ref, name)}")
    diffs = {name: rel_diff(getattr(res, name), getattr(ref, name))
             for name in ("server_energy", "p50_latency", "p95_latency",
                          "p99_latency")}
    print("kernels rel_diff vs jnp path: " + " ".join(
        f"{k}={v:.3g}" for k, v in diffs.items()))
    bad = {k: v for k, v in diffs.items() if v > KERNEL_RTOL}
    check(not bad, f"kernels: beyond rtol {KERNEL_RTOL}: {bad}")
    report("kernels", time.perf_counter() - t0, res.events, res.n_finished,
           n_jobs)
    return res


def replica_batch(n_replicas: int, n_servers: int, n_jobs: int, seed: int):
    """A batch of farms that differ only in their arrival seed."""
    cfg = farm_config(n_servers, n_jobs)
    lam = workload.utilization_to_rate(UTILIZATION, WEB_SERVING_SVC,
                                       n_servers, 4)
    arrs = np.stack([workload.poisson_arrivals(lam, n_jobs, seed=seed + r)
                     for r in range(n_replicas)])
    _, specs = web_serving(n_servers, n_jobs, seed)
    state_b, tc = montecarlo.batched_state(
        cfg, arrs, specs, taus=np.full((n_replicas,), TAU))
    return cfg, arrs, specs, state_b, tc


def replicas_phase(n_replicas: int, n_servers: int, n_jobs: int,
                   seed: int = SEED):
    """``montecarlo.run_replicas`` over replicas with their own seeds."""
    t0 = time.perf_counter()
    cfg, arrs, specs, state_b, tc = replica_batch(n_replicas, n_servers,
                                                  n_jobs, seed)
    out = jax.block_until_ready(montecarlo.run_replicas(cfg, state_b, tc))
    stats = montecarlo.replica_stats(out, cfg)
    check((stats["finished"] == n_jobs).all(),
          f"replicas: finished per replica {stats['finished'].tolist()}")
    state, tc = initial_state(cfg, arrs[0], specs)
    lone = jax.block_until_ready(engine.run(state, cfg, tc))
    check(int(lone.events) == int(stats["events"][0]),
          f"replicas: replica 0 has {int(stats['events'][0])} events, a "
          f"lone engine.run {int(lone.events)}")
    report("replicas", time.perf_counter() - t0, int(stats["events"].sum()),
           int(stats["finished"].sum()), n_replicas * n_jobs)


def oracle_config() -> SimConfig:
    """Sleep timers, thermal throttling and the trace ring on a small farm
    (the rich configuration of tests/test_trace.py)."""
    tcfg = ThermalConfig(enabled=True, r_th=0.5, tau_th=2.0, t_inlet=22.0,
                         recirc=0.2, rack_size=3, t_throttle=50.0,
                         t_release=45.0, throttle_freq=0.5,
                         throttle_power_scale=0.6, carbon_period=600.0,
                         price_period=600.0)
    return SimConfig(n_servers=6, n_cores=2, max_jobs=256, tasks_per_job=1,
                     sched_policy=SchedPolicy.LOAD_BALANCE,
                     sleep_policy=SleepPolicy.SINGLE_TIMER,
                     sleep_state=SrvState.S3, max_events=60_000,
                     thermal=tcfg, trace=TraceConfig(enabled=True))


def oracle_phase(n_jobs: int = 150):
    """The chip's event stream against the heapq reference simulator."""
    from oracle import OracleSim
    t0 = time.perf_counter()
    cfg = oracle_config()
    arr = workload.poisson_arrivals(60.0, n_jobs, seed=3)
    rng = np.random.default_rng(7)
    specs = [dag_single(rng.exponential(0.02)) for _ in range(n_jobs)]
    res = farm.simulate(cfg, arr, specs, tau=0.05)
    orc = OracleSim(cfg, arr, specs, tau=0.05).run()
    check(res.n_finished == n_jobs,
          f"oracle: {res.n_finished}/{n_jobs} jobs finished")
    check(res.trace_dropped == 0, f"oracle: {res.trace_dropped} records "
          f"evicted from the trace ring")
    msg = traceio.diff_traces(res.trace_events,
                              traceio.as_events(orc.trace), time_tol=5e-3)
    check(msg is None, f"oracle: trace differs from the heapq oracle: {msg}")
    report("oracle", time.perf_counter() - t0, res.events, res.n_finished,
           n_jobs)


# ==========================================================================
# four-chip phases
# ==========================================================================

def sharded_farm_phase(n_servers: int, n_jobs: int, n_devices: int,
                       seed: int = SEED):
    """Rack-sharded run over ``n_devices`` against ``engine.run`` of the
    same state on one device: every state leaf bit-identical."""
    t0 = time.perf_counter()
    cfg = farm.pad_to_racks(farm_config(n_servers, n_jobs,
                                        n_shards=n_devices))
    one = dataclasses.replace(cfg, partition=PartitionConfig())
    check(farm.config_digest(cfg) == farm.config_digest(one),
          "sharded_farm: config_digest depends on the device count")
    arr, specs = web_serving(cfg.present, n_jobs, seed)
    state, tc = initial_state(cfg, arr, specs)
    ref = jax.block_until_ready(engine.run(state, one, tc))
    out = jax.block_until_ready(shard_sim.run_sharded(state, cfg, tc))
    spread = len(out.farm.energy.sharding.device_set)
    check(spread == n_devices,
          f"sharded_farm: server state on {spread} devices, not {n_devices}")
    check(int(out.jobs.arr_ptr) == n_jobs and bool(out.done),
          "sharded_farm: run did not finish")
    bad = leaf_diffs(ref, out)
    check(not bad, f"sharded_farm: leaves differ from one device: {bad}")
    finished = int((np.asarray(out.jobs.job_finish) < 1e29).sum())
    report("sharded_farm", time.perf_counter() - t0, int(out.events),
           finished, n_jobs)


def sharded_replicas_phase(n_replicas: int, n_servers: int, n_jobs: int,
                           n_devices: int, seed: int = SEED):
    """The replica batch sharded over a ("replicas",) mesh against the same
    batch on one device: every state leaf bit-identical."""
    t0 = time.perf_counter()
    cfg, _, _, state_b, tc = replica_batch(n_replicas, n_servers, n_jobs,
                                           seed)
    mesh = jax.make_mesh((n_devices,), ("replicas",),
                         devices=jax.devices()[:n_devices])
    ref = jax.block_until_ready(montecarlo.run_replicas(cfg, state_b, tc))
    out = jax.block_until_ready(
        montecarlo.run_replicas(cfg, state_b, tc, mesh=mesh))
    spread = len(out.t.sharding.device_set)
    check(spread == n_devices,
          f"sharded_replicas: batch on {spread} devices, not {n_devices}")
    bad = leaf_diffs(ref, out)
    check(not bad, f"sharded_replicas: leaves differ from one device: {bad}")
    stats = montecarlo.replica_stats(out, cfg)
    check((stats["finished"] == n_jobs).all(),
          f"sharded_replicas: finished {stats['finished'].tolist()}")
    report("sharded_replicas", time.perf_counter() - t0,
           int(stats["events"].sum()), int(stats["finished"].sum()),
           n_replicas * n_jobs)


# ==========================================================================

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the rack-sharded farm and the sharded "
                         "replica batch, each against one chip")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 chips; JAX found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        sharded_farm_phase(20480, FOUR_CHIP_FARM_JOBS, n_devices=4)
        sharded_replicas_phase(64, 512, FOUR_CHIP_REPLICA_JOBS, n_devices=4)
    else:
        ref = farm_phase(20480, FARM_JOBS)
        kernels_phase(20480, FARM_JOBS, ref)
        replicas_phase(64, 512, REPLICA_JOBS)
        oracle_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

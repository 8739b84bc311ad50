"""Event-coalesced macro-stepping + task-major queue properties:

  * for events_per_step in {1, 4, 16} the final state is IDENTICAL leaf
    by leaf — the cheap-core gating is conservative, so macro-stepping
    only changes how many event times one jitted step retires, never the
    dynamics — across load-balance, round-robin + network flows, and
    thermal-aware + throttling configs
  * the same runs match the sequential heapq oracle (latencies, energy,
    drop/flow accounting), so the coalesced path is validated against an
    independent implementation, not just against K=1
  * the fused-kernel advance (cfg.use_kernel, interpret mode off-TPU)
    reproduces the jnp advance path bit-for-bit inside the engine
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import engine, topology, workload
from repro.core.jobs import build_jobs, dag_chain, dag_single
from repro.core.types import (INF, SchedPolicy, SimConfig, SleepPolicy,
                              SrvState, ThermalConfig)

from oracle import OracleSim

KS = (1, 4, 16)


def _run_engine(cfg, arr, specs, topo=None, tau=None):
    jt = build_jobs(cfg, np.asarray(arr), specs)
    state, tc = engine.init_state(cfg, jt, topo)
    if tau is not None:
        state = dataclasses.replace(
            state, farm=dataclasses.replace(
                state.farm,
                srv_tau=jax.numpy.broadcast_to(
                    jax.numpy.asarray(tau, cfg.time_dtype),
                    (cfg.n_servers,))))
    return engine.run(state, cfg, tc)


def _assert_states_equal(ref, other, context):
    paths = [".".join(str(p) for p in kp)
             for kp, _ in jax.tree_util.tree_leaves_with_path(ref)]
    for name, lv, ls in zip(paths, jax.tree.leaves(ref),
                            jax.tree.leaves(other)):
        if name == ".steps":
            continue      # macro-step count: K-dependent by definition
        np.testing.assert_array_equal(
            np.asarray(lv), np.asarray(ls),
            err_msg=f"{context}: state leaf {name} diverged")


def _sweep_ks(cfg, arr, specs, topo=None, tau=None):
    outs = {k: _run_engine(dataclasses.replace(cfg, events_per_step=k),
                           arr, specs, topo, tau) for k in KS}
    for k in KS[1:]:
        _assert_states_equal(outs[KS[0]], outs[k],
                             f"events_per_step={k} vs 1")
    return outs[KS[0]]


def test_macro_step_load_balance_with_sleep():
    n_jobs = 150
    cfg = SimConfig(n_servers=6, n_cores=2, max_jobs=256, tasks_per_job=1,
                    sched_policy=SchedPolicy.LOAD_BALANCE,
                    sleep_policy=SleepPolicy.SINGLE_TIMER,
                    sleep_state=SrvState.S3, max_events=50_000)
    rng = np.random.default_rng(3)
    arr = workload.poisson_arrivals(100.0, n_jobs, seed=2)
    specs = [dag_single(rng.exponential(0.02)) for _ in range(n_jobs)]
    final = _sweep_ks(cfg, arr, specs, tau=0.05)

    orc = OracleSim(cfg, arr, specs, tau=0.05).run()
    fin = np.asarray(final.jobs.job_finish)
    lat = np.sort((fin - np.asarray(final.jobs.arrival))[fin < INF / 2])
    np.testing.assert_allclose(lat, np.sort(orc.latencies()),
                               rtol=1e-4, atol=1e-4)
    assert float(np.asarray(final.farm.energy).sum()) == pytest.approx(
        orc.total_energy(), rel=2e-3)


def test_macro_step_network_flows_round_robin():
    """ROUND_ROBIN splits 2-task chains across servers so every job
    routes a flow: the gate must hand flow completions and spawning
    completions to the full step, and still match the fluid oracle."""
    n_jobs = 30
    cfg = SimConfig(n_servers=6, n_cores=2, max_jobs=64, tasks_per_job=2,
                    max_children=2, max_flows=64, local_q=32,
                    sched_policy=SchedPolicy.ROUND_ROBIN,
                    sleep_policy=SleepPolicy.ALWAYS_ON,
                    has_network=True, comm_model=0, max_events=60_000)
    topo = topology.star(cfg.n_servers, link_cap=1.0e8)
    rng = np.random.default_rng(2)
    arr = workload.poisson_arrivals(25.0, n_jobs, seed=2)
    specs = [dag_chain(rng.uniform(0.01, 0.04, size=2),
                       edge_bytes=float(rng.uniform(4e6, 8e6)))
             for _ in range(n_jobs)]
    final = _sweep_ks(cfg, arr, specs, topo=topo)

    orc = OracleSim(cfg, arr, specs, topo=topo).run()
    fin = np.asarray(final.jobs.job_finish)
    lat = np.sort((fin - np.asarray(final.jobs.arrival))[fin < INF / 2])
    assert len(lat) == n_jobs == len(orc.job_finish)
    np.testing.assert_allclose(lat, np.sort(orc.latencies()),
                               rtol=1e-4, atol=1e-4)
    # flows actually routed (port ACTIVE residency is only accrued while
    # links carry traffic)
    assert float(np.asarray(final.net.port_residency)[..., 0].sum()) > 0


def test_macro_step_thermal_aware_throttling():
    """THERMAL_AWARE placement + engaged throttling: crossings stop the
    chew (they are full-step events), the latch/stretch stays exact, and
    all three K values match the numpy thermal oracle."""
    tcfg = ThermalConfig(enabled=True, r_th=0.5, tau_th=2.0, t_inlet=22.0,
                         recirc=0.2, rack_size=3, t_throttle=50.0,
                         t_release=45.0, throttle_freq=0.5,
                         throttle_power_scale=0.6)
    cfg = SimConfig(n_servers=6, n_cores=1, max_jobs=256, tasks_per_job=1,
                    sched_policy=SchedPolicy.THERMAL_AWARE,
                    sleep_policy=SleepPolicy.ALWAYS_ON, max_events=60_000,
                    thermal=tcfg)
    rng = np.random.default_rng(7)
    arr = workload.poisson_arrivals(25.0, 120, seed=3)
    specs = [dag_single(rng.exponential(0.08)) for _ in range(120)]
    final = _sweep_ks(cfg, arr, specs)

    orc = OracleSim(cfg, arr, specs).run()
    fin = np.asarray(final.jobs.job_finish)
    lat = np.sort((fin - np.asarray(final.jobs.arrival))[fin < INF / 2])
    assert len(lat) == len(arr) == len(orc.job_finish)
    np.testing.assert_allclose(lat, np.sort(orc.latencies()),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final.thermal.t_srv), orc.temp,
                               rtol=2e-3, atol=5e-2)
    assert float(np.asarray(final.thermal.throttle_seconds).sum()) > 0
    assert float(np.asarray(final.thermal.throttle_seconds).sum()) == \
        pytest.approx(orc.throttle_seconds.sum(), rel=5e-3, abs=1e-3)


def test_macro_step_queue_contention_fifo():
    """More queued tasks than free cores: the task-major FIFO rank must
    start tasks in enqueue order under every K (single 1-core server, so
    any ordering slip changes latencies)."""
    n_jobs = 20
    cfg = SimConfig(n_servers=1, n_cores=1, local_q=64, max_jobs=32,
                    tasks_per_job=1, sched_policy=SchedPolicy.LOAD_BALANCE,
                    sleep_policy=SleepPolicy.ALWAYS_ON, max_events=20_000)
    arr = 0.01 * np.arange(n_jobs)               # all queue behind job 0
    specs = [dag_single(0.5) for _ in range(n_jobs)]
    final = _sweep_ks(cfg, arr, specs)
    orc = OracleSim(cfg, arr, specs).run()
    fin = np.asarray(final.jobs.job_finish)
    lat = np.sort((fin - np.asarray(final.jobs.arrival))[fin < INF / 2])
    np.testing.assert_allclose(lat, np.sort(orc.latencies()),
                               rtol=1e-4, atol=1e-4)


def test_macro_step_congested_queue_argsort_fallback():
    """More than COMPACT_Q (128) tasks queued farm-wide: try_start must
    take the full lexicographic-argsort rank path and still start in
    FIFO order (2 one-core servers, 200 near-simultaneous jobs)."""
    n_jobs = 200
    cfg = SimConfig(n_servers=2, n_cores=1, local_q=256, max_jobs=256,
                    tasks_per_job=1, sched_policy=SchedPolicy.LOAD_BALANCE,
                    sleep_policy=SleepPolicy.ALWAYS_ON, max_events=20_000)
    arr = 0.001 * np.arange(n_jobs)
    specs = [dag_single(1.0) for _ in range(n_jobs)]
    final = _sweep_ks(cfg, arr, specs)
    assert int(final.farm.rank_fallbacks) > 0       # the dense rank ran
    orc = OracleSim(cfg, arr, specs).run()
    fin = np.asarray(final.jobs.job_finish)
    ok = fin < INF / 2
    assert int(ok.sum()) == n_jobs == len(orc.job_finish)
    lat = np.sort((fin - np.asarray(final.jobs.arrival))[ok])
    np.testing.assert_allclose(lat, np.sort(orc.latencies()),
                               rtol=1e-4, atol=1e-4)


def test_macro_step_dag_immediate_edges_coalesce():
    """ROADMAP item: with a network configured, a completing task whose
    DAG edges all resolve IMMEDIATELY (zero-byte edges here, split across
    servers by ROUND_ROBIN so colocating is not what saves them) must not
    stop the cheap-core chew — final states are bit-identical for K in
    {1, 8} and match the oracle, while flows never spawn (nothing routes
    for a zero-byte edge)."""
    n_jobs = 40
    cfg0 = SimConfig(n_servers=4, n_cores=2, max_jobs=64, tasks_per_job=3,
                     max_children=2, max_flows=64, local_q=32,
                     sched_policy=SchedPolicy.ROUND_ROBIN,
                     sleep_policy=SleepPolicy.ALWAYS_ON,
                     has_network=True, comm_model=0, max_events=60_000)
    topo = topology.star(cfg0.n_servers, link_cap=1.0e8)
    rng = np.random.default_rng(4)
    arr = workload.poisson_arrivals(30.0, n_jobs, seed=6)
    specs = [dag_chain(rng.uniform(0.01, 0.04, size=3), edge_bytes=0.0)
             for _ in range(n_jobs)]
    outs = {k: _run_engine(dataclasses.replace(cfg0, events_per_step=k),
                           arr, specs, topo=topo) for k in (1, 8)}
    _assert_states_equal(outs[1], outs[8], "dag-immediate K=8 vs K=1")
    final = outs[1]
    orc = OracleSim(cfg0, arr, specs, topo=topo).run()
    fin = np.asarray(final.jobs.job_finish)
    lat = np.sort((fin - np.asarray(final.jobs.arrival))[fin < INF / 2])
    assert len(lat) == n_jobs == len(orc.job_finish)
    np.testing.assert_allclose(lat, np.sort(orc.latencies()),
                               rtol=1e-4, atol=1e-4)
    # nothing routed: the chains resolved entirely through the immediate
    # (in-core) edge path
    assert not bool(np.asarray(final.flows.active).any())
    assert int(final.flows.flows_dropped) == 0


@pytest.mark.parametrize("events_per_step", [1, 8])
def test_use_kernel_advance_matches_jnp(events_per_step):
    """cfg.use_kernel routes the advance through the fused Pallas kernel
    (interpret mode off-TPU): the final state must match the jnp path
    exactly, including with thermal throttling (the kernel models the
    throttle power scaling)."""
    tcfg = ThermalConfig(enabled=True, r_th=0.5, tau_th=2.0, recirc=0.0,
                         t_throttle=50.0, t_release=45.0,
                         throttle_power_scale=0.6)
    cfg = SimConfig(n_servers=4, n_cores=2, max_jobs=32, tasks_per_job=1,
                    sleep_policy=SleepPolicy.SINGLE_TIMER,
                    sleep_state=SrvState.S3, max_events=20_000,
                    events_per_step=events_per_step, thermal=tcfg)
    rng = np.random.default_rng(5)
    arr = workload.poisson_arrivals(30.0, 25, seed=5)
    specs = [dag_single(rng.exponential(0.05)) for _ in range(25)]
    outs = []
    for uk in (False, True):
        c = dataclasses.replace(cfg, use_kernel=uk)
        outs.append(_run_engine(c, arr, specs, tau=0.05))
    _assert_states_equal(outs[0], outs[1], "use_kernel")

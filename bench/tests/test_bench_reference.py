"""The plain reference against the program at a small size, and its
control and histogram fault: the reference with times in bfloat16, or
with its latencies binned one bin off, put in the program's place, must
come out not correct under the cells' limits."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, control, generator, loader, program
from bench.references import farm_des
from repro.core import engine, montecarlo
from repro.core.jobs import build_jobs, dag_single

FARM = "farm20k_c6.websrv_j100k"
SWEEP = "caseB_20srv.fig5_websrv"
SEED = 2**31 + 11


def _small_farm():
    cell = loader.cell(FARM)
    sim = dict(cell.config["sim"], n_servers=128)
    traffic = dict(cell.traffic, jobs=1500)
    arr, svc, tau = generator.farm(traffic, sim, cell.config["tau_s"], SEED)
    return cell, sim, (arr, svc, tau)


def test_reference_matches_the_program_on_a_small_farm():
    cell, sim, (arr, svc, tau) = _small_farm()
    n_ev = 1200
    cfg = program.sim_config(sim, max_jobs=len(arr), max_events=n_ev)
    state, tc = engine.init_state(
        cfg, build_jobs(cfg, arr, [dag_single(s) for s in svc]))
    state = dataclasses.replace(state, farm=dataclasses.replace(
        state.farm, srv_tau=jnp.asarray(tau, cfg.time_dtype)))
    out = jax.block_until_ready(engine.run(state, cfg, tc))
    assert int(out.events) == n_ev
    ref = farm_des.simulate(sim, arr, svc, tau, max_events=n_ev)
    gaps = compare.gaps(program.outputs(out, len(arr)), ref)
    assert tuple(gaps) == compare.NUMBERS
    ok, checks = compare.judge(gaps, cell.limits)
    assert ok, checks
    assert int((np.asarray(out.farm.wake_count)).sum()) > 0   # sleeps woke


def test_reference_matches_the_program_on_a_small_sweep():
    cell = loader.cell(SWEEP)
    sim = cell.config["sim"]
    mix = dict(cell.traffic, jobs=60, seeds_per_point=1)
    arrs, svc, taus, _ = generator.sweep(mix, sim, SEED)
    cfg = program.sim_config(sim)
    state_b, tc = montecarlo.batched_state(
        cfg, arrs, [dag_single(s) for s in svc], taus=taus)
    out = jax.block_until_ready(montecarlo.run_replicas(cfg, state_b, tc))
    readings = [compare.gaps(program.outputs(out, 60, replica=r),
                             farm_des.simulate(sim, arrs[r], svc, taus[r]))
                for r in range(len(arrs))]
    ok, checks = compare.judge(compare.worst(readings), cell.limits)
    assert ok, checks


@pytest.mark.parametrize("seed", [SEED, 7, 123456789])
def test_control_in_bfloat16_is_not_correct(seed):
    cell, sim, _ = _small_farm()
    arr, svc, tau = generator.farm(dict(cell.traffic, jobs=1500), sim,
                                   cell.config["tau_s"], seed)
    ref = farm_des.simulate(sim, arr, svc, tau, max_events=1200)
    ctl = farm_des.simulate(sim, arr, svc, tau, max_events=1200,
                            time_dtype="bfloat16")
    ok, checks = compare.judge(compare.gaps(ctl, ref), cell.limits)
    assert not ok, checks


@pytest.mark.parametrize("name", [FARM, SWEEP])
def test_histogram_fault_is_not_correct_at_the_cells_size(name):
    # the same jobs finish at the same times; only their bins are off
    cell = loader.cell(name)
    entry = loader.entry(cell)
    ok, checks = compare.judge(entry.control(cell, SEED), cell.limits)
    assert ok, checks
    numbers = entry.control(cell, SEED, alter=control.hist_shift)
    ok, checks = compare.judge(numbers, cell.limits)
    assert not ok, checks
    assert [k for k, c in checks.items() if c["value"] > c["limit"]] == [
        "hist_misbinned"]

"""A run driven end to end on the CPU (the look for a chip skipped) with
the timed path broken underneath must come out ``correct: false``, once
for each fault the cells can have: a call that returns its state
unchanged, half of a replica batch left out, an answer altered where it
is produced.  (No cell spans chips, so there is no exchange to leave
out.)  Unbroken, the same small runs are correct."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.entries import farm, sweep

SEED = 2**31 + 5


def _run(cell, setup):
    args = run.parse(["--workload", cell.name, "--seed", str(SEED),
                      "--seconds", "0", "--trace", "0"])
    return run.run(args, cell, jax.devices(), entry_setup=setup)


def _broken(entry, fault):
    def setup(cell, seed):
        r = entry.setup(cell, seed)
        good = r.call
        r.call = lambda: fault(r, good)
        return r
    return setup


def _unchanged_farm(r, good):
    # the state comes back as it went in, its event counter advanced
    s = r.s_w
    return dataclasses.replace(s, events=s.events + r.E)


def _altered_answer(r, good):
    out = good()
    jobs = out.jobs
    fin = jobs.job_finish                           # (J,) or (R, J)
    j = jnp.unravel_index(jnp.argmin(fin), fin.shape)   # a finished job
    return dataclasses.replace(out, jobs=dataclasses.replace(
        jobs, job_finish=fin.at[j].add(1e-3)))


def _unchanged_sweep(r, good):
    good()
    return r.state_b


def _half_batch(r, good):
    # run the first half of the replicas and stand them in for the rest
    half = r.R // 2
    first = jax.tree.map(lambda x: x[:half], r.state_b)
    out = sweep.montecarlo.run_replicas(r.cfg, first, r.tc)
    idx = jnp.arange(r.R) % half
    return jax.tree.map(lambda x: x[idx], out)


def test_unbroken_farm_run_is_correct(farm_cell):
    res = _run(farm_cell, farm.setup)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res["checks"])[-1] == "counter_mismatch"
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_unchanged_farm, _altered_answer])
def test_broken_farm_run_is_not_correct(farm_cell, fault):
    res = _run(farm_cell, _broken(farm, fault))
    assert not res["correct"], res["checks"]


def test_unbroken_sweep_run_is_correct(sweep_cell):
    res = _run(sweep_cell, sweep.setup)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [_unchanged_sweep, _half_batch,
                                   _altered_answer])
def test_broken_sweep_run_is_not_correct(sweep_cell, fault):
    res = _run(sweep_cell, _broken(sweep, fault))
    assert not res["correct"], res["checks"]

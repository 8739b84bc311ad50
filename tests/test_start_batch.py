"""Batch-space drain and start (core/server.py):

  * ``compact_mask`` gives the numpy reference's first K set ids, valid
    slots and coverage flag: empty mask, fewer than K, exactly K and more
    than K set bits, alone and under vmap, by each of its three methods
    (compare-and-count; binary search at JT = 20,000 with K = 128; the
    scatter at JT = 20,000 with K = 4,096);
  * on random farm states with at most COMPACT_Q queued tasks, the
    batch-space start (``try_start``'s compact arm) writes the same farm
    and jobs, bit for bit, as the job-table-space ``_start_dense`` —
    queues over few and many servers, random free cores, sleeping
    servers, FIFO stamps across the int32 wrap, throttling off and on;
  * ``farm.rank_fallbacks`` counts the start passes that take the dense
    rank: none on an uncongested farm, every pass on a table no wider
    than COMPACT_Q (the congested case is asserted in test_macro_step).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import farm as farm_mod
from repro.core import server, workload
from repro.core.jobs import build_jobs, dag_single
from repro.core.types import (INF, SchedPolicy, SimConfig, SleepPolicy,
                              SrvState, TaskStatus, init_farm, replace)


def _np_compact(mask, K):
    ids = np.flatnonzero(mask)[:K]
    tids = np.full((K,), -1, np.int32)
    tids[:ids.size] = ids
    return tids, tids >= 0, mask.sum() <= K


@pytest.mark.parametrize("n_set", ["empty", "fewer", "exactly", "more"])
@pytest.mark.parametrize("JT,K", [(200, 8), (200, 128), (1000, 8),
                                  (1000, 128), (20_000, 8), (20_000, 128),
                                  (20_000, 4096)])
def test_compact_mask_matches_numpy(JT, n_set, K):
    rng = np.random.default_rng(JT + K)
    n = {"empty": 0, "fewer": K // 2, "exactly": K,
         "more": min(3 * K, JT)}[n_set]
    masks = np.zeros((4, JT), bool)
    for m in masks:
        m[rng.choice(JT, size=n, replace=False)] = True
    masks[1] = np.arange(JT) >= JT - n      # set bits at the table's end
    batched = jax.vmap(lambda m: server.compact_mask(m, K))(
        jnp.asarray(masks))
    for i, m in enumerate(masks):
        single = server.compact_mask(jnp.asarray(m), K)
        for want, got_v, got_s in zip(_np_compact(m, K), batched, single):
            np.testing.assert_array_equal(np.asarray(got_v[i]), want)
            np.testing.assert_array_equal(np.asarray(got_s), want)


def _random_state(cfg, rng, n_srv_used, wrap):
    """A consistent farm + job table: up to COMPACT_Q QUEUED tasks on the
    first ``n_srv_used`` servers with distinct FIFO stamps issued before
    ``q_seq`` (straddling the int32 wrap when ``wrap``), q_len equal to
    each server's queued count, random free cores and power states."""
    N, C, JT = cfg.n_servers, cfg.n_cores, cfg.n_tasks
    jt = build_jobs(cfg, np.zeros(JT), [dag_single(1.0)] * JT)
    n_q = int(rng.integers(0, server.COMPACT_Q + 1))
    rows = rng.choice(JT, size=n_q, replace=False)
    srv = np.full((JT,), -1, np.int32)
    srv[rows] = rng.integers(0, n_srv_used, size=n_q)
    status = rng.choice(np.asarray([TaskStatus.BLOCKED, TaskStatus.READY,
                                    TaskStatus.RUNNING, TaskStatus.DONE],
                                   np.int32), size=JT)
    status[rows] = TaskStatus.QUEUED
    run = (status == TaskStatus.RUNNING) & (srv < 0)
    srv[run] = rng.integers(0, N, size=int(run.sum()))
    q_seq = np.int64(2 ** 31 + 100 if wrap else rng.integers(1000, 10 ** 6))
    back = rng.choice(3 * server.COMPACT_Q, size=n_q, replace=False) + 1
    seq = np.zeros((JT,), np.int64)
    seq[rows] = q_seq - back
    wrap32 = lambda x: ((x + 2 ** 31) % 2 ** 32 - 2 ** 31).astype(np.int32)
    jt = replace(
        jt, status=jnp.asarray(status), server=jnp.asarray(srv),
        enqueue_seq=jnp.asarray(wrap32(seq)),
        service=jnp.asarray(rng.exponential(0.1, size=JT), jnp.float32))

    busy = rng.uniform(0.0, 2.0, size=(N, C)).astype(np.float32)
    busy[rng.random((N, C)) < 0.5] = INF
    st = rng.choice(np.asarray([SrvState.ACTIVE, SrvState.IDLE,
                                SrvState.PKG_C6, SrvState.WAKING],
                               np.int32), size=N, p=[0.4, 0.4, 0.1, 0.1])
    q_len = np.bincount(srv[rows], minlength=N).astype(np.int32)
    farm = replace(
        init_farm(cfg), core_busy_until=jnp.asarray(busy, cfg.time_dtype),
        srv_state=jnp.asarray(st), q_len=jnp.asarray(q_len),
        q_seq=jnp.asarray(wrap32(np.asarray(q_seq + 7))))
    return farm, jt, n_q


@pytest.mark.parametrize("throttled", [False, True])
@pytest.mark.parametrize("spread", ["few", "many"])
@pytest.mark.parametrize("max_jobs", [200, 1000])
def test_batch_start_equals_dense_start(max_jobs, spread, throttled):
    cfg = SimConfig(n_servers=64, n_cores=4, local_q=256, max_jobs=max_jobs,
                    tasks_per_job=1, sleep_policy=SleepPolicy.ALWAYS_ON)
    N = cfg.n_servers
    rng = np.random.default_rng(max_jobs + 2 * throttled
                                + (spread == "many"))

    @jax.jit
    def both(farm, jobs, now, freq):
        freq = freq if throttled else None
        got = server.try_start(farm, cfg, jobs, now, freq)
        awake = (farm.srv_state == SrvState.ACTIVE) \
            | (farm.srv_state == SrvState.IDLE)
        n_free = (farm.core_busy_until >= INF).sum(axis=1, dtype=jnp.int32)
        n_start = jnp.where(awake, jnp.minimum(n_free, farm.q_len), 0)
        want = server._start_dense(farm, cfg, jobs, now, freq, n_start,
                                   jobs.status == TaskStatus.QUEUED)
        return got, want

    seen_start = 0
    for i in range(12):
        farm, jt, n_q = _random_state(
            cfg, rng, 3 if spread == "few" else N, wrap=i % 3 == 0)
        now = jnp.asarray(rng.uniform(0.0, 1.0), cfg.time_dtype)
        freq = jnp.asarray(rng.uniform(0.5, 1.0, size=N), jnp.float32)
        got, want = both(farm, jt, now, freq)
        assert int(got[0].rank_fallbacks) == 0      # the batch arm ran
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        seen_start += int((got[1].status == TaskStatus.RUNNING).sum()
                          - (jt.status == TaskStatus.RUNNING).sum())
    assert seen_start > 0


def _poisson_farm(max_jobs, n_jobs):
    cfg = SimConfig(n_servers=8, n_cores=4, local_q=64, max_jobs=max_jobs,
                    tasks_per_job=1, sched_policy=SchedPolicy.LOAD_BALANCE,
                    sleep_policy=SleepPolicy.ALWAYS_ON, max_events=20_000)
    arr = workload.poisson_arrivals(40.0, n_jobs, seed=3)
    rng = np.random.default_rng(5)
    specs = [dag_single(s) for s in rng.exponential(0.1, size=n_jobs)]
    return farm_mod.simulate(cfg, arr, specs)


def test_rank_fallbacks_zero_on_uncongested_farm():
    res = _poisson_farm(max_jobs=300, n_jobs=300)
    assert res.n_finished == 300
    assert res.rank_fallbacks == 0


def test_rank_fallbacks_count_every_pass_on_small_table():
    res = _poisson_farm(max_jobs=100, n_jobs=100)
    assert res.n_finished == 100
    assert res.rank_fallbacks > 0

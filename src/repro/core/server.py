"""Server model primitives (paper §III-A).

Each server: C cores (one task per core, paper's processing-unit model), a
local FIFO queue, and a hierarchical ACPI power state.  All operations are
dense/masked over the whole farm — no per-server control flow.

Queue representation is TASK-MAJOR: a queued task is simply a task with
``status == QUEUED``; its FIFO position is the global ``enqueue_seq`` stamp
it received on push, and the farm only keeps a per-server occupancy counter
(``q_len``) plus the global stamp counter (``q_seq``).  Pushes stamp
sequence numbers elementwise in task space and starts resolve FIFO order by
ranking queued tasks per server — there is no (N, Q) ring to scatter slots
into or gather task ids out of, which removes the two core->task scatters
per starting step and all ring-buffer state from the hot loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .types import (INF, JobTable, ServerFarm, SimConfig, SrvState,
                    TaskStatus, replace)

__all__ = ["queue_push", "queue_push_many", "queued_rank", "compact_mask",
           "try_start", "wake_latency", "begin_wake", "begin_wake_mask",
           "refresh_idle_state"]


def queue_push(farm: ServerFarm, cfg: SimConfig, server, tid):
    """Push one task onto ``server``'s queue (scalar args; the seed
    reference drain path).  Returns (farm, ok, seq): ``seq`` is the FIFO
    stamp the caller writes into ``jobs.enqueue_seq[tid]`` when ok."""
    full = farm.q_len[server] >= cfg.local_q
    q_len = farm.q_len.at[server].add(jnp.where(full, 0, 1))
    q_seq = farm.q_seq + jnp.where(full, 0, 1).astype(jnp.int32)
    dropped = farm.dropped + jnp.where(full, 1, 0).astype(jnp.int32)
    return (replace(farm, q_len=q_len, q_seq=q_seq, dropped=dropped),
            ~full, farm.q_seq)


def queue_push_many(farm: ServerFarm, cfg: SimConfig, servers, tids, valid):
    """Push up to K tasks onto their servers' queues in one pass.

    servers/tids (K,) int32, valid (K,) bool.  Tasks destined to the same
    server take FIFO stamps in position order (matching K sequential
    queue_push calls); once a queue fills, later same-server tasks drop.
    Returns (farm, ok (K,) bool, seq (K,) int32 — the stamp for each
    accepted task, garbage where ~ok).
    """
    K = tids.shape[0]
    N, Q = cfg.n_servers, cfg.local_q
    s = jnp.clip(servers, 0)
    # rank among earlier valid tasks bound for the same server
    pos = jnp.arange(K)
    same = valid[None, :] & valid[:, None] & (s[None, :] == s[:, None])
    rank = jnp.sum(same & (pos[None, :] < pos[:, None]), axis=1)
    # sequential equivalence: drops only start once the queue is full
    ok = valid & (farm.q_len[s] + rank < Q)
    seq = farm.q_seq + jnp.cumsum(ok.astype(jnp.int32)) - 1
    row = jnp.where(ok, s, N)                       # drop-sentinel row
    q_len = farm.q_len.at[row].add(1, mode="drop")
    q_seq = farm.q_seq + ok.sum().astype(jnp.int32)
    dropped = farm.dropped + (valid & ~ok).sum().astype(jnp.int32)
    return (replace(farm, q_len=q_len, q_seq=q_seq, dropped=dropped),
            ok, seq)


def wake_latency(cfg: SimConfig, state):
    sp = cfg.server_power
    table = jnp.asarray([0.0, 0.0, sp.t_wake_pkg_c6, sp.t_wake_s3,
                         sp.t_wake_off, 0.0], cfg.time_dtype)
    return table[state]


def begin_wake(farm: ServerFarm, cfg: SimConfig, server, now):
    """Start waking ``server`` if it is in a sleep state (idempotent)."""
    st = farm.srv_state[server]
    sleeping = (st == SrvState.PKG_C6) | (st == SrvState.S3) | (st == SrvState.OFF)
    lat = wake_latency(cfg, st)
    srv_state = farm.srv_state.at[server].set(
        jnp.where(sleeping, SrvState.WAKING, st))
    srv_wake_at = farm.srv_wake_at.at[server].set(
        jnp.where(sleeping, now + lat, farm.srv_wake_at[server]))
    wake_count = farm.wake_count.at[server].add(
        jnp.where(sleeping, 1, 0).astype(jnp.int32))
    return replace(farm, srv_state=srv_state, srv_wake_at=srv_wake_at,
                   wake_count=wake_count)


def begin_wake_mask(farm: ServerFarm, cfg: SimConfig, mask, now):
    """Masked whole-farm begin_wake: start waking every sleeping server in
    ``mask`` (N,).  Idempotent like the scalar version."""
    st = farm.srv_state
    sleeping = mask & ((st == SrvState.PKG_C6) | (st == SrvState.S3)
                       | (st == SrvState.OFF))
    lat = wake_latency(cfg, st)
    return replace(
        farm,
        srv_state=jnp.where(sleeping, SrvState.WAKING, st),
        srv_wake_at=jnp.where(sleeping, now + lat, farm.srv_wake_at),
        wake_count=farm.wake_count + sleeping.astype(jnp.int32))


def queued_rank(jobs: JobTable, cfg: SimConfig, queued, q_seq):
    """(JT,) FIFO rank of each queued task among the queued tasks of ITS
    server (0 = head), by enqueue_seq; garbage where ~queued.

    One argsort by (server, seq) makes same-server tasks contiguous in
    FIFO order, so the rank is position minus the server run's first
    position — O(JT log JT) in task space, independent of N and with only
    JT-row scatters (vs the (N, Q) ring's core-space gathers/scatters).

    Stamps sort by their wrap-safe int32 distance to the farm's CURRENT
    counter ``q_seq``: live stamps were issued within the last JT < 2^31
    pushes (a task enqueues at most once — build_jobs guards the table
    width), so ``stamp - q_seq`` is a strictly negative int32 even when
    the counter has wrapped, and FIFO order survives wrap-around instead
    of silently inverting at the 2^31 boundary.
    """
    JT = queued.shape[0]
    N = cfg.n_servers
    srv = jnp.clip(jobs.server, 0)
    # lexicographic (server, seq) sort via two STABLE argsorts — a fused
    # srv*(JT+1)+seq key would overflow int32 once n_servers·JT passes
    # 2^31 (a 20K-server farm with a ~100K-task table); seq (< JT) and
    # srv (< N) are individually safe
    imax = jnp.iinfo(jnp.int32).max
    rel_seq = jobs.enqueue_seq - q_seq          # wrap-safe, < 0 for live
    by_seq = jnp.argsort(jnp.where(queued, rel_seq, imax))
    order = by_seq[jnp.argsort(
        jnp.where(queued[by_seq], srv[by_seq], imax), stable=True)]
    srv_o = jnp.where(queued[order], srv[order], N)     # sentinel last
    first = jnp.full((N,), JT, jnp.int32).at[srv_o].min(
        jnp.arange(JT, dtype=jnp.int32), mode="drop")
    rank_o = jnp.arange(JT, dtype=jnp.int32) \
        - first[jnp.clip(srv_o, 0, N - 1)]
    return jnp.zeros((JT,), jnp.int32).at[order].set(rank_o)


# largest K * JT that compact_mask searches by a (K, JT) compare-and-count;
# above it, a binary search while its K * log2(JT) probes are fewer than
# JT, else one scatter of JT updates.  On a TPU v5e the compare costs about
# 4 ps an element, a probe or an update about 6-9 ns an index, so the
# compare wins at JT = 500 (under vmap too) and at JT = 100,000 with K = 8,
# and the search at JT = 100,000 with K = 128
_COMPARE_ALL_MAX = 1 << 21


def compact_mask(mask, K: int):
    """Gather the first K set ids of ``mask`` (JT,) into a (K,) batch in
    ascending order.  The k-th set id is where the running count
    ``cumsum(mask)`` first reaches k + 1: found by a (K, JT)
    compare-and-count on small tables and a binary search with K indices
    per probe on large ones, so no index op runs per row.  Only a batch
    nearly as wide as the table scatters the ids, one update per row.
    Returns (tids (K,), -1 in unused slots; valid (K,); covered — True iff
    mask.sum() <= K, i.e. the batch holds EVERY set id)."""
    JT = mask.shape[0]
    count = jnp.cumsum(mask, dtype=jnp.int32)
    covered = count[-1] <= K
    if K * JT > _COMPARE_ALL_MAX and K * JT.bit_length() >= JT:
        tids = jnp.full((K,), -1, jnp.int32).at[
            jnp.where(mask, count - 1, K)].set(
            jnp.arange(JT, dtype=jnp.int32), mode="drop")
        return tids, tids >= 0, covered
    k = jnp.arange(K, dtype=jnp.int32)
    valid = k < count[-1]
    method = "compare_all" if K * JT <= _COMPARE_ALL_MAX else "scan"
    tids = jnp.searchsorted(count, k + 1, method=method).astype(jnp.int32)
    return jnp.where(valid, tids, -1), valid, covered


# compact-batch size for start resolution: when at most this many tasks
# are QUEUED farm-wide (the overwhelmingly common case — drains are
# bounded by ready_per_step and starts immediately consume what they
# push), the start pass runs over a compacted batch of the queued tasks;
# only genuinely congested steps pay the job-table-wide argsort rank
COMPACT_Q = 128


def _start_dense(farm: ServerFarm, cfg: SimConfig, jobs: JobTable, now,
                 freq, n_start, queued):
    """Job-table-space start, for congested steps (more than COMPACT_Q
    queued tasks) and tables no wider than COMPACT_Q: the argsort FIFO
    rank over every row, elementwise task side, (N, C) core side."""
    N, C = cfg.n_servers, cfg.n_cores
    JT = jobs.status.shape[0]
    rank = queued_rank(jobs, cfg, queued, farm.q_seq)
    srv = jnp.clip(jobs.server, 0)
    # task side: elementwise
    start_t = queued & (rank < n_start[srv])                    # (JT,)
    if freq is None:
        svc = jobs.service / cfg.core_freq
    else:
        svc = jobs.service / freq[srv]
    end_t = (now + svc).astype(jobs.task_end.dtype)
    status = jnp.where(start_t, TaskStatus.RUNNING, jobs.status)
    task_end = jnp.where(start_t, end_t, jobs.task_end)
    start_at = jnp.where(
        start_t, jnp.asarray(now, jobs.start_at.dtype), jobs.start_at)
    jobs = replace(jobs, status=status, task_end=task_end,
                   start_at=start_at)

    # core side: the r-th starting task of server s takes the r-th free
    # core; build the (s, r) -> task table with one scatter, then fill
    # cores elementwise (the busy_until expression is the same float math
    # as end_t, so task_end stays bit-equal)
    free = farm.core_busy_until >= INF                          # (N, C)
    row = jnp.where(start_t, srv, N)
    col = jnp.clip(jnp.where(start_t, rank, 0), 0, C - 1)
    tid_at = jnp.full((N, C), JT, jnp.int32).at[row, col].set(
        jnp.arange(JT, dtype=jnp.int32), mode="drop")
    fr = jnp.cumsum(free, axis=1) - 1                           # free rank
    start_c = free & (fr < n_start[:, None])                    # (N, C)
    tid_c = jnp.take_along_axis(tid_at, jnp.clip(fr, 0, C - 1), axis=1)
    if freq is None:
        svc_c = jobs.service[jnp.clip(tid_c, 0, JT - 1)] / cfg.core_freq
    else:
        svc_c = jobs.service[jnp.clip(tid_c, 0, JT - 1)] / freq[:, None]
    busy_until = (now + svc_c).astype(farm.core_busy_until.dtype)
    farm = replace(
        farm,
        core_busy_until=jnp.where(start_c, busy_until, farm.core_busy_until),
        q_len=farm.q_len - n_start)
    return farm, jobs


def _start_batch(farm: ServerFarm, cfg: SimConfig, jobs: JobTable, now,
                 freq, n_start, tids, valid):
    """Batch-space start over ``tids`` (K,), the compacted queued tasks,
    which must hold EVERY queued task: FIFO ranks from a pairwise count
    inside the batch, then K-slot gathers and scatters for the task and
    core sides — no index op per job-table row or per core.  Gives the
    farm and jobs of _start_dense on the same state, bit for bit."""
    N = cfg.n_servers
    JT = jobs.status.shape[0]
    tq = jnp.clip(tids, 0)
    srv = jnp.clip(jobs.server[tq], 0)                          # (K,)
    seq = jobs.enqueue_seq[tq]
    # pairwise FIFO rank inside the batch — equal to the dense rank
    # because the batch covers every queued task; the compare is the
    # wrap-safe int32 diff (see queued_rank)
    same = valid[None, :] & valid[:, None] & (srv[None, :] == srv[:, None])
    rank = jnp.sum(same & ((seq[None, :] - seq[:, None]) < 0), axis=1,
                   dtype=jnp.int32)
    start = valid & (rank < n_start[srv])
    # the float math of _start_dense, per starting task
    if freq is None:
        svc = jobs.service[tq] / cfg.core_freq
    else:
        svc = jobs.service[tq] / freq[srv]

    # task side: a K-slot start mask for the elementwise status and
    # start_at; the end stamps scattered at their rows
    row = jnp.where(start, tids, JT)
    start_t = jnp.zeros((JT,), bool).at[row].set(True, mode="drop")
    jobs = replace(
        jobs,
        status=jnp.where(start_t, TaskStatus.RUNNING, jobs.status),
        task_end=jobs.task_end.at[row].set(
            (now + svc).astype(jobs.task_end.dtype), mode="drop"),
        start_at=jnp.where(start_t, jnp.asarray(now, jobs.start_at.dtype),
                           jobs.start_at))

    # core side: the task of rank r takes its server's r-th free core
    free = farm.core_busy_until[srv] >= INF                     # (K, C)
    fr = jnp.cumsum(free, axis=1) - 1
    core = jnp.argmax(free & (fr == rank[:, None]), axis=1)
    busy_until = farm.core_busy_until.at[jnp.where(start, srv, N), core].set(
        (now + svc).astype(farm.core_busy_until.dtype), mode="drop")
    return replace(farm, core_busy_until=busy_until,
                   q_len=farm.q_len - n_start), jobs


def try_start(farm: ServerFarm, cfg: SimConfig, jobs: JobTable, now,
              freq=None):
    """Start as many queued tasks as there are free cores, FIFO per
    server: a queued task starts iff its per-server FIFO rank is below
    its server's free-core count, and the r-th starting task of a server
    takes its r-th free core.

    When at most COMPACT_Q tasks are queued farm-wide (queues are
    near-empty in steady state), the pass works in batch space over the
    compacted queued tasks (_start_batch), so its cost follows the work
    in flight, not the job table.  Congested steps with more queued tasks
    than that, and tables no wider than COMPACT_Q, take the job-table-space
    argsort rank (_start_dense) and count one ``farm.rank_fallbacks``.
    Both paths define the identical rank and float math, so the runtime
    choice never changes the dynamics.

    ``freq`` (N,) optionally overrides the scalar cfg.core_freq with a
    per-server effective frequency (thermal throttling); None keeps the
    untrottled expression bit-exact.

    Returns (farm, jobs).
    """
    JT = jobs.status.shape[0]
    awake = (farm.srv_state == SrvState.ACTIVE) \
        | (farm.srv_state == SrvState.IDLE)
    free = farm.core_busy_until >= INF                          # (N, C)
    n_free = free.sum(axis=1, dtype=jnp.int32)
    n_start = jnp.where(awake, jnp.minimum(n_free, farm.q_len), 0)

    def do(args):
        farm, jobs = args
        queued = jobs.status == TaskStatus.QUEUED

        def dense(args):
            farm, jobs = args
            farm, jobs = _start_dense(farm, cfg, jobs, now, freq, n_start,
                                      queued)
            return (replace(farm, rank_fallbacks=farm.rank_fallbacks + 1),
                    jobs)

        if JT <= COMPACT_Q:
            return dense(args)

        tids, valid, covered = compact_mask(queued, COMPACT_Q)

        def batch(args):
            farm, jobs = args
            return _start_batch(farm, cfg, jobs, now, freq, n_start, tids,
                                valid)

        return jax.lax.cond(covered, batch, dense, args)

    return jax.lax.cond((n_start > 0).any(), do, lambda a: a, (farm, jobs))


def refresh_idle_state(farm: ServerFarm, cfg: SimConfig, now):
    """Recompute ACTIVE/IDLE for awake servers; stamp idle_since on the
    ACTIVE->IDLE edge (the delay timer anchor, paper §IV-B)."""
    busy = (farm.core_busy_until < INF).any(axis=1)
    awake = (farm.srv_state == SrvState.ACTIVE) | (farm.srv_state == SrvState.IDLE)
    new_state = jnp.where(
        awake, jnp.where(busy, SrvState.ACTIVE, SrvState.IDLE), farm.srv_state)
    went_idle = awake & (farm.srv_state == SrvState.ACTIVE) & ~busy
    idle_since = jnp.where(went_idle, now, farm.srv_idle_since)
    return replace(farm, srv_state=new_state, srv_idle_since=idle_since)

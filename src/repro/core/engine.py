"""The event-driven simulation engine, vectorized for TPU (DESIGN.md §3).

The paper's sequential priority-queue loop becomes:

    while not done:
        t_next = min over all dense candidate-event arrays      (VPU reduce)
        accrue energy for (t_next - t)                          (exact: state
                                                                 is piecewise
                                                                 constant)
        apply ALL events with time <= t_next as masked updates  (dense)

Semantics are identical to a heap-based DES — we always advance to the
global minimum event time, so there is no time-discretization error.  The
per-iteration work is O(state) streaming instead of O(log n) pointer
chasing, which is exactly the trade the TPU wants; `kernels/dcsim_step.py`
fuses the min-reduction + energy accrual + completion free of the hot loop
into one VMEM pass (enabled with ``cfg.use_kernel``).

Event sources:
  job arrival            jobs.arrival[arr_ptr]
  task completion        min core_busy_until
  wake completion        min srv_wake_at
  delay-timer expiry     scheduler.next_timer_event
  flow completion        min flows.done_at          (network mode)
  throttle crossing      thermal.next_crossing      (thermal throttling)
  pending work           t (now) when READY tasks await placement

Macro-stepping (``cfg.events_per_step``): one jitted sim_step retires up
to K successive event times.  The first K-1 run a CHEAP core — the full
advance/wakeup/completion/admission/drain/start pipeline minus the
expensive passes (flow completion + rate recompute, flow spawning,
throttle-crossing handling, latency-histogram binning) — and a gate stops
the chew whenever the pending event needs one of those, handing it to the
full step that always closes the macro-step.  The gating is conservative,
so final states are identical for every K; only the per-step event count
changes.  Latency binning is deferred to once per macro-step (the finish
arrays identify every completion since the macro began); window accrual
stays exact per interval.

Engine regions: each phase of a step runs under a ``jax.named_scope``,
nested inside ``cheap_core``/``full_step``, and the name reaches the
compiled program's ``op_name`` metadata, so a profile's device time can be
read per region (``bench/regions.py``); the innermost scope names an op:
  next_event    the cheap gate, next_event_time, the done check
  advance       the interval advance (energy, residency)
  completions   wakeups, task completions, flow completions
  admission     deferred releases, arrivals
  drain_start   drain into server queues, task starts
  power         idle refresh, provisioning, WASP, delay timers
  flows         rate recompute and switch states; the flow drain and
                switch energy inside the advance
  thermal       throttle latch, setpoint tick; the RC integrator and
                cooling power inside the advance
  trace_flush   the flight recorder's ring write
  telemetry     latency binning; the window accrual inside the advance

Scheduling/assignment model: the global scheduler assigns servers to ALL
tasks of a job at arrival (policy-driven).  When a parent task finishes,
each DAG edge either decrements the child's dep_count immediately (no
network / same server / zero bytes) or spawns a flow parent_server ->
child_server; the flow's completion decrements it.  dep_count==0 turns a
task READY; READY tasks are drained (bounded per step) into their server's
task-major FIFO queue (status QUEUED + enqueue_seq stamp — see server.py),
waking sleeping servers on demand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import network as net_mod
from . import power, scheduler, server, telemetry
from . import thermal as thermal_mod
from . import trace as trace_mod
from .types import (INF, JobTable, SchedPolicy,
                    ServerFarm, SimConfig, SimState,
                    SleepPolicy, SrvState, TaskStatus, TraceKind,
                    init_farm, init_flows, init_net, init_sched, replace)


# ==========================================================================
# helpers
# ==========================================================================

def _active_jobs(jobs: JobTable) -> jnp.ndarray:
    """Tasks in flight (READY/QUEUED/RUNNING) — the provisioning load
    metric."""
    s = jobs.status
    return ((s == TaskStatus.READY) | (s == TaskStatus.QUEUED)
            | (s == TaskStatus.RUNNING)).sum()


def _pending_jobs(jobs: JobTable) -> jnp.ndarray:
    """Tasks waiting for a core (READY/QUEUED, excluding RUNNING) — the
    WASP pool metric (paper §IV-C: 'pending jobs per server'); counting
    running tasks would stop multi-core servers from ever consolidating."""
    s = jobs.status
    return ((s == TaskStatus.READY) | (s == TaskStatus.QUEUED)).sum()


def _next_arrival(jobs: JobTable) -> jnp.ndarray:
    J = jobs.arrival.shape[0]
    return jnp.where(jobs.arr_ptr < J,
                     jobs.arrival[jnp.clip(jobs.arr_ptr, 0, J - 1)], INF)


def _deferral_on(cfg: SimConfig) -> bool:
    """Static: carbon-aware deferral machinery is traced only when the
    policy is CARBON_AWARE AND a finite signal threshold arms it —
    CARBON_AWARE with the default defer_threshold=INF is plain
    LOAD_BALANCE placement with zero extra step cost."""
    return cfg.sched_policy == SchedPolicy.CARBON_AWARE \
        and cfg.thermal.deferral


def _farm_candidates(state: SimState, cfg: SimConfig):
    """Candidate next-event time from arrivals + farm sources, with the
    READY/startable pin to ``now`` — everything the cheap core handles."""
    cands = [
        _next_arrival(state.jobs),
        state.farm.core_busy_until.min(),
        state.farm.srv_wake_at.min(),
        scheduler.next_timer_event(state.farm, cfg),
    ]
    if _deferral_on(cfg):
        # deferred-job releases (solved carbon down-crossing / deadline)
        # are ordinary events: the cheap core runs the release pass too
        cands.append(state.jobs.admit_at.min())
    if cfg.thermal.has_ctrl:
        # setpoint-controller ticks: applied right after the interval
        # advance in both the cheap and the full step
        cands.append(state.thermal.ctrl_next)
    t_next = functools.reduce(jnp.minimum, cands)
    # pending READY tasks (or queued work on awake free cores) execute "now"
    ready = (state.jobs.status == TaskStatus.READY).any()
    awake = (state.farm.srv_state == SrvState.ACTIVE) \
        | (state.farm.srv_state == SrvState.IDLE)
    startable = (awake & (state.farm.q_len > 0)
                 & (state.farm.core_busy_until >= INF).any(axis=1)).any()
    t_next = jnp.where(ready | startable, state.t, t_next)
    return jnp.maximum(t_next, state.t).astype(cfg.time_dtype)


def next_event_time(state: SimState, cfg: SimConfig) -> jnp.ndarray:
    t_next = _farm_candidates(state, cfg)
    if cfg.has_network:
        t_next = jnp.minimum(t_next, state.flows.done_at.min())
    if cfg.thermal.throttling:
        # throttle-threshold crossings are real events: the RC exponential
        # is solved for the crossing time, so throttling engages exactly
        # when the temperature reaches it, not at the next unrelated event
        t_next = jnp.minimum(t_next, thermal_mod.next_crossing(state, cfg))
    return jnp.maximum(t_next, state.t).astype(cfg.time_dtype)


# ==========================================================================
# interval advance (accrual phase)
# ==========================================================================

def _advance_interval(state: SimState, cfg: SimConfig, tc, t_next):
    """Integrate everything over the piecewise-constant interval
    [t, t_next) in one shared-pass sweep, then set t := t_next.

    The per-server power, busy count, and state one-hot are computed ONCE
    and shared by the energy/residency accrual, the telemetry window
    columns, and the thermal RC integrator (the seed step recomputed them
    in each subsystem).  With ``cfg.use_kernel`` the energy accrual +
    completion free runs in the fused Pallas kernel."""
    farm = state.farm
    dt = t_next - state.t
    with jax.named_scope("f32_domain"):
        # intentional exit from the clock domain: physics/energy math runs
        # in f32 regardless of time_dtype (audited — analysis/jaxpr_audit)
        dtf = dt.astype(jnp.float32)
    telemetry_on = cfg.telemetry.enabled
    thermal_on = cfg.thermal.enabled
    throttled = state.thermal.throttled if thermal_on else None
    need_p = telemetry_on or thermal_on or not cfg.use_kernel
    p_busy = power.server_power(farm, cfg, throttled) if need_p else None
    onehot = (farm.srv_state[:, None]
              == jnp.arange(SrvState.NUM)[None, :]).astype(jnp.float32)
    thermal_ctx = t_end = p_cool = p_sw_t = None
    if thermal_on:
        # one RC evaluation (recirculated inlet + exponential) and one
        # CRAC/COP evaluation shared by the telemetry columns and the
        # thermal integrator
        with jax.named_scope("thermal"):
            tcfg = cfg.thermal
            target = p_busy[0] * tcfg.r_th \
                + thermal_mod.inlet_temps(state.thermal, tcfg, state.t)
            alpha = 1.0 - jnp.exp(-dtf / tcfg.tau_th)
            t_end = state.thermal.t_srv \
                + (target - state.thermal.t_srv) * alpha
            p_sw_t = power.switch_power(state.net, cfg).sum() \
                if cfg.has_network else jnp.float32(0.0)
            p_cool = thermal_mod.cooling_power(p_busy[0], p_sw_t,
                                               state.thermal, tcfg)
            thermal_ctx = (target, alpha, t_end, p_cool)

    telem = state.telem
    if telemetry_on:
        # window metrics integrate the PRE-advance state over [t, t_next)
        # (piecewise constant, same exactness as the energy accrual)
        with jax.named_scope("telemetry"):
            wvals = telemetry.window_values(state, cfg, dt, p_busy, onehot,
                                            thermal_ctx)
            widx = telemetry.window_index(state.t, dt, cfg.telemetry)
            # intervals past the window horizon still clamp into the last
            # window (conservation: columns keep integrating to the run
            # totals) but the clamped seconds are counted so summarize
            # can flag/NaN the contaminated last-window time-averages
            spill = telemetry.window_spill(state.t, dt, cfg.telemetry)
            telem = replace(telem, win=telem.win.at[widx].add(wvals),
                            win_overflow=telem.win_overflow + spill)

    if cfg.use_kernel:
        if cfg.time_dtype != jnp.float32:
            raise ValueError(
                "cfg.use_kernel requires time_dtype=float32: the fused "
                "advance kernel computes in f32, and the core_busy_until "
                "round-trip would silently destroy f64 precision")
        from ..kernels import dcsim_step, interpret_mode
        sp = cfg.server_power
        table = jnp.asarray([sp.p_base, sp.p_base, sp.p_pkg_c6, sp.p_s3,
                             sp.p_off, sp.p_wake], jnp.float32)
        thr = throttled if cfg.thermal.throttling else None
        nb, _done, en, bs, _cand = dcsim_step.dcsim_advance(
            farm.core_busy_until.astype(jnp.float32), farm.srv_state,
            farm.energy, farm.busy_core_seconds, state.t, t_next, table,
            sp.p_core_active, sp.p_core_idle,
            farm.srv_wake_at.astype(jnp.float32),
            farm.srv_idle_since.astype(jnp.float32),
            farm.srv_tau.astype(jnp.float32), throttled=thr,
            throttle_power_scale=cfg.thermal.throttle_power_scale,
            interpret=interpret_mode())
        farm = replace(farm,
                       core_busy_until=nb.astype(cfg.time_dtype),
                       energy=en, busy_core_seconds=bs,
                       residency=farm.residency + onehot * dtf)
    else:
        farm = power.accrue_server_energy(farm, cfg, dt, p_busy, onehot)

    net, flows = state.net, state.flows
    if cfg.has_network:
        with jax.named_scope("flows"):
            net = power.accrue_switch_energy(net, cfg, dt)
            # drain the fluid model over the interval (rates are
            # piecewise constant, fixed at the last recompute)
            flows = net_mod.advance_flows(flows, dt)
    therm = state.thermal
    if thermal_on:
        with jax.named_scope("thermal"):
            therm = thermal_mod.advance(therm, cfg, p_busy[0], p_sw_t,
                                        state.t, dt, t_new=t_end,
                                        p_cool=p_cool)
    return replace(state, farm=farm, net=net, flows=flows, thermal=therm,
                   telem=telem, t=t_next)


# ==========================================================================
# event appliers
# ==========================================================================

def _rebuild_job_completion(jobs: JobTable, cfg: SimConfig, now):
    """(tasks_done, job_finish) rebuilt from task statuses: DONE is
    terminal (completions and drops both land there), so the per-job count
    is a pure function of the current statuses.  Newly-complete jobs get
    job_finish stamped at ``now``."""
    T = cfg.tasks_per_job
    tasks_done = ((jobs.status == TaskStatus.DONE)
                  & jobs.valid).reshape(-1, T).sum(axis=1,
                                                   dtype=jnp.int32)
    n_valid_tasks = jobs.valid.reshape(-1, T).sum(axis=1, dtype=jnp.int32)
    job_complete = (tasks_done >= n_valid_tasks) & (tasks_done > 0)
    job_finish = jnp.where(job_complete & (jobs.job_finish >= INF),
                           now, jobs.job_finish)
    return tasks_done, job_finish


def _promote_ready(jobs: JobTable, dep_count, cfg: SimConfig):
    """BLOCKED -> READY where deps are now satisfied (arrived jobs only).

    Carbon-deferred jobs are NOT promotable even though arr_ptr has moved
    past them (admission consumed their arrival slot): their zero-dep
    roots must stay BLOCKED until _apply_releases admits them — without
    the parked mask, any DAG-edge resolution between arrival and release
    would flip the parked roots READY on the server=-1 sentinel, running
    the job mid-high-carbon-window with no placement and no telemetry."""
    T = cfg.tasks_per_job
    arrived = jnp.arange(jobs.status.shape[0]) // T < jobs.arr_ptr
    if _deferral_on(cfg):
        parked = jnp.repeat(jobs.admit_at < INF / 2, T)
        arrived = arrived & ~parked
    ready = (jobs.status == TaskStatus.BLOCKED) & (dep_count <= 0) & arrived
    return jnp.where(ready, TaskStatus.READY, jobs.status)


def _apply_wakeups(farm: ServerFarm, cfg, now):
    done = (farm.srv_state == SrvState.WAKING) & (farm.srv_wake_at <= now)
    return replace(
        farm,
        srv_state=jnp.where(done, SrvState.IDLE, farm.srv_state),
        srv_wake_at=jnp.where(done, INF, farm.srv_wake_at),
        srv_idle_since=jnp.where(done, now, farm.srv_idle_since))


def _apply_completions(state: SimState, cfg: SimConfig, tc=None,
                       recs=None):
    """Handle all tasks whose task_end <= now.  Marks tasks DONE, updates
    job bookkeeping, and resolves DAG edges (immediate dep decrement or
    flow spawn).

    Everything is elementwise in task space: a RUNNING task with
    task_end <= now is complete (task_end was stamped with its core's
    busy_until at start), and its DAG edges live on task rows too — no
    core->task gather or scatter anywhere.  The core array just frees its
    expired slots elementwise."""
    farm, jobs, flows, net = state.farm, state.jobs, state.flows, state.net
    now = state.t
    T = cfg.tasks_per_job

    # free the cores (elementwise; a no-op for slots the fused kernel
    # already freed during the advance)
    done_core = farm.core_busy_until <= now                       # (N, C)
    farm = replace(
        farm,
        core_busy_until=jnp.where(done_core, INF, farm.core_busy_until))

    # mark DONE + record finish time (elementwise in task space)
    done_task = (jobs.status == TaskStatus.RUNNING) \
        & (jobs.task_end <= now)
    status = jnp.where(done_task, TaskStatus.DONE, jobs.status)
    finish = jnp.where(done_task, now, jobs.finish)
    jobs = replace(jobs, status=status, finish=finish)
    tasks_done, job_finish = _rebuild_job_completion(jobs, cfg, now)

    if cfg.trace.enabled:
        JT = jobs.status.shape[0]
        trace_mod.stage(
            recs, done_task, TraceKind.FINISH, jobs.server,
            jnp.arange(JT, dtype=jnp.int32), now - jobs.start_at)
        new_jf = (jobs.job_finish >= INF / 2) & (job_finish < INF / 2)
        J = job_finish.shape[0]
        trace_mod.stage(
            recs, new_jf, TraceKind.JOB_FINISH, -1,
            jnp.arange(J, dtype=jnp.int32), job_finish - jobs.arrival)
    jobs = replace(jobs, tasks_done=tasks_done, job_finish=job_finish)

    if T > 1:
        jobs, flows, net = _resolve_done_edges(
            jobs, flows, net, cfg, tc, done_task, now, recs)
    return replace(state, farm=farm, jobs=jobs, flows=flows, net=net)


def _resolve_done_edges(jobs, flows, net, cfg, tc, done_task, now,
                        recs=None):
    """DAG edges of tasks completed this step: immediate dep decrement or
    flow spawn, then BLOCKED -> READY.  Works on the COMPLETING tasks'
    rows only: at most N·C tasks can finish simultaneously (each RUNNING
    task occupies a core), so when the task table is wider than the core
    array the done set is first compacted into a (N·C,)-batch — exact,
    not a heuristic — and all edge math runs on (Kd, D) rows.  (The seed
    walked (N·C, D) core slots via a core->task gather; the task table
    carries the same information without the gather.)  Single-task jobs
    have no edges, so this is only traced for T > 1 and only runs when a
    task finished."""
    JT = jobs.status.shape[0]
    Kd = min(JT, cfg.n_servers * cfg.n_cores)
    D = jobs.children.shape[1]
    # flow-spawn records leave the cond as data (mask + payload lanes);
    # the identity branch hands back all-false/zero lanes
    spawn0 = (jnp.zeros((Kd * D,), bool),
              jnp.full((Kd * D,), -1, jnp.int32),
              jnp.full((Kd * D,), -1, jnp.int32),
              jnp.zeros((Kd * D,), jobs.edge_bytes.dtype))

    def resolve(args):
        jobs, flows, net, _ = args
        if Kd < JT:
            tid_b, valid_b, _ = server.compact_mask(done_task, Kd)
            tq = jnp.clip(tid_b, 0)
            ch = jobs.children[tq]                                # (Kd, D)
            eb = jobs.edge_bytes[tq]
            ch_valid = (ch >= 0) & valid_b[:, None] \
                & ~jobs.edge_sent[tq]
            edge_sent = jobs.edge_sent.at[
                jnp.where(valid_b, tid_b, JT)].set(
                jobs.edge_sent[tq] | ch_valid, mode="drop")
            src_of = jobs.server[tq]                              # (Kd,)
        else:
            ch = jobs.children                                    # (JT, D)
            eb = jobs.edge_bytes
            ch_valid = (ch >= 0) & done_task[:, None] & ~jobs.edge_sent
            edge_sent = jobs.edge_sent | ch_valid
            src_of = jobs.server

        dep_count = jobs.dep_count
        if cfg.has_network:
            # same-server or zero-byte edges resolve immediately; others
            # spawn flows parent_server -> child_server
            dst_srv = jobs.server[jnp.clip(ch, 0)]                # (Kd, D)
            needs_flow = ch_valid & (eb > 0) & (dst_srv != src_of[:, None])
            immediate = ch_valid & ~needs_flow
            dep_count = dep_count.at[jnp.clip(ch, 0).reshape(-1)].add(
                -immediate.reshape(-1).astype(jnp.int32), mode="drop")

            flat = needs_flow.reshape(-1)
            f_src = jnp.broadcast_to(src_of[:, None], ch.shape).reshape(-1)
            f_dst = dst_srv.reshape(-1)
            f_bytes = eb.reshape(-1)
            f_child = ch.reshape(-1)

            no_fail = jnp.zeros_like(flat)
            if cfg.use_vectorized_hot_loop:
                def spawn(args):
                    flows, net, _ = args
                    flows, net, ok = net_mod.spawn_flows_many(
                        flows, net, tc, cfg, flat, f_src, f_dst, f_bytes,
                        f_child, now)
                    return flows, net, flat & ~ok

                # most steps spawn nothing — gate the dense pass
                flows, net, failed = jax.lax.cond(
                    flat.any(), spawn, lambda a: a, (flows, net, no_fail))
            else:
                def spawn_one(i, carry):
                    flows, net, failed = carry

                    def do(args):
                        flows, net, failed = args
                        fl, nt, ok = net_mod.spawn_flow(
                            flows, net, tc, cfg, f_src[i], f_dst[i],
                            f_bytes[i], f_child[i], now)
                        return fl, nt, failed.at[i].set(~ok)
                    return jax.lax.cond(flat[i], do, lambda a: a,
                                        (flows, net, failed))

                flows, net, failed = jax.lax.fori_loop(
                    0, flat.shape[0], spawn_one, (flows, net, no_fail))

            # a full FlowTable drop-resolves the edge like the queue-drop
            # path: the child's dep decrements immediately (the results
            # simply never ship) instead of leaving it BLOCKED forever;
            # the spawn primitives count the drop in flows.flows_dropped
            dep_count = dep_count.at[jnp.where(failed, f_child, JT)].add(
                -1, mode="drop")
            spawned = (flat & ~failed, f_src, f_child, f_bytes)
        else:
            dep_count = dep_count.at[jnp.clip(ch, 0).reshape(-1)].add(
                -ch_valid.reshape(-1).astype(jnp.int32), mode="drop")
            spawned = spawn0

        status = _promote_ready(jobs, dep_count, cfg)
        jobs = replace(jobs, status=status, dep_count=dep_count,
                       edge_sent=edge_sent)
        return jobs, flows, net, spawned

    jobs, flows, net, spawned = jax.lax.cond(
        done_task.any(), resolve, lambda a: a, (jobs, flows, net, spawn0))
    if cfg.trace.enabled and cfg.has_network:
        sm, s_src, s_child, s_bytes = spawned
        trace_mod.stage(recs, sm, TraceKind.FLOW_SPAWN, s_src, s_child,
                        s_bytes)
    return jobs, flows, net


def _apply_flow_completions(state: SimState, cfg: SimConfig, recs=None):
    flows, fin = net_mod.complete_flows(state.flows, state.t)

    def resolve(jobs):
        child = jnp.where(fin, flows.child, -1)
        dep_count = jobs.dep_count.at[jnp.clip(child, 0)].add(
            -fin.astype(jnp.int32), mode="drop")
        status = _promote_ready(jobs, dep_count, cfg)
        return replace(jobs, dep_count=dep_count, status=status)

    jobs = jax.lax.cond(fin.any(), resolve, lambda j: j, state.jobs)
    if cfg.trace.enabled:
        # complete_flows keeps dst/child on deactivated rows, so the
        # delivered edge is still addressable here
        trace_mod.stage(recs, fin, TraceKind.FLOW_FINISH, flows.dst,
                        flows.child)
    return replace(state, flows=flows, jobs=jobs)


def _apply_arrival(state: SimState, cfg: SimConfig, tc=None, hold=None,
                   recs=None):
    """Admit up to cfg.arrivals_per_step jobs whose arrival <= t in one
    pass: assign servers to all their tasks (policy), mark roots READY.

    All jobs admitted in the same step share one scheduler snapshot —
    admission itself never changes server load (queue pushes happen later,
    at READY drain), so the batched pass equals K sequential picks against
    the same farm, exactly the ``pick_servers_for_job`` argument one level
    up.  Same-timestamp bursts (MMPP high state, trace replays) therefore
    no longer serialize one step per job."""
    jobs, farm, sched = state.jobs, state.farm, state.sched
    J = jobs.arrival.shape[0]
    T = cfg.tasks_per_job
    K = cfg.arrivals_per_step
    j0 = jobs.arr_ptr
    jid = j0 + jnp.arange(K)
    nxt = jobs.arrival[jnp.clip(jid, 0, J - 1)]
    elig = (jid < J) & (nxt <= state.t) & (nxt < INF / 2)
    # arrivals are sorted, so eligibility is a prefix; enforce it anyway
    # so an unsorted table degrades to the old one-at-a-time behavior
    elig = jnp.cumprod(elig.astype(jnp.int32)).astype(bool)
    if hold is not None:
        # deferred releases strictly precede fresh arrivals at the same
        # instant: while this step entered with due-but-unreleased jobs,
        # hold arrivals for the next same-time step — the oracle admits
        # (and enqueues) every release chunk before popping a coincident
        # arrival event, so an arrival admitted in the same step as a
        # release chunk would see a load snapshot missing that chunk's
        # not-yet-drained roots
        elig = elig & ~hold
    # pinned accumulator dtype: under jax_enable_x64 a bare bool sum lands
    # int64 and poisons arr_ptr's branch dtypes (found by the simlint
    # f64-clock twin configs)
    n_adm = elig.sum(dtype=jnp.int32)

    def _net_cost():
        if cfg.has_network and \
                cfg.sched_policy == SchedPolicy.NETWORK_AWARE:
            # wake cost from the front-end (server 0) to each server; the
            # net state does not change during admission, so one
            # evaluation serves every task of the batch
            return jax.vmap(
                lambda d: net_mod.route_wake_cost(
                    tc, state.net, jnp.int32(0), d)
            )(jnp.arange(cfg.n_servers))
        return None

    def _temp():
        if cfg.thermal.enabled and \
                cfg.sched_policy == SchedPolicy.THERMAL_AWARE:
            return state.thermal.t_srv
        return None

    def admit(args):
        jobs, farm, sched = args
        JT = jobs.status.shape[0]
        if _deferral_on(cfg):
            # carbon-aware deferral: deferrable jobs arriving while the
            # carbon/price signal exceeds the threshold are NOT admitted;
            # they park with a release time (solved sinusoid down-crossing
            # or their deadline, whichever first) that becomes an event
            # candidate.  A release candidate at/before now — or none at
            # all — admits immediately, so deferral never deadlocks.
            tcfg = cfg.thermal
            jc = jnp.clip(jid, 0, J - 1)
            sig = thermal_mod.defer_signal_now(tcfg, state.t)
            rel = thermal_mod.next_release_time(tcfg, state.t)
            cand = jnp.minimum(rel.astype(cfg.time_dtype),
                               jobs.deadline[jc])
            dfr = (elig & jobs.deferrable[jc]
                   & (sig > tcfg.defer_threshold)
                   & (cand > state.t) & (cand < INF / 2))
            jobs = replace(jobs, admit_at=jobs.admit_at.at[
                jnp.where(dfr, jid, J)].set(
                jnp.where(dfr, cand, INF), mode="drop"))
            adm = elig & ~dfr
        else:
            adm = elig
        tids = j0 * T + jnp.arange(K * T)                  # flat task ids
        in_range = tids < JT
        sc = jnp.where(in_range, tids, JT)                 # scatter sentinel
        gather = jnp.clip(tids, 0, JT - 1)
        elig_t = jnp.repeat(adm, T)
        is_valid = jobs.valid[gather] & elig_t & in_range

        root = is_valid & (jobs.dep_count[gather] <= 0)

        if cfg.sched_policy == SchedPolicy.ROUND_ROBIN:
            if cfg.use_vectorized_hot_loop:
                # all K*T assignments in one shot (cumulative-offset
                # round-robin rank matching)
                srvs, rr_new = scheduler.pick_servers_for_job(
                    farm, cfg, sched, is_valid)
                server_arr = jobs.server.at[sc].set(
                    jnp.where(is_valid, srvs, jobs.server[gather]),
                    mode="drop")
                jobs = replace(jobs, server=server_arr)
                sched = replace(sched, rr_ptr=rr_new)
            else:
                def assign_one(i, carry):
                    jobs, sched = carry
                    tid = gather[i]
                    v = is_valid[i]
                    srv, rr = scheduler.pick_server(farm, cfg, sched)
                    server_arr = jobs.server.at[tid].set(
                        jnp.where(v, srv, jobs.server[tid]))
                    sched = replace(sched,
                                    rr_ptr=jnp.where(v, rr, sched.rr_ptr))
                    return replace(jobs, server=server_arr), sched

                jobs, sched = jax.lax.fori_loop(0, K * T, assign_one,
                                                (jobs, sched))
        else:
            # score policies: one pick PER JOB (the farm cannot change
            # during a single job's assignment), but job k's pick must see
            # the roots committed by jobs 0..k-1 of the same batch —
            # otherwise a same-timestamp burst piles onto the one argmin
            # server, where the old one-job-per-step path spread it (each
            # admit saw the previous job's drained roots as queue load)
            net_cost = _net_cost()
            temp = _temp()
            root_k = root.reshape(K, T)
            extra = jnp.zeros((cfg.n_servers,), jnp.float32)
            picks = []
            for k in range(K):                     # static unroll, K small
                srv_k, _ = scheduler.pick_server(farm, cfg, sched,
                                                 net_cost, temp, extra)
                extra = extra.at[srv_k].add(
                    root_k[k].sum().astype(jnp.float32))
                picks.append(srv_k)
            srvs = jnp.repeat(jnp.stack(picks), T)
            server_arr = jobs.server.at[sc].set(
                jnp.where(is_valid, srvs, jobs.server[gather]), mode="drop")
            jobs = replace(jobs, server=server_arr)

        # roots -> READY
        status = jobs.status.at[sc].set(
            jnp.where(root, TaskStatus.READY, jobs.status[gather]),
            mode="drop")
        return replace(jobs, status=status, arr_ptr=j0 + n_adm), farm, \
            sched

    jobs, farm, sched = jax.lax.cond(
        n_adm > 0, admit, lambda a: a, (jobs, farm, sched))
    if cfg.trace.enabled:
        # ARRIVAL for every job whose arrival slot was consumed this
        # chunk (deferred ones included), ADMIT only for placed jobs
        # (server = the job's first task's pick, aux = its queue depth).
        # Staged OUTSIDE the admit cond: admission wrote everything the
        # records need (deferral is visible as a finite admit_at, the
        # pick as the first task's server; q_len doesn't change until
        # the READY drain), and a skipped cond means elig is all-false.
        JT = jobs.status.shape[0]
        trace_mod.stage(recs, elig, TraceKind.ARRIVAL, -1,
                        jid.astype(jnp.int32))
        adm = elig
        if _deferral_on(cfg):
            adm = elig & ~(jobs.admit_at[jnp.clip(jid, 0, J - 1)]
                           < INF / 2)
        first = jnp.clip(j0 * T + jnp.arange(K) * T, 0, JT - 1)
        job_srv = jobs.server[first]
        trace_mod.stage(recs, adm, TraceKind.ADMIT, job_srv,
                        jid.astype(jnp.int32),
                        farm.q_len[jnp.clip(job_srv, 0)])
    return replace(state, jobs=jobs, farm=farm, sched=sched)


def _apply_releases(state: SimState, cfg: SimConfig, tc=None, recs=None):
    """Admit deferred jobs whose release time has come (CARBON_AWARE
    only): up to cfg.arrivals_per_step per step in ascending job id, one
    shared scheduler snapshot per step — mirroring batched arrival
    admission, so a window's worth of deferred jobs spreads exactly like
    a same-timestamp burst.  Leftover due jobs pin the next event to
    ``now`` (their admit_at is a next-event candidate) and release on the
    following step.  Runs BEFORE fresh-arrival admission: released jobs
    always carry lower ids than jobs arriving now, so the READY drain's
    ascending-tid order serves them first, matching the oracle's
    release-then-arrive event order.

    Also accrues the deferral telemetry: total deferred seconds, release
    count, and a first-order grams-avoided estimate (marginal job energy
    × the carbon-intensity drop between arrival and release)."""
    jobs = state.jobs
    now = state.t
    due = (jobs.admit_at < INF / 2) & (jobs.admit_at <= now)
    K0 = cfg.arrivals_per_step
    # released-job records leave the cond as data; the identity branch
    # hands back an all-invalid chunk
    rel0 = (jnp.zeros((K0,), bool), jnp.full((K0,), -1, jnp.int32),
            jnp.zeros((K0,), jnp.float32), jnp.zeros((K0,), jnp.int32))

    def release(args):
        jobs, therm, _ = args
        farm, sched = state.farm, state.sched
        J = jobs.arrival.shape[0]
        T = cfg.tasks_per_job
        JT = jobs.status.shape[0]
        K = cfg.arrivals_per_step
        jid_b, jvalid, _ = server.compact_mask(due, K)            # (K,)
        jq = jnp.clip(jid_b, 0, J - 1)

        tids = (jq[:, None] * T + jnp.arange(T)[None, :]).reshape(-1)
        gather = jnp.clip(tids, 0, JT - 1)
        valid_t = jnp.repeat(jvalid, T)
        sc = jnp.where(valid_t, tids, JT)
        is_valid = jobs.valid[gather] & valid_t
        # BLOCKED check: only still-parked roots flip READY (a repeated
        # release of an already-processed row must never re-run a task)
        root = is_valid & (jobs.dep_count[gather] <= 0) \
            & (jobs.status[gather] == TaskStatus.BLOCKED)

        # per-job picks against one farm snapshot, with in-batch root
        # commitments as extra load — the same machinery as the score-
        # policy arrival batch (CARBON_AWARE places by load)
        root_k = root.reshape(K, T)
        extra = jnp.zeros((cfg.n_servers,), jnp.float32)
        picks = []
        for k in range(K):                         # static unroll, K small
            srv_k, _ = scheduler.pick_server(farm, cfg, sched,
                                             None, None, extra)
            extra = extra.at[srv_k].add(
                root_k[k].sum().astype(jnp.float32))
            picks.append(srv_k)
        srvs = jnp.repeat(jnp.stack(picks), T)
        server_arr = jobs.server.at[sc].set(
            jnp.where(is_valid, srvs, jobs.server[gather]), mode="drop")
        status = jobs.status.at[sc].set(
            jnp.where(root, TaskStatus.READY, jobs.status[gather]),
            mode="drop")
        admit_at = jobs.admit_at.at[jnp.where(jvalid, jid_b, J)].set(
            INF, mode="drop")
        jobs = replace(jobs, server=server_arr, status=status,
                       admit_at=admit_at)

        tcfg = cfg.thermal
        arr_j = jobs.arrival[jq]
        waited = jnp.where(jvalid, (now - arr_j).astype(jnp.float32), 0.0)
        ci_arr = thermal_mod.carbon_intensity_now(tcfg, arr_j)    # (K,)
        ci_now = thermal_mod.carbon_intensity_now(tcfg, now)
        sp = cfg.server_power
        e_kwh = jobs.service.reshape(-1, T)[jq].sum(axis=1) \
            * jnp.float32((sp.p_core_active - sp.p_core_idle) / 3.6e6)
        avoided = jnp.where(jvalid, (ci_arr - ci_now) * e_kwh, 0.0)
        therm = replace(
            therm,
            defer_seconds=therm.defer_seconds + waited.sum(),
            defer_count=therm.defer_count
            + jvalid.sum().astype(jnp.int32),
            grams_avoided=therm.grams_avoided + avoided.sum())

        return jobs, therm, (jvalid, jid_b, waited, jnp.stack(picks))

    jobs, therm, rel = jax.lax.cond(due.any(), release, lambda a: a,
                                    (jobs, state.thermal, rel0))
    if cfg.trace.enabled:
        jvalid, jid_b, waited, picks_j = rel
        trace_mod.stage(recs, jvalid, TraceKind.RELEASE, -1, jid_b,
                        waited)
        trace_mod.stage(recs, jvalid, TraceKind.ADMIT, picks_j, jid_b,
                        state.farm.q_len[jnp.clip(picks_j, 0)])
    return replace(state, jobs=jobs, thermal=therm)


def _resolve_drops(state: SimState, cfg: SimConfig, dropped, recs=None):
    """Complete the bookkeeping for tasks dropped by a full queue
    (dropped (JT,) bool, already marked DONE by the drain).

    Without this, a drop deadlocks DAG workloads: the task is DONE but its
    children's dep_count never reaches zero, so they stay BLOCKED forever
    and the sim spins to max_events.  A dropped task counts toward job
    completion (finish/job_finish stamped at drop time, flagged via
    farm.dropped) and resolves its DAG edges immediately — it never ran,
    so there are no results to ship and no flows to spawn.

    Gated on dropped.any(): overflow is the exception, and the healthy
    path must not pay the bookkeeping every step.
    """
    now = state.t

    def resolve(jobs):
        finish = jnp.where(dropped, now, jobs.finish)
        # drops were already marked DONE by the drain
        tasks_done, job_finish = _rebuild_job_completion(jobs, cfg, now)

        ch = jobs.children                           # (JT, D)
        ch_valid = (ch >= 0) & dropped[:, None] & ~jobs.edge_sent
        edge_sent = jobs.edge_sent | ch_valid
        dep_count = jobs.dep_count.at[jnp.clip(ch, 0).reshape(-1)].add(
            -ch_valid.reshape(-1).astype(jnp.int32), mode="drop")

        status = _promote_ready(jobs, dep_count, cfg)
        return replace(jobs, status=status, finish=finish,
                       tasks_done=tasks_done, job_finish=job_finish,
                       dep_count=dep_count, edge_sent=edge_sent)

    jobs = jax.lax.cond(dropped.any(), resolve, lambda j: j, state.jobs)
    if cfg.trace.enabled:
        # staged outside the cond: the drop mask and the job table's
        # before/after finish stamps carry everything the records need
        JT = jobs.status.shape[0]
        trace_mod.stage(recs, dropped, TraceKind.DROP, jobs.server,
                        jnp.arange(JT, dtype=jnp.int32))
        new_jf = (state.jobs.job_finish >= INF / 2) \
            & (jobs.job_finish < INF / 2)
        J = jobs.job_finish.shape[0]
        trace_mod.stage(recs, new_jf, TraceKind.JOB_FINISH, -1,
                        jnp.arange(J, dtype=jnp.int32),
                        jobs.job_finish - jobs.arrival)
    return replace(state, jobs=jobs)


def _drain_ready(state: SimState, cfg: SimConfig, recs=None):
    """Enqueue up to cfg.ready_per_step READY tasks at their servers
    (first K in task-id order).  Queue-full drops are resolved afterwards
    (_resolve_drops); their newly-READY children drain on the next step —
    still at the same simulation time, since READY tasks pin t_next to t."""
    if cfg.use_vectorized_hot_loop:
        return _drain_ready_batched(state, cfg, recs)
    return _drain_ready_scalar(state, cfg, recs)


def _drain_ready_batched(state: SimState, cfg: SimConfig, recs=None):
    """One multi-push: the first K READY tasks become QUEUED with FIFO
    stamps written elementwise into their own task rows (no ring-slot
    scatter).  The whole pass is gated on "any READY task" so quiet steps
    stay free."""
    is_ready = state.jobs.status == TaskStatus.READY
    JT0 = state.jobs.status.shape[0]

    def drain(args):
        state, _ = args
        jobs, farm = state.jobs, state.farm
        K = cfg.ready_per_step
        JT = jobs.status.shape[0]
        r = jnp.cumsum(is_ready) - 1                # rank among READY
        sel = is_ready & (r < K)
        # gather selected tids into (K,) batch slots, ascending tid order
        tids = jnp.full((K,), -1, jnp.int32).at[jnp.where(sel, r, K)].set(
            jnp.arange(JT, dtype=jnp.int32), mode="drop")
        valid = tids >= 0
        srv = jnp.where(valid, jobs.server[jnp.clip(tids, 0)], -1)

        farm, ok, seq = server.queue_push_many(farm, cfg, srv, tids, valid)
        dest = jnp.zeros((cfg.n_servers,), bool).at[
            jnp.where(valid, srv, cfg.n_servers)].set(True, mode="drop")
        farm = server.begin_wake_mask(farm, cfg, dest, state.t)

        sc = jnp.where(valid, tids, JT)
        status = jobs.status.at[sc].set(
            jnp.where(ok, TaskStatus.QUEUED, TaskStatus.DONE), mode="drop")
        enq = jobs.enqueue_seq.at[jnp.where(valid & ok, tids, JT)].set(
            seq, mode="drop")
        state = replace(state, jobs=replace(jobs, status=status,
                                            enqueue_seq=enq), farm=farm)
        dropped = jnp.zeros((JT,), bool).at[
            jnp.where(valid & ~ok, tids, JT)].set(True, mode="drop")
        return state, dropped

    # drop resolution happens outside the drain cond so its trace
    # records can be staged (it re-gates itself on dropped.any())
    state, dropped = jax.lax.cond(
        is_ready.any(), drain, lambda a: a,
        (state, jnp.zeros((JT0,), bool)))
    return _resolve_drops(state, cfg, dropped, recs)


def _drain_ready_scalar(state: SimState, cfg: SimConfig, recs=None):
    """Seed reference path: K sequential scalar queue_push + begin_wake."""
    status_before = state.jobs.status

    def one(_, st):
        jobs, farm = st.jobs, st.farm
        is_ready = jobs.status == TaskStatus.READY
        any_ready = is_ready.any()
        tid = jnp.argmax(is_ready)                      # first READY
        srv = jobs.server[tid]

        def do(st):
            jobs, farm = st.jobs, st.farm
            farm2, ok, seq = server.queue_push(farm, cfg, srv, tid)
            farm2 = server.begin_wake(farm2, cfg, srv, st.t)
            status = jobs.status.at[tid].set(
                jnp.where(ok, TaskStatus.QUEUED, TaskStatus.DONE))
            enq = jobs.enqueue_seq.at[tid].set(
                jnp.where(ok, seq, jobs.enqueue_seq[tid]))
            jobs2 = replace(jobs, status=status, enqueue_seq=enq)
            return replace(st, jobs=jobs2, farm=farm2)

        return jax.lax.cond(any_ready, do, lambda s: s, st)

    state = jax.lax.fori_loop(0, cfg.ready_per_step, one, state)
    # READY -> DONE transitions during the loop are exactly the drops
    dropped = (status_before == TaskStatus.READY) \
        & (state.jobs.status == TaskStatus.DONE)
    return _resolve_drops(state, cfg, dropped, recs)


def _start_tasks(state: SimState, cfg: SimConfig, recs=None):
    # throttled servers start work at their reduced effective frequency;
    # freq=None keeps the untrottled scalar expression when thermal is off
    freq = thermal_mod.effective_freq(state.thermal, cfg) \
        if cfg.thermal.throttling else None
    farm, jobs = server.try_start(state.farm, cfg, state.jobs, state.t,
                                  freq)
    if cfg.trace.enabled:
        started = (jobs.status == TaskStatus.RUNNING) \
            & (state.jobs.status == TaskStatus.QUEUED)
        JT = jobs.status.shape[0]
        trace_mod.stage(recs, started, TraceKind.START, jobs.server,
                        jnp.arange(JT, dtype=jnp.int32),
                        jobs.task_end - state.t)
    return replace(state, farm=farm, jobs=jobs)


def _apply_events(state: SimState, cfg: SimConfig, tc, cheap: bool,
                  recs=None):
    """The event-application pipeline at the (already advanced) time
    state.t.  ``cheap`` statically trims the passes the macro-step gating
    guarantees are not needed: flow completions (gated: t < min done_at)
    and the rate recompute (the active-flow set cannot change during a
    cheap event — no spawns, no completions — so rates stay valid).

    ``recs`` collects the pass's flight-recorder records (trace.stage);
    the caller flushes them to the ring in one write after the pipeline.
    """
    # ALWAYS_ON has no srv_state transition path (timer_transitions is
    # the identity, wasp_adjust is WASP-only), so the WAKEUP/SLEEP masks
    # are identically false — skip both sites statically and keep ~1/4
    # of the flush lane space out of the hot loop
    trace_sleep = (cfg.trace.enabled
                   and cfg.sleep_policy != SleepPolicy.ALWAYS_ON)
    with jax.named_scope("completions"):
        if trace_sleep:
            N = cfg.n_servers
            woke = (state.farm.srv_state == SrvState.WAKING) \
                & (state.farm.srv_wake_at <= state.t)
            trace_mod.stage(recs, woke, TraceKind.WAKEUP,
                            jnp.arange(N, dtype=jnp.int32))
        state = replace(state,
                        farm=_apply_wakeups(state.farm, cfg, state.t))
        state = _apply_completions(state, cfg, tc, recs)
        if cfg.has_network and not cheap:
            state = _apply_flow_completions(state, cfg, recs)
    with jax.named_scope("admission"):
        hold = None
        if _deferral_on(cfg):
            # deferred releases admit BEFORE fresh arrivals (lower job ids
            # drain first; see _apply_releases); a step that entered with
            # due releases also HOLDS fresh arrivals until the next
            # same-time step, so the arrival's load snapshot sees the
            # release train fully admitted AND drained (the oracle's
            # event order)
            admit_at = state.jobs.admit_at
            hold = ((admit_at < INF / 2) & (admit_at <= state.t)).any()
            state = _apply_releases(state, cfg, tc, recs)
        state = _apply_arrival(state, cfg, tc, hold, recs)
    with jax.named_scope("drain_start"):
        state = _drain_ready(state, cfg, recs)
        state = _start_tasks(state, cfg, recs)

    # refresh ACTIVE/IDLE, run local power controllers + pool managers
    with jax.named_scope("power"):
        if trace_sleep:
            st_before = state.farm.srv_state
        farm = server.refresh_idle_state(state.farm, cfg, state.t)
        active = _active_jobs(state.jobs)
        farm, sched = scheduler.provisioning_adjust(farm, cfg, state.sched,
                                                    active)
        farm = scheduler.wasp_adjust(farm, cfg, _pending_jobs(state.jobs),
                                     state.t)
        farm = scheduler.timer_transitions(farm, cfg, state.t)
        state = replace(state, farm=farm, sched=sched)
        if trace_sleep:
            # awake -> sleep edges from the local power controllers
            was_awake = (st_before == SrvState.ACTIVE) \
                | (st_before == SrvState.IDLE)
            asleep = (farm.srv_state == SrvState.PKG_C6) \
                | (farm.srv_state == SrvState.S3) \
                | (farm.srv_state == SrvState.OFF)
            trace_mod.stage(recs, was_awake & asleep, TraceKind.SLEEP,
                            jnp.arange(cfg.n_servers, dtype=jnp.int32), -1,
                            farm.srv_state)

    if cfg.has_network:
        with jax.named_scope("flows"):
            state = _update_flows(state, cfg, tc, cheap)
    return state


def _update_flows(state: SimState, cfg: SimConfig, tc, cheap: bool):
    """Flow rates and switch power states after the step's events."""
    if cheap:
        # the flow set is unchanged (gating), so rates and link_flows
        # stay valid — but ports/linecards still enter LPI on idle
        # timeouts, which is a function of *time*, not of flow events
        net = net_mod.update_switch_states(
            state.net, state.net.link_flows, tc, cfg, state.t)
        return replace(state, net=net)
    # rate recomputation is only needed while flows are in flight — gate
    # the (F, H) pass.  The no-flow branch must still ZERO link_flows
    # (recompute_rates would): reusing last step's counts would pin ports
    # ACTIVE forever after the final flow completes.
    flows, link_flows = jax.lax.cond(
        state.flows.active.any(),
        lambda args: net_mod.recompute_rates(args[0], tc, state.t),
        lambda args: (args[0], jnp.zeros_like(args[1])),
        (state.flows, state.net.link_flows))
    net = net_mod.update_switch_states(state.net, link_flows, tc, cfg,
                                       state.t)
    return replace(state, flows=flows, net=net)


# ==========================================================================
# the step
# ==========================================================================

def _cheap_gate(state: SimState, cfg: SimConfig):
    """(consume?, t_next) for one cheap event: the pending event time,
    restricted to the sources the cheap core handles (arrival, task
    completion, wakeup, timer, pending READY work).  ``consume`` is False
    whenever the full step is needed first: a flow completes at or before
    t_next, a completing task would resolve network edges (flow spawn +
    rate recompute), a throttle crossing fires, nothing is pending, or
    consuming the event would finish the simulation (the one-event loop
    sets ``done`` in the same step as the last completion and never
    processes trailing sleep-timer events — the last completion must
    therefore reach the full step, which owns the done check)."""
    t_next = _farm_candidates(state, cfg)
    jobs = state.jobs
    will_be_done = (~jobs.valid | (jobs.status == TaskStatus.DONE)
                    | ((jobs.status == TaskStatus.RUNNING)
                       & (jobs.task_end <= t_next))).all() \
        & (_next_arrival(jobs) >= INF)
    if cfg.has_network:
        will_be_done = will_be_done & ~state.flows.active.any()
    ok = (t_next < INF / 2) & ~will_be_done
    if cfg.has_network:
        ok = ok & (t_next < state.flows.done_at.min())
        if cfg.tasks_per_job > 1:
            # a completing task whose unsent edges all resolve locally
            # (same server / zero bytes) is still cheap — the in-core
            # edge resolver handles immediate edges; only an edge that
            # would SPAWN a flow (and force a rate recompute) stops the
            # chew.  Colocating policies (case D) therefore coalesce
            # their chain completions.
            jobs = state.jobs
            will_done = (jobs.status == TaskStatus.RUNNING) \
                & (jobs.task_end <= t_next)
            unsent = (jobs.children >= 0) & ~jobs.edge_sent
            dst = jobs.server[jnp.clip(jobs.children, 0)]     # (JT, D)
            spawns = unsent & (jobs.edge_bytes > 0) \
                & (dst != jobs.server[:, None])
            ok = ok & ~(will_done[:, None] & spawns).any()
    if cfg.thermal.throttling:
        ok = ok & (t_next < thermal_mod.next_crossing(state, cfg))
    return ok, t_next


def _apply_thermal_events(state: SimState, cfg: SimConfig,
                          recs=None) -> SimState:
    """Throttle hysteresis latch + setpoint-controller tick, shared by the
    cheap core and the full step (both run right after the interval
    advance), with their flight-recorder emission."""
    if cfg.thermal.throttling:
        # hysteresis latch + in-flight stretch; cond-gated on "any flip"
        old_thr = state.thermal.throttled
        farm, jobs, therm = thermal_mod.apply_throttle(
            state.farm, state.jobs, state.thermal, cfg, state.t)
        state = replace(state, farm=farm, jobs=jobs, thermal=therm)
        if cfg.trace.enabled:
            trace_mod.stage(recs, therm.throttled != old_thr,
                            TraceKind.THROTTLE_CROSSING,
                            jnp.arange(cfg.n_servers, dtype=jnp.int32),
                            -1, therm.t_srv)
    if cfg.thermal.has_ctrl:
        if cfg.trace.enabled:
            # the tick fires exactly when time reaches ctrl_next (it is a
            # next-event candidate); stage before the controller advances
            trace_mod.stage1(recs, state.t >= state.thermal.ctrl_next,
                             TraceKind.CTRL_TICK)
        # per-rack setpoint controller tick (cond-gated on the period)
        state = replace(state, thermal=thermal_mod.apply_setpoint_ctrl(
            state.thermal, cfg, state.t))
    return state


def _consume_cheap(state: SimState, cfg: SimConfig, tc, t_next):
    # the named_scope tags every equation of the cheap core with region
    # "cheap_core" so the static auditor (analysis/) can budget it
    # separately from the full step
    with jax.named_scope("cheap_core"):
        with jax.named_scope("advance"):
            state = _advance_interval(state, cfg, tc, t_next)
        recs = [] if cfg.trace.enabled else None
        with jax.named_scope("thermal"):
            state = _apply_thermal_events(state, cfg, recs)
        state = _apply_events(state, cfg, tc, cheap=True, recs=recs)
        if cfg.trace.enabled:
            with jax.named_scope("trace_flush"):
                state = replace(state, trace=trace_mod.flush(
                    state.trace, cfg, state.t, recs))
        return replace(state, events=state.events + 1)


def _macro_chew(state: SimState, cfg: SimConfig, tc):
    """Retire up to events_per_step - 1 cheap events in a bounded inner
    while_loop; stops early when the gate demands the full step."""
    K = cfg.events_per_step - 1

    def cond(carry):
        _, k, ok = carry
        return ok & (k < K)

    def body(carry):
        state, k, _ = carry
        with jax.named_scope("next_event"):
            ok, t_next = _cheap_gate(state, cfg)
        state = jax.lax.cond(
            ok, lambda s: _consume_cheap(s, cfg, tc, t_next),
            lambda s: s, state)
        return state, k + 1, ok

    state, _, _ = jax.lax.while_loop(
        cond, body, (state, jnp.zeros((), jnp.int32), jnp.asarray(True)))
    return state


def _full_step(state: SimState, cfg: SimConfig, tc=None) -> SimState:
    with jax.named_scope("full_step"):
        with jax.named_scope("next_event"):
            t_next = next_event_time(state, cfg)
            # a t_next at the INF sentinel means "no pending events":
            # freeze time (the done check below will terminate the loop)
            # instead of integrating energy over an unbounded interval
            t_next = jnp.where(t_next >= INF / 2, state.t, t_next)
        with jax.named_scope("advance"):
            state = _advance_interval(state, cfg, tc, t_next)
        recs = [] if cfg.trace.enabled else None
        with jax.named_scope("thermal"):
            state = _apply_thermal_events(state, cfg, recs)
        state = _apply_events(state, cfg, tc, cheap=False, recs=recs)
        if cfg.trace.enabled:
            with jax.named_scope("trace_flush"):
                state = replace(state, trace=trace_mod.flush(
                    state.trace, cfg, state.t, recs))

        with jax.named_scope("next_event"):
            all_done = (~state.jobs.valid
                        | (state.jobs.status == TaskStatus.DONE)).all() \
                & (_next_arrival(state.jobs) >= INF)
            if cfg.has_network:
                all_done = all_done & ~state.flows.active.any()
        return replace(state, events=state.events + 1, done=all_done)


def sim_step(state: SimState, cfg: SimConfig, tc=None) -> SimState:
    """One macro-step: chew up to events_per_step - 1 cheap events, then
    one full step; latency/QoS binning runs once over everything that
    finished since the macro began (the INF -> finite finish transitions
    identify them, independent of which inner step stamped them)."""
    telemetry_on = cfg.telemetry.enabled
    if telemetry_on:
        old_job_finish = state.jobs.job_finish
        old_task_finish = state.jobs.finish

    if cfg.events_per_step > 1:
        state = _macro_chew(state, cfg, tc)
    state = _full_step(state, cfg, tc)
    state = replace(state, steps=state.steps + 1)

    if telemetry_on:
        with jax.named_scope("telemetry"):
            state = replace(state, telem=telemetry.accumulate_finishes(
                state.telem, cfg, state.jobs, old_job_finish,
                old_task_finish))
    return state


def init_state(cfg: SimConfig, jobs: JobTable, topo=None,
               racks=None) -> SimState:
    """``racks`` — optional (N,) host array of rack ids for the thermal
    recirculation grouping; defaults to the topology's first-hop-switch
    grouping when a topo is given, else ``i // thermal.rack_size``."""
    if cfg.has_network and topo is None:
        raise ValueError(
            "cfg.has_network=True requires a topology: pass topo= "
            "(flows would silently never route with tc=None)")
    if cfg.n_present > cfg.n_servers:
        raise ValueError(
            f"n_present={cfg.n_present} exceeds n_servers={cfg.n_servers}")
    if cfg.partition.sharded and cfg.thermal.enabled and racks is None \
            and topo is None \
            and cfg.n_servers % max(cfg.thermal.rack_size, 1):
        # unsharded runs handle an uneven last rack via the general
        # one-hot grouping; the rack-major block partition cannot, so the
        # sharded path refuses it up front instead of falling back
        raise ValueError(
            f"n_servers={cfg.n_servers} does not fill whole racks of "
            f"rack_size={cfg.thermal.rack_size}, so the rack-major "
            f"partition cannot cut on rack boundaries; pad the farm with "
            f"farm.pad_to_racks(cfg) (inert filler rows)")
    if cfg.sched_policy == SchedPolicy.THERMAL_AWARE \
            and not cfg.thermal.enabled:
        raise ValueError(
            "SchedPolicy.THERMAL_AWARE requires cfg.thermal.enabled=True "
            "(placement would silently ignore temperatures)")
    if cfg.sched_policy == SchedPolicy.CARBON_AWARE \
            and not cfg.thermal.enabled:
        raise ValueError(
            "SchedPolicy.CARBON_AWARE requires cfg.thermal.enabled=True "
            "(the deferral signal and telemetry live in the thermal/"
            "carbon subsystem)")
    tc = net_mod.topo_consts(topo) if (topo is not None and
                                       cfg.has_network) else None
    if racks is None and topo is not None and cfg.thermal.enabled:
        from . import topology as topo_mod
        racks = topo_mod.rack_of_servers(topo, cfg.thermal.rack_size)
    n_sw = topo.n_switches if topo is not None else 0
    n_ports = topo.n_ports if topo is not None else 1
    n_links = topo.n_links if topo is not None else 1
    n_lc = topo.n_linecards if topo is not None else 1
    state = SimState(
        t=jnp.zeros((), cfg.time_dtype),
        farm=init_farm(cfg),
        jobs=jobs,
        flows=init_flows(cfg),
        net=init_net(n_sw, n_ports, n_links, n_lc, cfg),
        sched=init_sched(cfg),
        telem=telemetry.init_telemetry(cfg),
        thermal=thermal_mod.init_thermal(cfg, racks),
        trace=trace_mod.init_trace(cfg),
        events=jnp.zeros((), jnp.int32),
        steps=jnp.zeros((), jnp.int32),
        done=jnp.zeros((), bool),
    )
    return state, tc


def step_closure(cfg: SimConfig, tc=None):
    """A ``state -> state`` closure over one macro-step, for jaxpr tracing
    by the static auditor (``analysis/``)."""
    def step(state: SimState) -> SimState:
        return sim_step(state, cfg, tc)
    return step


def _layout_key(tree) -> tuple:
    """Hashable (shape, dtype) layout of a pytree of tracers/arrays."""
    return tuple(
        (tuple(x.shape), str(x.dtype)) if hasattr(x, "shape") else repr(x)
        for x in jax.tree_util.tree_leaves(tree))


def _note_trace(tag: str, key) -> None:
    """Trace-time side effect feeding the retrace sentinel (no-op unless
    analysis.retrace has enabled counting)."""
    from ..analysis import retrace
    retrace.note_trace(tag, key)


def loop_cond(cfg: SimConfig):
    """The run-to-completion while-loop predicate, shared by :func:`run`
    and the rack-sharded driver (core/shard_sim.py) so both loops stop on
    exactly the same replicated scalars."""
    def cond(s):
        return (~s.done) & (s.events < cfg.max_events)
    return cond


@functools.partial(jax.jit, static_argnames=("cfg",))
def run(state: SimState, cfg: SimConfig, tc=None) -> SimState:
    """Run to completion (or cfg.max_events) under lax.while_loop.

    With macro-stepping (cfg.events_per_step > 1) the event budget is
    checked between macro-steps, so a run may retire up to
    events_per_step - 1 events past max_events before stopping."""
    # executes only when XLA actually (re)traces this (cfg, layout) key —
    # the retrace sentinel fails if the same key traces twice
    _note_trace("engine.run", (cfg, _layout_key((state, tc))))
    return jax.lax.while_loop(loop_cond(cfg), lambda s: sim_step(s, cfg, tc),
                              state)

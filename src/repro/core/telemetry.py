"""Device-side telemetry: streaming histograms, windowed time series, and
QoS/SLA tracking inside the jitted DES loop.

The paper's case studies (§IV) all read *distributions* — per-job latency
percentiles (Fig 5-6), power-state residency over time (Fig 8), energy-delay
trade-offs — not just end-of-run scalars.  This module accumulates them on
device, entirely inside ``lax.while_loop``:

  * **Latency histograms** — fixed-bin log-spaced histograms at job and task
    granularity.  p50/p95/p99 are recovered host-side from the bins
    (:func:`hist_percentile`) with at most one-bin-width error, so a vmapped
    replica sweep ships (R, B) histograms instead of (R, J) job tables.
  * **Windowed time series** — per-bucket time-weighted sums of active jobs,
    awake servers, queue depth, server/switch power, and per-power-state
    server counts.  A DES interval [t, t_next) is piecewise constant, so
    ``metric * dt`` scattered into the window containing the interval
    midpoint integrates the series exactly up to window-boundary rounding.
  * **QoS/SLA counters** — deadline misses against a per-job ``sla`` field
    and tail-latency violations against a global threshold.

The hot accumulation path has two interchangeable backends: the fused Pallas
kernel (``kernels/telemetry_bin.py`` — histogram binning + window bucketing
in one VMEM pass) and its pure-jnp oracle (``kernels/ref.py``), selected by
``TelemetryConfig.use_kernel``.  On CPU the kernel runs in interpret mode
(``kernels.interpret_mode``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import power
from . import thermal as thermal_mod
from .types import (INF, SimConfig, SrvState, TaskStatus, Telemetry,
                    TelemetryConfig, replace)

__all__ = ["init_telemetry", "window_values", "accumulate_finishes",
           "summarize", "hist_percentile", "hist_mean", "bin_edges",
           "TelemetrySummary", "WIN_COLS"]

# ``Telemetry.win`` column layout.  Columns up to WIN_MAX_TEMP are
# time-weighted sums (column WIN_OCC accumulates dt itself, i.e. the
# occupancy used to normalize the others back to time averages); the
# columns from WIN_CI on are *exact interval integrals* (already
# time-integrated in closed form, no normalization by dt).  The thermal
# block stays zero when cfg.thermal.enabled=False.
WIN_OCC = 0          # sum of dt landing in this window
WIN_ACTIVE_JOBS = 1  # tasks in flight (READY|QUEUED|RUNNING) · dt
WIN_AWAKE = 2        # servers in ACTIVE|IDLE · dt
WIN_QDEPTH = 3       # local + global queue occupancy · dt
WIN_SRV_POWER = 4    # total server power (W) · dt  == joules per window
WIN_SW_POWER = 5     # total switch power (W) · dt
WIN_STATE0 = 6       # server count in SrvState s · dt, s = 0..NUM-1
WIN_COOL_POWER = WIN_STATE0 + SrvState.NUM   # CRAC power (W) · dt
WIN_MEAN_TEMP = WIN_COOL_POWER + 1           # farm-mean temperature · dt
WIN_MAX_TEMP = WIN_MEAN_TEMP + 1             # farm-max temperature · dt
WIN_CI = WIN_MAX_TEMP + 1                    # ∫ carbon intensity dt
WIN_PRICE = WIN_CI + 1                       # ∫ electricity price dt
WIN_CARBON_G = WIN_PRICE + 1                 # grams CO2 in this window
WIN_COST = WIN_CARBON_G + 1                  # $ in this window
WIN_COLS = WIN_COST + 1


# ==========================================================================
# state init
# ==========================================================================

def init_telemetry(cfg: SimConfig) -> Telemetry:
    """Zeroed telemetry pytree; minimal (1-sized) arrays when disabled so
    the disabled path carries no per-step cost and ~no memory."""
    tcfg = cfg.telemetry
    B = tcfg.n_bins if tcfg.enabled else 1
    W = tcfg.n_windows if tcfg.enabled else 1
    return Telemetry(
        job_hist=jnp.zeros((B,), jnp.float32),
        task_hist=jnp.zeros((B,), jnp.float32),
        win=jnp.zeros((W, WIN_COLS), jnp.float32),
        sla_miss=jnp.zeros((), jnp.int32),
        sla_total=jnp.zeros((), jnp.int32),
        tail_viol=jnp.zeros((), jnp.int32),
        win_overflow=jnp.zeros((), jnp.float32),
    )


# ==========================================================================
# in-loop accumulation
# ==========================================================================

def window_values(state, cfg: SimConfig, dt, p_busy=None,
                  onehot=None, thermal_ctx=None) -> jnp.ndarray:
    """(WIN_COLS,) metric·dt vector for the piecewise-constant interval
    [t, t+dt) — computed from the PRE-advance state, matching the exact
    energy integration in power.accrue_server_energy.  The carbon/price
    columns are closed-form interval integrals (not rate·dt samples), so
    window sums reproduce the accumulated grams/dollars exactly.

    ``p_busy`` / ``onehot`` optionally supply the precomputed per-server
    (power, busy-count) pair and (N, NUM) state one-hot, and
    ``thermal_ctx`` the (target, alpha, t_end, p_cool) RC/CRAC pieces — the engine's
    advance shares one evaluation between energy accrual, these window
    columns, and the thermal integrator instead of recomputing the power
    select, state comparisons, and RC exponential in each subsystem."""
    farm = state.farm
    tcfg = cfg.thermal
    dtf = dt.astype(jnp.float32)
    s = state.jobs.status
    active = ((s == TaskStatus.READY) | (s == TaskStatus.QUEUED)
              | (s == TaskStatus.RUNNING)).sum().astype(jnp.float32)
    qdepth = (farm.q_len.sum() + state.sched.gq_len).astype(jnp.float32)
    throttled = state.thermal.throttled if tcfg.enabled else None
    if p_busy is None:
        p_busy = power.server_power(farm, cfg, throttled)
    if onehot is None:
        onehot = (farm.srv_state[:, None]
                  == jnp.arange(SrvState.NUM)[None, :]).astype(jnp.float32)
    p_srv = p_busy[0].sum().astype(jnp.float32)
    if cfg.has_network:
        p_sw = power.switch_power(state.net, cfg).sum().astype(jnp.float32)
    else:
        p_sw = jnp.float32(0.0)
    # padded filler rows (farm.pad_to_racks) are telemetry-inert: they sit
    # OFF forever, so the static suffix slice keeps them out of the
    # per-state counts (the padding is a suffix by construction)
    per_state = onehot[:cfg.present].sum(axis=0) if cfg.has_padding \
        else onehot.sum(axis=0)
    awake = per_state[SrvState.ACTIVE] + per_state[SrvState.IDLE]
    head = jnp.stack([jnp.float32(1.0), active, awake, qdepth, p_srv, p_sw])
    if tcfg.enabled:
        t_srv = state.thermal.t_srv
        ici, ipr = thermal_mod.carbon_price_integrals(tcfg, state.t, dt)
        # temperature varies exponentially WITHIN the interval, so the
        # mean column integrates the closed form (∫T dt = target·dt +
        # (T0−target)·τ·(1−e^{−dt/τ}), averaged over servers) and the max
        # column uses the endpoint max (trajectories are monotone toward
        # their targets) — same exactness as the energy/carbon columns
        if thermal_ctx is None:
            p_vec = p_busy[0]
            target = p_vec * tcfg.r_th \
                + thermal_mod.inlet_temps(state.thermal, tcfg, state.t)
            alpha = 1.0 - jnp.exp(-dtf / tcfg.tau_th)
            t_end = t_srv + (target - t_srv) * alpha
            p_cool = thermal_mod.cooling_power(p_vec, p_sw,
                                               state.thermal, tcfg)
        else:
            target, alpha, t_end, p_cool = thermal_ctx
        kw = (p_srv + p_sw + p_cool) * jnp.float32(1.0e-3)
        if cfg.has_padding:
            # padded rows idle at the cold-aisle temperature; keep them
            # out of the farm mean/max columns (suffix padding -> slice)
            np_ = cfg.present
            target, t_srv_m, t_end_m = (target[:np_], t_srv[:np_],
                                        t_end[:np_])
        else:
            t_srv_m, t_end_m = t_srv, t_end
        mean_int = target.mean() * dtf \
            + (t_srv_m - target).mean() * tcfg.tau_th * alpha
        max_interval = jnp.maximum(t_srv_m, t_end_m).max()
        therm_cols = jnp.stack([
            p_cool * dtf, mean_int, max_interval * dtf,
            ici, ipr, kw * ici / 3600.0, kw * ipr / 3600.0])
    else:
        therm_cols = jnp.zeros((7,), jnp.float32)
    base = jnp.concatenate([head, per_state.astype(jnp.float32)]) * dtf
    return jnp.concatenate([base, therm_cols])


def _compact_finishes(mask, vals, K: int, fill: float):
    """Gather the first K True entries of ``mask`` into a (K,) batch of
    (values, weights) via top_k — scatter-free (XLA:CPU serializes
    scatters, which is exactly the cost this compaction removes from the
    binning).  Padding slots carry ``fill`` at weight 0, so the weighted
    histogram of the batch equals the dense masked histogram whenever
    mask.sum() <= K (counts are exact in f32 well past 2^24)."""
    w, idx = jax.lax.top_k(mask.astype(jnp.float32), K)
    out = jnp.where(w > 0, vals[idx], jnp.float32(fill))
    return out, w


def window_index(t, dt, tcfg: TelemetryConfig) -> jnp.ndarray:
    """Window containing the interval midpoint, clamped into range."""
    mid = t.astype(jnp.float32) + 0.5 * dt.astype(jnp.float32)
    return jnp.clip((mid / tcfg.window_dt).astype(jnp.int32),
                    0, tcfg.n_windows - 1)


def window_spill(t, dt, tcfg: TelemetryConfig) -> jnp.ndarray:
    """Seconds of this interval that window_index clamped into the last
    window because its midpoint lies past the n_windows·window_dt horizon.
    Conservation is deliberately preserved (the seconds still land in the
    last window) — the accumulated spill lets summarize flag/NaN the
    contaminated last-window time-averages instead of silently skewing
    them on runs longer than the horizon."""
    mid = t.astype(jnp.float32) + 0.5 * dt.astype(jnp.float32)
    horizon = jnp.float32(tcfg.n_windows * tcfg.window_dt)
    return jnp.where(mid >= horizon, dt.astype(jnp.float32), 0.0)


def accumulate_finishes(telem: Telemetry, cfg: SimConfig, jobs,
                        old_job_finish, old_task_finish) -> Telemetry:
    """Bin the latencies of every job/task that finished since the finish
    arrays were captured, and bump the QoS counters.

    ``old_*_finish`` are the finish arrays captured before the macro-step
    began — the INF -> finite transition identifies new completions, so
    one binning pass per macro-step covers every inner event (the bin a
    latency lands in does not depend on WHEN it is binned).  Window
    accrual is separate (the engine adds each interval's metric·dt inside
    its advance, exactly like the energy integral)."""
    tcfg = cfg.telemetry
    T = cfg.tasks_per_job
    new_job = (old_job_finish >= INF / 2) & (jobs.job_finish < INF / 2)
    new_task = (old_task_finish >= INF / 2) & (jobs.finish < INF / 2)

    def bin_finishes(args):
        # everything latency-shaped lives INSIDE the gate: quiet steps
        # must not pay the (J,)/(J·T,) latency/QoS passes
        jh0, th0 = args
        job_lat = jnp.maximum(jobs.job_finish - jobs.arrival, 0.0)
        jw = new_job.astype(jnp.float32)
        # task latency = finish - its job's arrival (sojourn to this stage)
        arr_t = jnp.repeat(jobs.arrival, T)
        task_lat = jnp.maximum(jobs.finish - arr_t, 0.0)
        tw = new_task.astype(jnp.float32)

        has_sla = jobs.sla < INF / 2
        miss = (new_job & has_sla
                & (job_lat > jobs.sla)).sum().astype(jnp.int32)
        tot = (new_job & has_sla).sum().astype(jnp.int32)
        tail = (new_job
                & (job_lat > tcfg.tail_thresh)).sum().astype(jnp.int32)

        from ..kernels import ref
        if tcfg.use_kernel:
            from ..kernels import interpret_mode, telemetry_bin
            # the fused kernel bins histograms and buckets windows in one
            # pass; windows accrue separately per interval now, so feed
            # it a single dummy row with a zero add (the kernel shapes
            # off win, so this keeps the dead window pass at one row)
            zwin = jnp.zeros((telem.win.shape[1],), jnp.float32)
            jh, th, _ = telemetry_bin.telemetry_accum(
                job_lat, jw, task_lat, tw, jh0, th0, telem.win[:1],
                jnp.zeros((), jnp.int32), zwin,
                tcfg.lat_lo, tcfg.lat_hi, interpret=interpret_mode())
            return jh, th, miss, tot, tail

        def dense(args):
            jh0, th0 = args
            B = jh0.shape[0]
            jh = jh0.at[ref.log_bin(job_lat, tcfg.lat_lo, tcfg.lat_hi,
                                    B)].add(jw)
            th = th0.at[ref.log_bin(task_lat, tcfg.lat_lo, tcfg.lat_hi,
                                    B)].add(tw)
            return jh, th

        Kc = tcfg.compact
        if Kc <= 0 or Kc >= job_lat.shape[0]:
            return (*dense(args), miss, tot, tail)

        # most finishing steps complete only a handful of jobs/tasks
        # (bounded by free cores + drop resolution): gather them into a
        # (Kc,)-batch so the log-binning stops paying (J)+(J·T)-wide
        # work, falling back to the dense pass on mass-finish steps
        def compact(args):
            jv, jww = _compact_finishes(new_job, job_lat, Kc, tcfg.lat_lo)
            tv, tww = _compact_finishes(new_task, task_lat, Kc, tcfg.lat_lo)
            jh0, th0 = args
            B = jh0.shape[0]
            jh = jh0.at[ref.log_bin(jv, tcfg.lat_lo, tcfg.lat_hi,
                                    B)].add(jww)
            th = th0.at[ref.log_bin(tv, tcfg.lat_lo, tcfg.lat_hi,
                                    B)].add(tww)
            return jh, th

        small = (new_job.sum() <= Kc) & (new_task.sum() <= Kc)
        jh, th = jax.lax.cond(small, compact, dense, args)
        return jh, th, miss, tot, tail

    def no_finishes(args):
        jh0, th0 = args
        zero = jnp.zeros((), jnp.int32)
        return jh0, th0, zero, zero, zero

    jh, th, miss, tot, tail = jax.lax.cond(
        new_job.any() | new_task.any(), bin_finishes, no_finishes,
        (telem.job_hist, telem.task_hist))

    return replace(telem, job_hist=jh, task_hist=th,
                   sla_miss=telem.sla_miss + miss,
                   sla_total=telem.sla_total + tot,
                   tail_viol=telem.tail_viol + tail)


# ==========================================================================
# host-side summarization
# ==========================================================================

def bin_edges(tcfg: TelemetryConfig) -> np.ndarray:
    """(B+1,) log-spaced histogram bin edges in seconds."""
    return tcfg.lat_lo * (tcfg.lat_hi / tcfg.lat_lo) ** (
        np.arange(tcfg.n_bins + 1) / tcfg.n_bins)


def _centers(lo: float, hi: float, n_bins: int) -> np.ndarray:
    # geometric bin centers of the log-spaced grid
    return lo * (hi / lo) ** ((np.arange(n_bins) + 0.5) / n_bins)


def hist_percentile(hist, lo: float, hi: float, q: float) -> np.ndarray:
    """Percentile(s) recovered from log-spaced histogram(s).

    ``hist`` is (..., B); returns (...) — the geometric center of the first
    bin whose CDF reaches q%.  Error vs the exact percentile is at most one
    bin width.  Empty histograms return NaN (no warnings).
    """
    h = np.asarray(hist, np.float64)
    B = h.shape[-1]
    total = h.sum(axis=-1)
    cdf = np.cumsum(h, axis=-1)
    target = (q / 100.0) * total[..., None]
    idx = np.clip((cdf < target).sum(axis=-1), 0, B - 1)
    vals = _centers(lo, hi, B)[idx]
    return np.where(total > 0, vals, np.nan)


def hist_mean(hist, lo: float, hi: float) -> np.ndarray:
    """Mean latency estimated from log-spaced histogram(s) (..., B)."""
    h = np.asarray(hist, np.float64)
    total = h.sum(axis=-1)
    est = (h * _centers(lo, hi, h.shape[-1])).sum(axis=-1)
    return np.where(total > 0, est / np.maximum(total, 1.0), np.nan)


@dataclasses.dataclass
class TelemetrySummary:
    """Host-side view of one run's Telemetry (numpy)."""

    # histogram-derived latency percentiles (seconds)
    job_p50: float
    job_p95: float
    job_p99: float
    task_p50: float
    task_p95: float
    task_p99: float
    mean_latency: float             # histogram-estimated
    jobs_binned: int
    tasks_binned: int
    # QoS / SLA
    sla_miss: int
    sla_total: int
    tail_violations: int
    # energy·delay product (J·s): total energy × histogram mean latency
    energy_delay_product: float
    # windowed time series (time-averaged per window; NaN where empty)
    times: np.ndarray               # (W,) window centers (sec)
    occupancy: np.ndarray           # (W,) seconds of sim time per window
    active_jobs: np.ndarray         # (W,)
    awake_servers: np.ndarray       # (W,)
    queue_depth: np.ndarray         # (W,)
    server_power: np.ndarray        # (W,) watts
    switch_power: np.ndarray        # (W,) watts
    state_residency: np.ndarray     # (W, SrvState.NUM) seconds
    n_windows_used: int
    # thermal/carbon/cost series (zeros unless cfg.thermal.enabled)
    cooling_power: np.ndarray = None    # (W,) watts, time-averaged
    mean_temp: np.ndarray = None        # (W,) °C, farm mean
    max_temp: np.ndarray = None         # (W,) °C, farm max
    carbon_intensity: np.ndarray = None  # (W,) gCO2/kWh, time-averaged
    price: np.ndarray = None            # (W,) $/kWh, time-averaged
    carbon_per_window: np.ndarray = None  # (W,) grams CO2 (raw integral)
    cost_per_window: np.ndarray = None    # (W,) $ (raw integral)
    # seconds of sim time clamped into the last window because the run
    # outlived the n_windows·window_dt horizon; > 0 means the last
    # window's time-averaged series were NaN-ed out as contaminated
    # (raw integrals — occupancy, residency, carbon/cost — are kept)
    win_overflow: float = 0.0

    @property
    def last_window_contaminated(self) -> bool:
        return self.win_overflow > 0.0

    @property
    def sla_miss_rate(self) -> float:
        return self.sla_miss / max(self.sla_total, 1)


def summarize(state, cfg: SimConfig) -> TelemetrySummary:
    """Summarize a finished SimState's device telemetry on the host."""
    tcfg = cfg.telemetry
    if not tcfg.enabled:
        raise ValueError("telemetry was disabled for this run "
                         "(cfg.telemetry.enabled=False)")
    telem = state.telem
    jh = np.asarray(telem.job_hist)
    th = np.asarray(telem.task_hist)
    win = np.asarray(telem.win, np.float64)
    lo, hi = tcfg.lat_lo, tcfg.lat_hi

    occ = win[:, WIN_OCC]
    norm = np.where(occ > 0, occ, np.nan)
    used = int((occ > 0).sum())
    overflow = float(telem.win_overflow)
    if overflow > 0.0:
        # the run outlived the window horizon: the last window absorbed
        # the clamped tail, so its time-averages mix in-horizon and
        # post-horizon state — NaN them out rather than report a skewed
        # value (the raw integral columns are left intact)
        norm[-1] = np.nan
    energy = float(np.asarray(state.farm.energy).sum()
                   + np.asarray(state.net.sw_energy).sum())
    mean_lat = float(hist_mean(jh, lo, hi))
    return TelemetrySummary(
        job_p50=float(hist_percentile(jh, lo, hi, 50)),
        job_p95=float(hist_percentile(jh, lo, hi, 95)),
        job_p99=float(hist_percentile(jh, lo, hi, 99)),
        task_p50=float(hist_percentile(th, lo, hi, 50)),
        task_p95=float(hist_percentile(th, lo, hi, 95)),
        task_p99=float(hist_percentile(th, lo, hi, 99)),
        mean_latency=mean_lat,
        jobs_binned=int(jh.sum()),
        tasks_binned=int(th.sum()),
        sla_miss=int(telem.sla_miss),
        sla_total=int(telem.sla_total),
        tail_violations=int(telem.tail_viol),
        energy_delay_product=energy * mean_lat if mean_lat == mean_lat
        else float("nan"),
        times=(np.arange(tcfg.n_windows) + 0.5) * tcfg.window_dt,
        occupancy=occ,
        active_jobs=win[:, WIN_ACTIVE_JOBS] / norm,
        awake_servers=win[:, WIN_AWAKE] / norm,
        queue_depth=win[:, WIN_QDEPTH] / norm,
        server_power=win[:, WIN_SRV_POWER] / norm,
        switch_power=win[:, WIN_SW_POWER] / norm,
        state_residency=win[:, WIN_STATE0:WIN_STATE0 + SrvState.NUM],
        n_windows_used=used,
        cooling_power=win[:, WIN_COOL_POWER] / norm,
        mean_temp=win[:, WIN_MEAN_TEMP] / norm,
        max_temp=win[:, WIN_MAX_TEMP] / norm,
        carbon_intensity=win[:, WIN_CI] / norm,
        price=win[:, WIN_PRICE] / norm,
        carbon_per_window=win[:, WIN_CARBON_G],
        cost_per_window=win[:, WIN_COST],
        win_overflow=overflow,
    )

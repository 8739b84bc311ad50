"""Thermal / cooling / carbon-cost subsystem.

HolDCSim's thesis is *holistic* co-simulation; this module carries the
simulation past the electrical boundary: the power accounted by
``power.py`` becomes heat, heat becomes cooling load, and both become
grams of CO2 and dollars — with two couplings back into behavior
(temperature-triggered throttling and thermal-aware placement).

Model
-----
Per-server thermal RC dynamics (cf. rack thermal models in
energy-aware-DC literature, e.g. Buyya et al. arXiv:1006.0308):

    T' = (P·r_th − (T − T_inlet)) / tau_th

Between DES events power is piecewise constant, so the ODE has the exact
closed-form update

    T += (P·r_th + T_inlet − T) · (1 − exp(−dt/tau_th))

which slots into the engine's accrual phase with zero discretization
error — the same trick the exact energy integration uses.  Rack-level
recirculation couples a server's inlet to its rack's mean excess
temperature; the inlet is held piecewise constant per interval
(recomputed from the pre-interval temperatures at every event), the
standard operator split for coupled RC networks in a DES.

CRAC/PUE: cooling power = P_IT / COP(T_setpoint) with the classic
quadratic chilled-water COP curve (cop_a·T² + cop_b·T + cop_c).  With one
static setpoint COP folds to a python constant at trace time; the control
plane (``t_setpoint`` / the setpoint controller) turns the setpoints into
per-rack *state* (``ThermalState.t_set``), each rack's IT load cooled at
its own in-trace quadratic COP, and an optional controller walks the
setpoints toward a target peak temperature on a control period (a real
event source).  A diurnal ambient sinusoid (``ambient_swing``) rides on
the supply temperature — held piecewise constant per event interval, the
same operator split as the recirculation, so the RC update stays exact.

Carbon & cost: grid carbon intensity (gCO2/kWh) and electricity price
($/kWh) follow diurnal sinusoids integrated in CLOSED FORM over each
event interval (∫ base·(1+swing·sin(2π(t+φ)/period)) dt), so the
accumulated grams/dollars are exact, not sampled.

Throttling: a server at/above ``t_throttle`` latches into a throttled
state (released below ``t_release`` — hysteresis) where its effective
core frequency is ``core_freq·throttle_freq``; in-flight work stretches
(``core_busy_until``/``task_end`` rescaled about *now*) and active-core
power scales by ``throttle_power_scale``.  Threshold crossings between
events are real events: :func:`next_crossing` solves the exponential for
the crossing time, so the engine advances exactly to the flip.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import power
from .types import (INF, SimConfig, TaskStatus, ThermalConfig, ThermalState,
                    replace)

__all__ = ["init_thermal", "inlet_temps", "advance", "apply_throttle",
           "next_crossing", "effective_freq", "cooling_power", "cop_at",
           "ambient", "apply_setpoint_ctrl", "defer_signal_now",
           "next_release_time", "rate_integral", "TEMP_TOL"]

# flip tolerance (°C): crossings land within f32 rounding of the
# threshold, so the hysteresis predicate accepts T >= t_throttle - TOL
TEMP_TOL = 1.0e-3
# relative overshoot applied to solved crossing times so the integrated
# temperature robustly lands past the threshold (cf. the delay-timer
# livelock note in scheduler.timer_transitions)
_CROSS_EPS = 1.0e-5


def init_thermal(cfg: SimConfig, racks=None) -> ThermalState:
    """Zeroed thermal pytree.  ``racks`` is an optional (N,) host array of
    rack ids (e.g. :func:`topology.rack_of_servers`); default grouping is
    ``i // cfg.thermal.rack_size``.  Minimal (1,)-sized arrays when the
    subsystem is disabled so the off path carries no cost."""
    tcfg = cfg.thermal
    if not tcfg.enabled:
        z = jnp.zeros((1,), jnp.float32)
        zs = jnp.zeros((), jnp.float32)
        return ThermalState(
            t_srv=z, throttled=jnp.zeros((1,), bool),
            rack_id=jnp.zeros((1,), jnp.int32),
            rack_onehot=jnp.zeros((1, 1), jnp.float32),
            rack_inv=z, t_set=z,
            ctrl_next=jnp.asarray(INF, cfg.time_dtype),
            t_peak=z, throttle_seconds=z,
            cool_energy=zs, carbon_g=zs, cost=zs,
            defer_seconds=zs, defer_count=jnp.zeros((), jnp.int32),
            grams_avoided=zs)

    N = cfg.n_servers
    if racks is None:
        racks = np.arange(N) // max(tcfg.rack_size, 1)
    racks = np.asarray(racks, np.int64)
    if racks.shape != (N,):
        raise ValueError(f"racks must be ({N},), got {racks.shape}")
    _, dense = np.unique(racks, return_inverse=True)   # 0..R-1, dense
    R = int(dense.max()) + 1
    counts = np.bincount(dense, minlength=R)
    # contiguous equal-size blocks (the i // rack_size default and every
    # built-in topology grouping) reduce by reshape — O(N) instead of the
    # (R, N) one-hot matmul, which at 20K servers would mean ~200 MB of
    # constant state and a ~50M-MAC pass per event.  The empty (0, 0)
    # onehot is the static marker for the fast path (inlet_temps).
    contiguous = N % R == 0 and (counts == N // R).all() \
        and (dense == np.arange(N) // (N // R)).all()
    if contiguous:
        onehot = np.zeros((0, 0), np.float32)
    else:
        onehot = (dense[None, :]
                  == np.arange(R)[:, None]).astype(np.float32)
    sp = tcfg.t_inlet if tcfg.t_setpoint is None else tcfg.t_setpoint
    try:
        t_set = np.broadcast_to(np.asarray(sp, np.float32), (R,))
    except ValueError:
        raise ValueError(
            f"t_setpoint must be a scalar or length-{R} (one per rack) "
            f"sequence, got {np.asarray(sp).shape}")
    # servers start at their own rack's supply temperature (the cold-aisle
    # fixed point of an unloaded rack, like the old uniform t_inlet)
    t0 = t_set[dense] + np.float32(ambient_host(tcfg, 0.0))
    ctrl_next = tcfg.ctrl_period if tcfg.has_ctrl else INF
    zs = jnp.zeros((), jnp.float32)
    return ThermalState(
        t_srv=jnp.asarray(t0, jnp.float32),
        throttled=jnp.zeros((N,), bool),
        rack_id=jnp.asarray(dense, jnp.int32),
        rack_onehot=jnp.asarray(onehot),
        rack_inv=jnp.asarray(1.0 / counts, jnp.float32),
        t_set=jnp.asarray(t_set, jnp.float32),
        ctrl_next=jnp.asarray(ctrl_next, cfg.time_dtype),
        t_peak=jnp.asarray(t0, jnp.float32),
        throttle_seconds=jnp.zeros((N,), jnp.float32),
        cool_energy=zs, carbon_g=zs, cost=zs,
        defer_seconds=zs, defer_count=jnp.zeros((), jnp.int32),
        grams_avoided=zs)


# ==========================================================================
# continuous models
# ==========================================================================

def ambient_host(tcfg: ThermalConfig, t: float) -> float:
    """Host-side diurnal ambient offset at time ``t`` (°C)."""
    if tcfg.ambient_swing == 0.0:
        return 0.0
    w = 2.0 * math.pi / tcfg.ambient_period
    return tcfg.ambient_swing * math.sin(w * (t + tcfg.ambient_phase))


def ambient(tcfg: ThermalConfig, t) -> jnp.ndarray:
    """In-trace diurnal ambient offset at time ``t`` (scalar, °C)."""
    w = 2.0 * math.pi / tcfg.ambient_period
    tf = t.astype(jnp.float32) if hasattr(t, "astype") else jnp.float32(t)
    return jnp.float32(tcfg.ambient_swing) \
        * jnp.sin(w * (tf + tcfg.ambient_phase))


def _rack_sums(therm: ThermalState, vals):
    """(R,) per-rack sums of a per-server vector.  Contiguous equal-size
    racks (the empty-onehot marker, set at init) reduce by reshape in
    O(N); irregular groupings fall back to the one-hot matmul, which
    still beats a segment-sum scatter on XLA:CPU."""
    R = therm.rack_inv.shape[0]
    if therm.rack_onehot.size == 0:                # contiguous fast path
        return vals.reshape(R, -1).sum(axis=1)
    # full f32: the TPU's default matmul precision rounds through bf16
    return jnp.matmul(therm.rack_onehot, vals,
                      precision=jax.lax.Precision.HIGHEST)


def inlet_temps(therm: ThermalState, tcfg: ThermalConfig,
                t=None) -> jnp.ndarray:
    """(N,) per-server inlet: rack supply temperature + recirc·rack-mean
    excess.  The supply temperature is the static ``t_inlet`` constant on
    the uniform path, or per-rack ``t_set`` (+ the diurnal ambient at
    ``t``) when the control plane is active — held piecewise constant per
    event interval (the operator split the RC exactness relies on)."""
    if not tcfg.per_rack and not tcfg.ambient_on:
        # static path, bit-identical to the pre-control-plane expression
        excess = therm.t_srv - tcfg.t_inlet
        mean = _rack_sums(therm, excess) * therm.rack_inv          # (R,)
        return tcfg.t_inlet + tcfg.recirc * mean[therm.rack_id]
    base_r = therm.t_set                                           # (R,)
    if tcfg.ambient_on:
        base_r = base_r + ambient(tcfg, t)
    base = base_r[therm.rack_id]                                   # (N,)
    excess = therm.t_srv - base
    mean = _rack_sums(therm, excess) * therm.rack_inv
    return base + tcfg.recirc * mean[therm.rack_id]


def cop_at(tcfg: ThermalConfig, t_sup):
    """In-trace quadratic COP at supply temperature(s) ``t_sup``."""
    return tcfg.cop_a * t_sup * t_sup + tcfg.cop_b * t_sup + tcfg.cop_c


def cooling_power(p_srv, p_sw, therm: ThermalState, tcfg: ThermalConfig):
    """CRAC power (W) for the per-server IT load ``p_srv`` (N,) plus
    switch load ``p_sw``.  Uniform setpoints fold COP to the static
    python constant; per-rack setpoints cool each rack's load at its own
    in-trace quadratic COP (switch load is cooled at the mean setpoint's
    COP — switches sit outside the rack model)."""
    if not tcfg.per_rack:
        return (p_srv.sum() + p_sw) / tcfg.cop
    rack_p = _rack_sums(therm, p_srv)                              # (R,)
    return (rack_p / cop_at(tcfg, therm.t_set)).sum() \
        + p_sw / cop_at(tcfg, therm.t_set.mean())


def rate_integral(base: float, swing: float, period: float, phase: float,
                  t1, t2):
    """∫_{t1}^{t2} base·(1 + swing·sin(2π(t+phase)/period)) dt, closed
    form — exact accumulation of the diurnal carbon/price series."""
    w = 2.0 * math.pi / period
    t1f = t1.astype(jnp.float32) if hasattr(t1, "astype") else jnp.float32(t1)
    t2f = t2.astype(jnp.float32) if hasattr(t2, "astype") else jnp.float32(t2)
    lin = t2f - t1f
    osc = (jnp.cos(w * (t1f + phase)) - jnp.cos(w * (t2f + phase))) / w
    return base * (lin + swing * osc)


def carbon_price_integrals(tcfg: ThermalConfig, t, dt):
    """(∫ci dt, ∫price dt) over [t, t+dt) — the window-exact series."""
    ci = rate_integral(tcfg.carbon_base, tcfg.carbon_swing,
                       tcfg.carbon_period, tcfg.carbon_phase, t, t + dt)
    pr = rate_integral(tcfg.price_base, tcfg.price_swing,
                       tcfg.price_period, tcfg.price_phase, t, t + dt)
    return ci, pr


def effective_freq(therm: ThermalState, cfg: SimConfig) -> jnp.ndarray:
    """(N,) effective core frequency under the throttle latch."""
    return jnp.where(therm.throttled,
                     jnp.float32(cfg.core_freq * cfg.thermal.throttle_freq),
                     jnp.float32(cfg.core_freq))


# ==========================================================================
# in-loop updates
# ==========================================================================

def advance(therm: ThermalState, cfg: SimConfig, p_srv, p_sw, t,
            dt, t_new=None, p_cool=None) -> ThermalState:
    """Integrate temperatures, cooling energy, carbon, and cost over the
    piecewise-constant interval [t, t+dt).  ``p_srv`` (N,) is the
    per-server power of the PRE-advance state (throttle-scaled), ``p_sw``
    the total switch power.  ``t_new`` / ``p_cool`` optionally supply the
    already computed end-of-interval temperatures and CRAC power (the
    engine's advance shares one RC + COP evaluation with the telemetry
    window columns)."""
    tcfg = cfg.thermal
    dtf = dt.astype(jnp.float32)
    if t_new is None:
        target = p_srv * tcfg.r_th + inlet_temps(therm, tcfg, t)
        alpha = 1.0 - jnp.exp(-dtf / tcfg.tau_th)
        t_new = therm.t_srv + (target - therm.t_srv) * alpha
    # temperature is monotone toward target within the interval, so the
    # endpoint max tracks the true running peak exactly
    t_peak = jnp.maximum(therm.t_peak, t_new)
    throttle_s = therm.throttle_seconds \
        + therm.throttled.astype(jnp.float32) * dtf

    p_it = p_srv.sum() + p_sw
    if p_cool is None:
        p_cool = cooling_power(p_srv, p_sw, therm, tcfg)
    p_tot = p_it + p_cool
    ici, ipr = carbon_price_integrals(tcfg, t, dt)
    kw = p_tot * jnp.float32(1.0e-3)
    return replace(
        therm, t_srv=t_new, t_peak=t_peak, throttle_seconds=throttle_s,
        cool_energy=therm.cool_energy + p_cool * dtf,
        carbon_g=therm.carbon_g + kw * ici / 3600.0,
        cost=therm.cost + kw * ipr / 3600.0)


def apply_throttle(farm, jobs, therm: ThermalState, cfg: SimConfig, now):
    """Hysteresis latch update + in-flight work stretch at time ``now``.

    Servers crossing ``t_throttle`` upward engage, servers cooled to the
    release threshold disengage; on any flip the remaining service of
    in-flight tasks rescales about *now* by the frequency ratio —
    elementwise in core space (``core_busy_until``) and, with the same
    expression, elementwise in task space (``task_end`` via each task's
    assigned server), so completion bookkeeping stays scatter-free and
    bit-consistent.  Returns (farm, jobs, therm)."""
    tcfg = cfg.thermal
    thr = tcfg.t_throttle
    rel = min(tcfg.t_release, tcfg.t_throttle)
    t = therm.t_srv
    engage = ~therm.throttled & (t >= thr - TEMP_TOL)
    release = therm.throttled & (t <= rel + TEMP_TOL)
    new_throttled = (therm.throttled | engage) & ~release
    changed = new_throttled != therm.throttled

    def stretch(args):
        farm, jobs = args
        tf = jnp.float32(tcfg.throttle_freq)
        f_old = jnp.where(therm.throttled, tf, jnp.float32(1.0))
        f_new = jnp.where(new_throttled, tf, jnp.float32(1.0))
        ratio = f_old / f_new                                   # (N,)
        bu = farm.core_busy_until
        in_flight = (bu < INF) & (bu > now) & changed[:, None]
        bu = jnp.where(in_flight, now + (bu - now) * ratio[:, None], bu)
        farm = replace(farm, core_busy_until=bu)

        srv = jnp.clip(jobs.server, 0)
        te = jobs.task_end
        run = (jobs.status == TaskStatus.RUNNING) & (te < INF) \
            & (te > now) & changed[srv] & (jobs.server >= 0)
        te = jnp.where(run, now + (te - now) * ratio[srv], te)
        return farm, replace(jobs, task_end=te)

    farm, jobs = jax.lax.cond(changed.any(), stretch, lambda a: a,
                              (farm, jobs))
    return farm, jobs, replace(therm, throttled=new_throttled)


def next_crossing(state, cfg: SimConfig) -> jnp.ndarray:
    """Earliest throttle engage/release threshold crossing (scalar; INF if
    none) — a real event source: solving T(t) = threshold on the
    exponential keeps throttling exact instead of checked-at-events.

    The solve (a power evaluation + rack recirculation + masked logs,
    ~4 dense passes) is cond-gated on "any server within
    ``crossing_guard`` °C of its pending threshold" — far from the
    thresholds the candidate is INF without touching the farm arrays,
    which removes the throttling event source's per-step cost from the
    common no-crossing-imminent regime.  Servers outside the band engage
    at the next ordinary event (apply_throttle checks every step) rather
    than at the exact crossing instant; crossing_guard=INF restores the
    always-solve exact behavior.  The numpy oracle mirrors the band."""
    tcfg = cfg.thermal
    therm = state.thermal
    t = therm.t_srv
    thr = tcfg.t_throttle
    rel = min(tcfg.t_release, tcfg.t_throttle)
    guard = tcfg.crossing_guard
    near_up = ~therm.throttled & (t >= thr - guard)
    near_dn = therm.throttled & (t <= rel + guard)

    def solve_all(_):
        p_srv, _b = power.server_power(state.farm, cfg,
                                       throttled=therm.throttled)
        # the inlet (incl. the diurnal ambient) is evaluated at state.t
        # and held constant — exactly the piecewise-constant-inlet target
        # the interval integrator uses, so the solved crossing is exact
        # w.r.t. the dynamics actually integrated
        target = p_srv * tcfg.r_th + inlet_temps(therm, tcfg, state.t)

        def solve(valid, num, den):
            arg = jnp.where(valid, num / den, jnp.float32(2.0))
            return jnp.where(valid & (arg > 1.0),
                             tcfg.tau_th * jnp.log(arg), INF)

        up = near_up & (t < thr - TEMP_TOL) & (target > thr)
        dt_up = solve(up, target - t, target - thr)
        dn = near_dn & (t > rel + TEMP_TOL) & (target < rel)
        dt_dn = solve(dn, t - target, rel - target)
        return jnp.minimum(dt_up, dt_dn).min()

    dt_min = jax.lax.cond((near_up | near_dn).any(), solve_all,
                          lambda _: jnp.float32(INF), None)
    t_cross = (state.t + dt_min * (1.0 + _CROSS_EPS) + 1.0e-9) \
        .astype(cfg.time_dtype)
    # at large t a small solved dt can round t_cross back onto state.t in
    # the time dtype (ulp(86400 f32) ~ 8 ms), freezing time while the
    # identical crossing is re-solved every step until max_events burns:
    # force at least one representable tick of progress — the tiny-dt
    # integration still moves T through the TEMP_TOL band in a step or two
    t_cross = jnp.maximum(
        t_cross, jnp.nextafter(state.t.astype(cfg.time_dtype),
                               jnp.asarray(INF, cfg.time_dtype)))
    return jnp.where(dt_min < INF / 2, t_cross, INF).astype(cfg.time_dtype)


# ==========================================================================
# control plane: setpoint controller + carbon-aware deferral
# ==========================================================================

def apply_setpoint_ctrl(therm: ThermalState, cfg: SimConfig,
                        now) -> ThermalState:
    """Per-rack setpoint controller tick at time ``now`` (no-op until
    ``therm.ctrl_next``).  Each rack whose hottest server exceeds
    ``ctrl_target`` lowers its supply setpoint by ``ctrl_step`` (colder
    air, worse COP); racks sitting below ``ctrl_target − ctrl_band``
    raise it (cheaper cooling), clipped into [ctrl_min, ctrl_max].  Only
    traced when ``cfg.thermal.has_ctrl``."""
    tcfg = cfg.thermal

    def tick(therm):
        R = therm.rack_inv.shape[0]
        if therm.rack_onehot.size == 0:
            rack_max = therm.t_srv.reshape(R, -1).max(axis=1)
        else:
            rack_max = jnp.where(therm.rack_onehot > 0,
                                 therm.t_srv[None, :],
                                 -jnp.float32(INF)).max(axis=1)
        down = rack_max > tcfg.ctrl_target
        up = ~down & (rack_max < tcfg.ctrl_target - tcfg.ctrl_band)
        step = jnp.float32(tcfg.ctrl_step)
        t_set = jnp.clip(
            therm.t_set - jnp.where(down, step, 0.0)
            + jnp.where(up, step, 0.0),
            jnp.float32(tcfg.ctrl_min), jnp.float32(tcfg.ctrl_max))
        # at least one representable tick of progress (cf. next_crossing:
        # a period below ulp(now) would freeze the event clock)
        nxt = jnp.maximum(
            (therm.ctrl_next + tcfg.ctrl_period).astype(cfg.time_dtype),
            jnp.nextafter(now.astype(cfg.time_dtype),
                          jnp.asarray(INF, cfg.time_dtype)))
        return replace(therm, t_set=t_set, ctrl_next=nxt)

    return jax.lax.cond(now >= therm.ctrl_next, tick, lambda th: th, therm)


def _defer_params(tcfg: ThermalConfig):
    """(base, swing, period, phase) of the deferral signal sinusoid."""
    if tcfg.defer_signal == "price":
        return (tcfg.price_base, tcfg.price_swing, tcfg.price_period,
                tcfg.price_phase)
    if tcfg.defer_signal != "carbon":
        raise ValueError(f"defer_signal must be 'carbon' or 'price', "
                         f"got {tcfg.defer_signal!r}")
    return (tcfg.carbon_base, tcfg.carbon_swing, tcfg.carbon_period,
            tcfg.carbon_phase)


def _sinusoid_now(base, swing, period, phase, t):
    w = 2.0 * math.pi / period
    tf = t.astype(jnp.float32) if hasattr(t, "astype") else jnp.float32(t)
    return jnp.float32(base) * (1.0 + swing * jnp.sin(w * (tf + phase)))


def defer_signal_now(tcfg: ThermalConfig, t) -> jnp.ndarray:
    """Instantaneous deferral signal (carbon gCO2/kWh or price $/kWh)."""
    return _sinusoid_now(*_defer_params(tcfg), t)


def carbon_intensity_now(tcfg: ThermalConfig, t) -> jnp.ndarray:
    """Instantaneous grid carbon intensity (gCO2/kWh) at time ``t`` —
    the grams-avoided estimator reads this regardless of which signal
    drives the deferral decision."""
    return _sinusoid_now(tcfg.carbon_base, tcfg.carbon_swing,
                         tcfg.carbon_period, tcfg.carbon_phase, t)


def next_release_time(tcfg: ThermalConfig, t):
    """Earliest t' >= t where the deferral signal sits at/below
    ``defer_threshold`` — the solved DOWN-crossing of the sinusoid
    (scalar; INF when the signal never crosses down, i.e. the threshold
    sits below the trough, in which case only deadlines admit).  All the
    trigonometry except the mod-2π shift is host-side constants; the
    traced shift runs in ``t``'s own dtype, so a float64 event clock
    (x64 mode) keeps float64 release times instead of collapsing to f32
    ulps at large t (with the default f32 clock the result carries the
    same ulp error as every other event time)."""
    base, swing, period, phase = _defer_params(tcfg)
    thr = tcfg.defer_threshold
    if base <= 0.0 or swing == 0.0 or thr >= INF / 2:
        return jnp.float32(INF)
    s = (thr / base - 1.0) / swing
    if s >= 1.0:       # signal never exceeds thr: deferral never triggers
        return jnp.float32(INF)
    if s <= -1.0:      # signal always above thr: no down-crossing exists
        return jnp.float32(INF)
    w = 2.0 * math.pi / period
    theta_dn = math.pi - math.asin(s)    # sin decreasing through s
    dt_t = t.dtype if hasattr(t, "dtype") else jnp.float32
    tf = jnp.asarray(t, dt_t)
    k = jnp.ceil((w * (tf + phase) - theta_dn) / (2.0 * math.pi))
    return ((theta_dn + 2.0 * math.pi * k) / w - phase).astype(dt_t)

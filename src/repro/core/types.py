"""Core pytree/state types for the HolDCSim-JAX engine.

Design notes
------------
The original HolDCSim is an object-oriented, priority-queue event simulator.
The TPU adaptation (DESIGN.md §3) replaces the heap with dense fixed-shape
state arrays; every "event source" exposes a vector of candidate next-event
times and the engine advances to the global minimum.  All types here are
either *static* configuration (frozen dataclasses hashable for jit) or
*dynamic* state (registered pytree dataclasses of jnp arrays).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

# A "practically infinite" simulation time.  Using a finite sentinel (rather
# than jnp.inf) keeps min-reductions well-defined under f32 and survives
# subtraction without producing NaNs.
INF = 1.0e30

# --------------------------------------------------------------------------
# enums (plain ints so they can live inside jnp arrays)
# --------------------------------------------------------------------------


class SrvState:
    """Hierarchical ACPI-style server power states (paper §III-A)."""

    ACTIVE = 0        # S0, at least one core in C0
    IDLE = 1          # S0, all cores idle (C1)
    PKG_C6 = 2        # package C6: cores+uncore power-gated, fast wake (<1ms)
    S3 = 3            # suspend-to-RAM, slow wake
    OFF = 4           # G2 soft-off
    WAKING = 5        # transitioning to ACTIVE
    NUM = 6


class CoreState:
    C0 = 0            # executing
    C1 = 1            # halt, clock-gated
    C6 = 2            # core power-gated
    NUM = 3


class TaskStatus:
    BLOCKED = 0       # waiting on DAG parents
    READY = 1         # deps satisfied, not yet enqueued at its server
    QUEUED = 2        # sitting in a local/global queue
    RUNNING = 3       # on a core
    COMM = 4          # finished compute, results in flight to children
    DONE = 5
    INVALID = 6       # padding
    NUM = 7


class PortState:
    ACTIVE = 0
    LPI = 1           # IEEE 802.3az Low Power Idle
    OFF = 2
    NUM = 3


class LinecardState:
    ACTIVE = 0
    SLEEP = 1
    OFF = 2
    NUM = 3


class SchedPolicy:
    ROUND_ROBIN = 0
    LOAD_BALANCE = 1       # least queue+running occupancy
    NETWORK_AWARE = 2      # least network wake cost (case study D)
    PROVISIONED = 3        # threshold-driven active-set (case study A)
    WASP_POOLS = 4         # two-pool workload adaptive (case study C)
    THERMAL_AWARE = 5      # coolest eligible server (thermal subsystem)
    CARBON_AWARE = 6       # LOAD_BALANCE placement + deferrable jobs held
                           # while the carbon/price signal is above
                           # ThermalConfig.defer_threshold (thermal ctrl
                           # plane); released at the solved sinusoid
                           # down-crossing or at their deadline


class SleepPolicy:
    """Local (per-server) power controller."""

    ALWAYS_ON = 0          # Active-Idle baseline
    SINGLE_TIMER = 1       # idle --tau--> deep state
    DUAL_TIMER = 2         # per-server tau (two pools with low/high tau)
    WASP = 3               # shallow PkgC6 in active pool; PkgC6->S3 in sleep pool


class TraceKind:
    """Event kinds recorded by the device-side flight recorder
    (core/trace.py).  Values are stable — they appear in exported traces
    and in the oracle mirror (tests/oracle.py)."""

    ARRIVAL = 0            # job's arrival processed (tid = job id)
    ADMIT = 1              # job admitted/placed (tid = job id, server =
                           # first task's server, aux = queue depth there)
    RELEASE = 2            # carbon-deferred job released (aux = seconds held)
    START = 3              # task started on a core (aux = stretched duration)
    FINISH = 4             # task finished compute
    JOB_FINISH = 5         # last task of a job done (tid = job id,
                           # aux = job latency)
    WAKEUP = 6             # server wake transition completed
    SLEEP = 7              # server entered a sleep state (aux = SrvState)
    DROP = 8               # task dropped on a full queue
    FLOW_SPAWN = 9         # network flow spawned (server = src,
                           # tid = child task, aux = bytes)
    FLOW_FINISH = 10       # network flow delivered (server = dst,
                           # tid = child task)
    THROTTLE_CROSSING = 11  # thermal throttle engaged/released
                            # (aux = temperature °C)
    CTRL_TICK = 12         # CRAC setpoint controller tick
    NUM = 13

    NAMES = ("arrival", "admit", "release", "start", "finish", "job_finish",
             "wakeup", "sleep", "drop", "flow_spawn", "flow_finish",
             "throttle_crossing", "ctrl_tick")


# --------------------------------------------------------------------------
# pytree dataclass helper
# --------------------------------------------------------------------------

def pytree_dataclass(cls):
    """A dataclass whose fields are all pytree leaves (jnp arrays)."""
    cls = dataclasses.dataclass(cls)
    fields = [f.name for f in dataclasses.fields(cls)]
    jax.tree_util.register_dataclass(cls, data_fields=fields, meta_fields=[])
    return cls


def replace(obj, **kw):
    return dataclasses.replace(obj, **kw)


# --------------------------------------------------------------------------
# static configuration
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ServerPowerProfile:
    """Per-server power (Watts) by state; loosely calibrated to a 10-core
    Xeon E5-2680 class machine (paper §V-A) and the ACPI hierarchy."""

    p_core_active: float = 13.0     # C0, per core
    p_core_idle: float = 2.0        # C1, per core
    p_core_c6: float = 0.3          # core C6, per core
    p_base: float = 65.0            # uncore+platform when in S0
    p_pkg_c6: float = 15.0          # package C6 (uncore gated, DRAM refresh)
    p_s3: float = 9.0               # suspend to RAM
    p_off: float = 0.0
    p_wake: float = 145.0           # burst draw during wake transition
    # transition latencies (seconds)
    t_wake_pkg_c6: float = 1.0e-3   # <1ms per paper §IV-C
    t_wake_s3: float = 1.0          # seconds-scale resume
    t_wake_off: float = 30.0        # full boot
    t_core_c6_wake: float = 5.0e-5

    def active_power(self, busy_cores: int, total_cores: int) -> float:
        idle = total_cores - busy_cores
        return (self.p_base + busy_cores * self.p_core_active
                + idle * self.p_core_idle)


@dataclass(frozen=True)
class SwitchPowerProfile:
    """Cisco WS-C2960-24-S calibration from the paper's §V-B: measured base
    14.7 W plus 0.23 W per active port."""

    p_chassis: float = 14.7
    p_port_active: float = 0.23
    p_port_lpi: float = 0.023       # ~10% of active, 802.3az ballpark
    p_port_off: float = 0.0
    p_linecard_active: float = 0.0  # folded into chassis for small switches
    p_linecard_sleep: float = 0.0
    t_lpi_wake: float = 5.0e-6      # 802.3az refresh/wake ~ microseconds
    t_port_lpi_enter: float = 1.0e-3  # idle threshold before entering LPI
    t_switch_wake: float = 0.5      # waking a slept switch (case study D)


@dataclass(frozen=True)
class ThermalConfig:
    """Thermal / cooling / carbon-cost subsystem knobs (core/thermal.py).

    Per-server thermal RC model: ``T' = (P·r_th − (T − T_inlet)) / tau_th``.
    Power is piecewise constant between DES events, so the closed-form
    exponential update integrates the ODE with zero discretization error —
    the same trick as the exact energy accrual.  Rack-level recirculation
    couples a server's inlet to its rack's mean excess temperature (held
    piecewise constant per interval, recomputed at every event).

    All behavioral couplings are off by default: ``enabled=False`` adds
    nothing to the step, and ``t_throttle=INF`` disables throttling even
    when temperatures are tracked.
    """

    enabled: bool = False
    # RC parameters: steady state T = T_inlet + P·r_th
    r_th: float = 0.25          # °C per Watt of server power
    tau_th: float = 60.0        # thermal time constant (seconds)
    t_inlet: float = 22.0       # CRAC supply / cold-aisle setpoint (°C)
    # --- control plane -----------------------------------------------
    # per-rack CRAC supply setpoints: None = one uniform setpoint
    # (t_inlet, the static path — COP folds to a Python constant at trace
    # time); a scalar or length-R tuple makes the setpoints *state*
    # (ThermalState.t_set) and COP a per-rack quadratic evaluated
    # in-trace, so each rack's supply temperature carries its own
    # cooling-efficiency cost
    t_setpoint: object = None
    # diurnal ambient sinusoid added onto the supply/cold-aisle
    # temperature: amb(t) = ambient_swing·sin(2π(t+ambient_phase)/
    # ambient_period) °C.  The RC integration stays exact per interval —
    # the inlet is held piecewise constant (evaluated at interval start),
    # the same operator split as the rack recirculation — and the
    # throttle crossing solve honors the time-varying target.
    ambient_swing: float = 0.0
    ambient_period: float = 86400.0
    ambient_phase: float = 0.0
    # simple per-rack setpoint controller: every ctrl_period seconds (a
    # real event) each rack's setpoint steps DOWN by ctrl_step when its
    # hottest server exceeds ctrl_target, UP when it sits below
    # ctrl_target - ctrl_band (cheaper cooling via a better COP), clipped
    # into [ctrl_min, ctrl_max].  ctrl_period = 0 disables.
    ctrl_period: float = 0.0
    ctrl_target: float = 55.0
    ctrl_band: float = 2.0
    ctrl_step: float = 1.0
    ctrl_min: float = 12.0
    ctrl_max: float = 27.0
    # carbon-aware deferral (SchedPolicy.CARBON_AWARE): deferrable jobs
    # arriving while the defer_signal ("carbon" or "price") sits above
    # defer_threshold are held unadmitted and released at the solved
    # sinusoid down-crossing or at their deadline, whichever is earlier
    # (a deadline at/before now — or no finite release candidate at all —
    # admits immediately, so deferral can never deadlock).  INF = never
    # defer.
    defer_threshold: float = INF
    defer_signal: str = "carbon"
    # rack recirculation: inlet_i = t_inlet + recirc·rack_mean(T − t_inlet)
    recirc: float = 0.2
    rack_size: int = 8          # servers per rack (rack id = i // rack_size
                                # unless a topology grouping is supplied)
    # temperature-coupled throttling with hysteresis: servers at/above
    # t_throttle run at core_freq·throttle_freq (in-flight work stretches)
    # until they cool to t_release; active-core power scales by
    # throttle_power_scale while throttled (linear-DVFS approximation)
    t_throttle: float = INF     # °C; INF = never throttle
    t_release: float = INF      # effective release = min(t_release, t_throttle)
    throttle_freq: float = 0.5
    throttle_power_scale: float = 0.5
    # crossing-solve guard band (°C): the per-step analytic crossing solve
    # (thermal.next_crossing — power eval + inlet recirculation + logs) is
    # cond-gated on "any server within this band of its pending threshold"
    # (t_throttle from below when unthrottled, t_release from above when
    # throttled).  Servers outside the band latch at the next ordinary
    # event instead of at the exact crossing instant, which only matters
    # when a temperature jumps the whole band within one event interval.
    # INF = solve every step (exact crossings regardless of distance).
    crossing_guard: float = 8.0
    # CRAC efficiency: COP(T_sup) = cop_a·T² + cop_b·T + cop_c evaluated at
    # the (static) supply setpoint; cooling power = P_IT / COP
    cop_a: float = 0.0068
    cop_b: float = 0.0008
    cop_c: float = 0.458
    # grid carbon intensity (gCO2/kWh) and electricity price ($/kWh):
    # diurnal sinusoids base·(1 + swing·sin(2π(t+phase)/period)) integrated
    # in closed form over each event interval
    carbon_base: float = 350.0
    carbon_swing: float = 0.4
    carbon_period: float = 86400.0
    carbon_phase: float = 0.0
    price_base: float = 0.12
    price_swing: float = 0.5
    price_period: float = 86400.0
    price_phase: float = 0.0
    # THERMAL_AWARE placement: score = load + (T − t_inlet)·weight
    sched_temp_weight: float = 100.0

    @property
    def cop(self) -> float:
        t = self.t_inlet
        return self.cop_a * t * t + self.cop_b * t + self.cop_c

    @property
    def throttling(self) -> bool:
        return self.enabled and self.t_throttle < INF / 2

    @property
    def has_ctrl(self) -> bool:
        """Setpoint controller armed (control-period ticks are events)."""
        return self.enabled and self.ctrl_period > 0.0

    @property
    def per_rack(self) -> bool:
        """Setpoints live in ThermalState (in-trace per-rack COP) instead
        of folding to the static t_inlet constant."""
        return self.enabled and (self.t_setpoint is not None
                                 or self.has_ctrl)

    @property
    def ambient_on(self) -> bool:
        return self.enabled and self.ambient_swing != 0.0

    @property
    def deferral(self) -> bool:
        """CARBON_AWARE deferral armed (a finite signal threshold)."""
        return self.enabled and self.defer_threshold < INF / 2


@dataclass(frozen=True)
class TelemetryConfig:
    """Device-side telemetry (histograms / windowed series / QoS) knobs.

    The simulator accumulates distributions *inside* the jitted event loop
    (core/telemetry.py) so replica sweeps never haul per-job tables off
    device.  All fields are static (hashable) — they size the Telemetry
    pytree arrays.
    """

    enabled: bool = True
    # log-spaced latency histogram: n_bins bins over [lat_lo, lat_hi) sec
    n_bins: int = 64
    lat_lo: float = 1.0e-5
    lat_hi: float = 1.0e3
    # windowed time series: n_windows buckets of window_dt seconds (times
    # past the last window clamp into it)
    n_windows: int = 256
    window_dt: float = 0.1
    # QoS: job latency above this counts as a tail-latency violation;
    # per-job deadlines come from JobTable.sla
    tail_thresh: float = 1.0
    # route the hot accumulation through the fused Pallas kernel
    # (kernels/telemetry_bin.py); interpreted on CPU, compiled on TPU
    use_kernel: bool = False
    # compact the "new finishes" set into a batch of this size before
    # histogram binning when few jobs/tasks finished this step (the jnp
    # path otherwise pays dense (J·T)-wide binning per finishing step);
    # 0 disables compaction, and steps with more finishes than the batch
    # fall back to the dense path
    compact: int = 32


@dataclass(frozen=True)
class TraceConfig:
    """Device-side event flight recorder (core/trace.py) knobs.

    When enabled, every retired event appends fixed-width records to a
    ring buffer living in ``SimState.trace`` — written from both the
    cheap macro-step core and the full step, so the recorded stream is
    identical for every ``events_per_step``.  When disabled the state
    shrinks to (1,)-sized placeholders and the emission code is
    statically absent from the trace (the thermal-off trick): dynamics
    are bit-identical and the step costs nothing extra.
    """

    enabled: bool = False
    # ring capacity in records (~17 bytes/record on device).  When the
    # run emits more, the oldest records are overwritten and counted in
    # TraceState.dropped — decode/export still see the most recent
    # `capacity` events in order.
    capacity: int = 65536


@dataclass(frozen=True)
class PartitionConfig:
    """Rack-major sharding of the per-server state axes (core/shard_sim.py).

    The farm is stored rack-major: server ``i`` sits in rack
    ``i // thermal.rack_size``, so a flat (N,) server axis IS the flattened
    (R, S) rack-major layout and a contiguous block partition along it cuts
    exactly on rack boundaries.  ``n_shards`` declares how many equal rack
    groups the per-server (and per-rack) axes split into; each shard lands
    on one device of the "racks" mesh axis, making recirculation row means,
    CRAC setpoints, and per-rack COP shard-local by construction.

    ``n_shards = 1`` (default) is the unsharded engine, bit-identical to a
    mesh-free run; the sharded step gathers the rack shards once per
    macro-step (the thin collective phase), runs the event core
    collective-free, and re-slices — so any ``n_shards`` produces the
    same trajectory bit-for-bit.
    """

    n_shards: int = 1
    axis: str = "racks"            # mesh axis name the rack groups map to

    @property
    def sharded(self) -> bool:
        return self.n_shards > 1


@dataclass(frozen=True)
class SimConfig:
    """Static shape/topology/policy configuration (hashable; jit-static)."""

    n_servers: int = 50
    n_cores: int = 4
    local_q: int = 64               # per-server ring-buffer capacity
    global_q: int = 256
    max_jobs: int = 2048
    tasks_per_job: int = 1          # T (padded DAG width)
    max_children: int = 4           # Dmax fanout per task
    max_flows: int = 256            # concurrent network flows
    max_events: int = 50_000        # scan iteration budget
    ready_per_step: int = 8         # bounded ready->enqueue work per step
    arrivals_per_step: int = 8      # same-timestamp jobs admitted per step
                                    # (one shared scheduler snapshot — open
                                    # loop bursts no longer serialize)
    # event-coalesced macro-stepping: one jitted sim_step retires up to
    # this many successive event TIMES.  The first events_per_step-1 go
    # through the cheap advance/completion core (an inner bounded
    # while_loop) whenever gating shows the pending event needs no
    # expensive pass (no flow completion/spawn, no throttle crossing);
    # the final event always runs the full step.  1 = seed one-event
    # behavior.  Final states are identical for any value (the gating is
    # conservative); only the step decomposition changes.
    events_per_step: int = 8
    # hot-loop implementation: dense masked batch updates for drain /
    # arrival-assignment / flow-spawn (True) vs the seed scalar fori_loops
    # (False, kept as the semantic reference — tests compare both)
    use_vectorized_hot_loop: bool = True
    # route the interval advance (energy accrual + completion free + farm
    # next-event candidate) through the fused Pallas kernel
    # (kernels/dcsim_step.py); interpreted on CPU, compiled on TPU, like
    # the telemetry kernel (kernels.interpret_mode)
    use_kernel: bool = False
    # policies
    sched_policy: int = SchedPolicy.LOAD_BALANCE
    sleep_policy: int = SleepPolicy.ALWAYS_ON
    sleep_state: int = SrvState.S3  # which state the timer drops into
    use_global_queue: bool = False
    # provisioning thresholds (case A): load per enabled server
    prov_lo: float = 0.3
    prov_hi: float = 0.9
    # WASP thresholds (case C): pending jobs per server
    wasp_t_wakeup: float = 1.5
    wasp_t_sleep: float = 0.5
    # frequency scaling (P-state): service time scales by 1/freq
    core_freq: float = 1.0
    # network
    has_network: bool = False
    flow_mtu: float = 1500.0
    comm_model: int = 0             # 0=flow(fluid), 1=packet(store&forward)
    hop_latency: float = 5.0e-6     # per-hop switching latency (packet model)
    # power profiles
    server_power: ServerPowerProfile = field(default_factory=ServerPowerProfile)
    switch_power: SwitchPowerProfile = field(default_factory=SwitchPowerProfile)
    # device-side telemetry subsystem
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    # thermal / cooling / carbon-cost subsystem
    thermal: ThermalConfig = field(default_factory=ThermalConfig)
    # device-side event flight recorder
    trace: TraceConfig = field(default_factory=TraceConfig)
    # rack-major device sharding of the per-server state axes
    partition: PartitionConfig = field(default_factory=PartitionConfig)
    # farm padding (see farm.pad_to_racks): servers at index >= n_present
    # are inert filler rows that round the farm up to whole racks (and to
    # a shardable rack-group multiple).  0 means "all n_servers real".
    # Padded rows boot OFF/disabled: they draw zero power, never receive
    # work, and are masked out of the telemetry temperature/state columns.
    n_present: int = 0
    time_dtype: Any = jnp.float32

    @property
    def n_tasks(self) -> int:
        return self.max_jobs * self.tasks_per_job

    @property
    def present(self) -> int:
        """Number of real (schedulable) servers; <= n_servers."""
        return self.n_present if self.n_present else self.n_servers

    @property
    def has_padding(self) -> bool:
        return 0 < self.n_present < self.n_servers


# --------------------------------------------------------------------------
# dynamic state pytrees
# --------------------------------------------------------------------------

@pytree_dataclass
class ServerFarm:
    # cores
    core_busy_until: jnp.ndarray    # (N, C) time current task completes, INF idle
    # server-level power
    srv_state: jnp.ndarray          # (N,) SrvState
    srv_wake_at: jnp.ndarray        # (N,) wake completion time (INF otherwise)
    srv_idle_since: jnp.ndarray     # (N,) time the server last went fully idle
    srv_tau: jnp.ndarray            # (N,) delay-timer value (INF = never sleep)
    srv_pool: jnp.ndarray           # (N,) 0 active pool / 1 sleep pool (WASP)
    srv_enabled: jnp.ndarray        # (N,) bool: receives new work (case A)
    # task-major local queues: queue membership lives on the TASKS
    # (JobTable.status == QUEUED + JobTable.enqueue_seq for FIFO order);
    # the farm only carries the per-server occupancy counter and the
    # global enqueue sequence counter.  The seed's (N, Q) ring-buffer —
    # 5 MB of per-step state at 20K servers, plus a core->task gather and
    # slot scatters on every start — is gone.
    q_len: jnp.ndarray              # (N,) queued-task count per server
    q_seq: jnp.ndarray              # () global FIFO enqueue counter
    # stats
    energy: jnp.ndarray             # (N,) joules
    residency: jnp.ndarray          # (N, SrvState.NUM) seconds per state
    busy_core_seconds: jnp.ndarray  # (N,)
    wake_count: jnp.ndarray         # (N,) number of sleep->active transitions
    dropped: jnp.ndarray            # () tasks dropped on full queues


@pytree_dataclass
class JobTable:
    arrival: jnp.ndarray            # (J,) job arrival times (INF padded)
    arr_ptr: jnp.ndarray            # () next arrival index
    service: jnp.ndarray            # (J*T,) task service time @ freq 1.0
    valid: jnp.ndarray              # (J*T,) bool
    dep_count: jnp.ndarray          # (J*T,) unfinished parents
    children: jnp.ndarray           # (J*T, Dmax) flat child ids (-1 pad)
    edge_bytes: jnp.ndarray         # (J*T, Dmax) result size to child
    status: jnp.ndarray             # (J*T,) TaskStatus
    edge_sent: jnp.ndarray          # (J*T, Dmax) network edge already handled
    server: jnp.ndarray             # (J*T,) assigned server (-1 unassigned)
    enqueue_seq: jnp.ndarray        # (J*T,) global FIFO stamp set when the
                                    # task enters its server's queue (each
                                    # task enqueues at most once, so stamps
                                    # are unique and bounded by J*T)
    task_end: jnp.ndarray           # (J*T,) busy_until stamped at start (INF
                                    # otherwise) — lets completions resolve
                                    # elementwise in task space, no scatter
    start_at: jnp.ndarray           # (J*T,) time the task began running (INF
                                    # until started) — the lifecycle stamp
                                    # between enqueue and finish, used by
                                    # traceio span/critical-path decoding
    finish: jnp.ndarray             # (J*T,) task finish time
    job_finish: jnp.ndarray         # (J,) completion time (INF if not done)
    tasks_done: jnp.ndarray         # (J,) per-job finished-task count
    sla: jnp.ndarray                # (J,) latency deadline (INF = no SLA)
    deferrable: jnp.ndarray         # (J,) bool — may be carbon-deferred
    deadline: jnp.ndarray           # (J,) absolute latest ADMIT time for a
                                    # deferred job (INF = no deadline)
    admit_at: jnp.ndarray           # (J,) release time of a currently
                                    # deferred job (INF = not deferred); the
                                    # min is a next_event_time candidate


@pytree_dataclass
class FlowTable:
    src: jnp.ndarray                # (F,) source server
    dst: jnp.ndarray                # (F,) destination server
    rem: jnp.ndarray                # (F,) remaining bytes
    rate: jnp.ndarray               # (F,) current share (bytes/s)
    extra: jnp.ndarray              # (F,) fixed latency budget left (seconds)
    done_at: jnp.ndarray            # (F,) projected completion (INF inactive)
    child: jnp.ndarray              # (F,) task whose dep_count decrements
    active: jnp.ndarray             # (F,) bool
    flows_dropped: jnp.ndarray      # () spawns refused by a full table (the
                                    # edge drop-resolves: dep decremented
                                    # immediately instead of deadlocking)


@pytree_dataclass
class NetState:
    port_state: jnp.ndarray         # (W, P) PortState
    port_idle_since: jnp.ndarray    # (W, P)
    lc_state: jnp.ndarray           # (W, LC) LinecardState
    sw_awake: jnp.ndarray           # (W,) bool (case D switch sleeping)
    link_flows: jnp.ndarray         # (L,) active flow count per link
    sw_energy: jnp.ndarray          # (W,) joules
    port_residency: jnp.ndarray     # (W, P, PortState.NUM)


@pytree_dataclass
class SchedState:
    rr_ptr: jnp.ndarray             # () round-robin pointer
    n_enabled: jnp.ndarray          # () provisioning active-set size
    gq_tasks: jnp.ndarray           # (GQ,) global queue ring
    gq_head: jnp.ndarray            # ()
    gq_len: jnp.ndarray             # ()


@pytree_dataclass
class Telemetry:
    """Device-side streaming telemetry accumulated inside the event loop.

    ``win`` packs all windowed time series as time-weighted column sums
    (metric·dt scattered into the window containing the interval midpoint);
    dividing by the occupancy column recovers time-averaged values.  Column
    layout is ``core/telemetry.py`` (WIN_* constants).
    """

    job_hist: jnp.ndarray           # (B,) job-latency histogram (weights)
    task_hist: jnp.ndarray          # (B,) task-latency histogram
    win: jnp.ndarray                # (W, K) windowed time-weighted series
    sla_miss: jnp.ndarray           # () jobs finishing past their sla
    sla_total: jnp.ndarray          # () finished jobs with a finite sla
    tail_viol: jnp.ndarray          # () jobs with latency > tail_thresh
    win_overflow: jnp.ndarray       # () seconds of simulated time falling
                                    # past the n_windows·window_dt horizon
                                    # (clamped into the last window, whose
                                    # time-averages are then contaminated)


@pytree_dataclass
class ThermalState:
    """Thermal/carbon/cost state (core/thermal.py).  Sized (1,) minimal
    arrays when the subsystem is disabled, like Telemetry."""

    t_srv: jnp.ndarray              # (N,) server temperature (°C)
    throttled: jnp.ndarray          # (N,) bool — hysteresis latch
    rack_id: jnp.ndarray            # (N,) server -> rack map (constant)
    rack_onehot: jnp.ndarray        # (R, N) f32 membership (constant)
    rack_inv: jnp.ndarray           # (R,) 1/servers-per-rack (constant)
    t_set: jnp.ndarray              # (R,) per-rack CRAC supply setpoint
                                    # (°C; state — the setpoint controller
                                    # moves it on a control period)
    ctrl_next: jnp.ndarray          # () next setpoint-controller tick (a
                                    # next_event_time candidate; INF = off)
    t_peak: jnp.ndarray             # (N,) running max temperature
    throttle_seconds: jnp.ndarray   # (N,) time spent throttled
    cool_energy: jnp.ndarray        # () CRAC joules
    carbon_g: jnp.ndarray           # () grams CO2 (IT + cooling)
    cost: jnp.ndarray               # () electricity cost ($)
    defer_seconds: jnp.ndarray      # () summed job deferral time (carbon-
                                    # aware control plane)
    defer_count: jnp.ndarray        # () jobs released after a deferral
    grams_avoided: jnp.ndarray      # () first-order estimate of CO2 grams
                                    # avoided by deferral: Δintensity at
                                    # (defer, release) × marginal job energy


@pytree_dataclass
class TraceState:
    """Device-side flight-recorder ring buffer (core/trace.py).  Sized
    (1, 5) placeholder when tracing is disabled, like Telemetry.

    Records are packed into ONE (cap, 5) float buffer — columns
    [kind, time, server, tid, aux] — so the per-step flush is a single
    row scatter (XLA CPU scatter costs ~60ns per update ROW, so op
    count, not buffer size, is what the hot loop pays for).  The dtype
    is ``cfg.time_dtype`` promoted to at least f32: integer columns
    round-trip exactly below 2^24 (f32) / 2^53 (f64), far above any
    realistic id space."""

    buf: jnp.ndarray                # (cap, 5) [kind, time, server(-1 =
                                    # farm-level), tid(-1 = n/a), aux]
    ptr: jnp.ndarray                # () monotonic write pointer (total
                                    # events ever emitted; slot = ptr % cap)
    dropped: jnp.ndarray            # () records overwritten by wrap-around


@pytree_dataclass
class SimState:
    t: jnp.ndarray                  # () current simulation time
    farm: ServerFarm
    jobs: JobTable
    flows: FlowTable
    net: NetState
    sched: SchedState
    telem: Telemetry
    thermal: ThermalState
    trace: TraceState
    events: jnp.ndarray             # () processed event count
    steps: jnp.ndarray              # () jitted sim_step invocations
    done: jnp.ndarray               # () bool — all jobs finished


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def init_farm(cfg: SimConfig) -> ServerFarm:
    N, C = cfg.n_servers, cfg.n_cores
    tdt = cfg.time_dtype
    # padded filler rows (index >= cfg.present) boot OFF and disabled: the
    # OFF power row is the literal 0.0, every next-event candidate is INF,
    # and no scheduling policy can pick a disabled server — so the rows
    # are power/event/scheduler inert without any per-step masking
    real = jnp.arange(N) < cfg.present
    return ServerFarm(
        core_busy_until=jnp.full((N, C), INF, tdt),
        srv_state=jnp.where(real, SrvState.IDLE,
                            SrvState.OFF).astype(jnp.int32),
        srv_wake_at=jnp.full((N,), INF, tdt),
        srv_idle_since=jnp.zeros((N,), tdt),
        srv_tau=jnp.full((N,), INF, tdt),
        srv_pool=jnp.zeros((N,), jnp.int32),
        srv_enabled=real,
        q_len=jnp.zeros((N,), jnp.int32),
        q_seq=jnp.zeros((), jnp.int32),
        energy=jnp.zeros((N,), jnp.float32),
        residency=jnp.zeros((N, SrvState.NUM), jnp.float32),
        busy_core_seconds=jnp.zeros((N,), jnp.float32),
        wake_count=jnp.zeros((N,), jnp.int32),
        dropped=jnp.zeros((), jnp.int32),
    )


def init_flows(cfg: SimConfig) -> FlowTable:
    F = cfg.max_flows
    tdt = cfg.time_dtype
    return FlowTable(
        src=jnp.full((F,), -1, jnp.int32),
        dst=jnp.full((F,), -1, jnp.int32),
        rem=jnp.zeros((F,), jnp.float32),
        rate=jnp.zeros((F,), jnp.float32),
        extra=jnp.zeros((F,), tdt),
        done_at=jnp.full((F,), INF, tdt),
        child=jnp.full((F,), -1, jnp.int32),
        active=jnp.zeros((F,), bool),
        flows_dropped=jnp.zeros((), jnp.int32),
    )


def init_net(n_switches: int, n_ports: int, n_links: int,
             n_linecards: int, cfg: SimConfig) -> NetState:
    W, P, L = max(n_switches, 1), max(n_ports, 1), max(n_links, 1)
    LC = max(n_linecards, 1)
    tdt = cfg.time_dtype
    return NetState(
        port_state=jnp.full((W, P), PortState.LPI, jnp.int32),
        port_idle_since=jnp.zeros((W, P), tdt),
        lc_state=jnp.full((W, LC), LinecardState.ACTIVE, jnp.int32),
        sw_awake=jnp.ones((W,), bool),
        link_flows=jnp.zeros((L,), jnp.int32),
        sw_energy=jnp.zeros((W,), jnp.float32),
        port_residency=jnp.zeros((W, P, PortState.NUM), jnp.float32),
    )


def init_sched(cfg: SimConfig) -> SchedState:
    return SchedState(
        rr_ptr=jnp.zeros((), jnp.int32),
        n_enabled=jnp.asarray(cfg.present, jnp.int32),
        gq_tasks=jnp.full((cfg.global_q,), -1, jnp.int32),
        gq_head=jnp.zeros((), jnp.int32),
        gq_len=jnp.zeros((), jnp.int32),
    )

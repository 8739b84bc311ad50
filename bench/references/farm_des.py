"""Plain sequential reference for a farm of single-task jobs.

A straightforward discrete-event simulation of the semantics that the
configurations in ``bench/configs`` state, written without any code of
the program under test: heaps of pending completions, wake-ups and sleep
timers, one Python object per server, lazily integrated energy.

What it models, in the order applied at each event time ``t``:

1. wake-ups due at ``t`` (WAKING -> IDLE, idle timer restarts at ``t``);
2. completions due at ``t`` (the core frees, the job finishes at ``t``);
3. up to ``arrivals_per_step`` arrivals due at ``t``: each job goes to the
   server with the least occupancy (running + queued), ties to the lowest
   index, servers with a full local queue excluded; jobs of one batch see
   one snapshot plus the jobs placed before them in the batch;
4. up to ``ready_per_step`` placed jobs enter their server's FIFO queue
   (a full queue drops the job, which finishes at ``t``); a sleeping
   destination starts to wake and counts one wake;
5. awake servers start queued jobs on free cores (end = t + service);
6. awake servers become ACTIVE or IDLE; ACTIVE -> IDLE restarts the idle
   timer at ``t``;
7. IDLE servers whose timer ``idle_since + tau`` has come go to sleep.

Work left over at ``t`` (more than a batch of arrivals or placed jobs)
is applied at another event of the same time.  Every event time counts
one event.  Times are held in ``time_dtype`` (the configuration's stated
precision), so each time sum is rounded as the configuration says;
energy, residency and the telemetry windows integrate in float64.
"""
from __future__ import annotations

import collections
import heapq
import math

import numpy as np

INF = 1.0e30
BIG = 1.0e9

# server power states
ACTIVE, IDLE, PKG_C6, S3, OFF, WAKING = range(6)
N_STATES = 6
STATE_NAMES = {"PKG_C6": PKG_C6, "S3": S3, "OFF": OFF}

# telemetry window columns kept by the comparison (see bench/compare.py)
W_OCC, W_ACTIVE_JOBS, W_AWAKE, W_QDEPTH, W_SRV_POWER = 0, 1, 2, 3, 4
W_STATE0 = 6
W_COLS = W_STATE0 + N_STATES


class Server:
    __slots__ = ("state", "busy", "queue", "wake_at", "idle_since",
                 "wake_count", "energy", "residency", "last")

    def __init__(self):
        self.state = IDLE
        self.busy = 0
        self.queue = collections.deque()
        self.wake_at = INF
        self.idle_since = 0.0
        self.wake_count = 0
        self.energy = 0.0
        self.residency = [0.0] * N_STATES
        self.last = 0.0


class FarmDES:
    """``sim`` is the configuration's ``sim`` block; ``arrivals`` and
    ``service`` (seconds, float64) and ``tau`` (per server) come from the
    traffic generator."""

    def __init__(self, sim: dict, arrivals, service, tau, time_dtype=None):
        if sim.get("tasks_per_job", 1) != 1:
            raise ValueError("farm_des models single-task jobs only")
        if sim.get("sched_policy") != "LOAD_BALANCE":
            raise ValueError("farm_des models LOAD_BALANCE placement only")
        if sim.get("sleep_policy") not in ("SINGLE_TIMER", "ALWAYS_ON"):
            raise ValueError("farm_des models single delay timers only")
        if sim.get("has_network") or sim.get("thermal", {}).get("enabled"):
            raise ValueError("farm_des models no network or thermal state")
        self.dt_type = np.dtype(time_dtype or sim["time_dtype"]).type
        self.N = int(sim["n_servers"])
        self.C = int(sim["n_cores"])
        self.Q = int(sim["local_q"])
        self.K_arr = int(sim["arrivals_per_step"])
        self.K_ready = int(sim["ready_per_step"])
        timers = sim["sleep_policy"] == "SINGLE_TIMER"
        self.sleep_state = STATE_NAMES[sim["sleep_state"]]
        sp = sim["server_power"]
        self.p_act, self.p_idle = sp["p_core_active"], sp["p_core_idle"]
        self.p_base = sp["p_base"]
        self.p_fixed = {PKG_C6: sp["p_pkg_c6"], S3: sp["p_s3"], OFF: 0.0,
                        WAKING: sp["p_wake"]}
        self.wake_lat = {PKG_C6: self._t(sp["t_wake_pkg_c6"]),
                         S3: self._t(sp["t_wake_s3"]),
                         OFF: self._t(sp["t_wake_off"])}
        tel = sim["telemetry"]
        self.n_bins = int(tel["n_bins"])
        self.lat_lo, self.lat_hi = tel["lat_lo"], tel["lat_hi"]
        self.n_windows = int(tel["n_windows"])
        self.window_dt = np.float32(tel["window_dt"])
        self.tail_thresh = tel["tail_thresh"]
        self.bin_scale = np.float32(self.n_bins
                                    / math.log(self.lat_hi / self.lat_lo))

        self.arrival = [self._t(a) for a in np.asarray(arrivals, float)]
        self.service = [self._t(s) for s in np.asarray(service, float)]
        self.J = len(self.arrival)
        tau = np.broadcast_to(np.asarray(tau, float), (self.N,))
        self.tau = [self._t(x) if timers and x < INF / 2 else INF
                    for x in tau]

        self.t = 0.0
        self.events = 0
        self.arr_ptr = 0
        self.srv = [Server() for _ in range(self.N)]
        # occupancy score for placement: running + queued, BIG*2 if full
        self.score = np.zeros(self.N)
        self.job_server = np.full(self.J, -1, np.int64)
        self.job_finish = np.full(self.J, INF)
        self.n_done = 0
        self.dropped = 0
        self.ready = collections.deque()
        self.comp = []                       # (end, job, server)
        self.wakes = []                      # (wake_at, server)
        self.timers = [(self.tau[i], i, 0.0) for i in range(self.N)
                       if self.tau[i] < INF / 2]
        heapq.heapify(self.timers)
        # farm-wide aggregates for the telemetry windows
        self.n_state = [0] * N_STATES
        self.n_state[IDLE] = self.N
        self.power = self.N * self._p_on(0)
        self.active_jobs = 0
        self.qdepth = 0
        self.job_hist = np.zeros(self.n_bins)
        self.task_hist = np.zeros(self.n_bins)
        self.tail_viol = 0
        self.win = np.zeros((self.n_windows, W_COLS))

    # ---- arithmetic in the stated time precision -------------------------
    def _t(self, x):
        return float(self.dt_type(x))

    def _add(self, a, b):
        return float(self.dt_type(a) + self.dt_type(b))

    def _p_on(self, busy):
        return self.p_base + busy * self.p_act + (self.C - busy) * self.p_idle

    def _power(self, s):
        if s.state in (ACTIVE, IDLE):
            return self._p_on(s.busy)
        return self.p_fixed[s.state]

    # ---- per-server lazy integration -------------------------------------
    def _accrue(self, s):
        dt = self.t - s.last
        if dt > 0.0:
            s.energy += self._power(s) * dt
            s.residency[s.state] += dt
        s.last = self.t

    def _set(self, i, state=None, busy=None):
        """Change server ``i``'s state and/or busy cores at time t."""
        s = self.srv[i]
        self._accrue(s)
        p0 = self._power(s)
        if state is not None and state != s.state:
            self.n_state[s.state] -= 1
            self.n_state[state] += 1
            s.state = state
        if busy is not None:
            s.busy = busy
        self.power += self._power(s) - p0

    def _rescore(self, i):
        s = self.srv[i]
        self.score[i] = 2 * BIG if len(s.queue) >= self.Q \
            else s.busy + len(s.queue)

    # ---- telemetry --------------------------------------------------------
    def _bin(self, lat):
        v = np.float32(max(lat, self.lat_lo)) / np.float32(self.lat_lo)
        raw = np.log(v) * self.bin_scale
        return min(max(int(raw), 0), self.n_bins - 1)

    def _finish(self, j):
        self.job_finish[j] = self.t
        self.n_done += 1
        lat = max(float(self.dt_type(self.t) - self.dt_type(self.arrival[j])),
                  0.0)
        b = self._bin(lat)
        self.job_hist[b] += 1
        self.task_hist[b] += 1
        if lat > self.tail_thresh:
            self.tail_viol += 1

    def _advance(self, t_next):
        dt = t_next - self.t
        if dt > 0.0:
            mid = np.float32(self.t) + np.float32(0.5) * np.float32(dt)
            w = min(max(int(mid / self.window_dt), 0), self.n_windows - 1)
            row = self.win[w]
            row[W_OCC] += dt
            row[W_ACTIVE_JOBS] += self.active_jobs * dt
            row[W_AWAKE] += (self.n_state[ACTIVE] + self.n_state[IDLE]) * dt
            row[W_QDEPTH] += self.qdepth * dt
            row[W_SRV_POWER] += self.power * dt
            for k in range(N_STATES):
                row[W_STATE0 + k] += self.n_state[k] * dt
        self.t = t_next

    # ---- the event loop ---------------------------------------------------
    def _next_time(self):
        if self.ready or (self.arr_ptr < self.J
                          and self.arrival[self.arr_ptr] <= self.t):
            return self.t
        cands = [self.arrival[self.arr_ptr] if self.arr_ptr < self.J
                 else INF]
        if self.comp:
            cands.append(self.comp[0][0])
        while self.wakes and (self.srv[self.wakes[0][1]].state != WAKING
                              or self.srv[self.wakes[0][1]].wake_at
                              != self.wakes[0][0]):
            heapq.heappop(self.wakes)
        if self.wakes:
            cands.append(self.wakes[0][0])
        while self.timers and not self._timer_live(self.timers[0]):
            heapq.heappop(self.timers)
        if self.timers:
            cands.append(self.timers[0][0])
        return max(min(cands), self.t)

    def _timer_live(self, entry):
        _, i, stamp = entry
        s = self.srv[i]
        return s.state == IDLE and s.idle_since == stamp

    def step(self):
        """Apply one event time; returns False when nothing is pending."""
        t_next = self._next_time()
        if t_next >= INF / 2:
            return False
        self._advance(t_next)
        self.events += 1
        t = self.t
        touched = set()

        while self.wakes and self.wakes[0][0] <= t:
            wt, i = heapq.heappop(self.wakes)
            s = self.srv[i]
            if s.state == WAKING and s.wake_at == wt:
                self._set(i, state=IDLE)
                s.wake_at = INF
                s.idle_since = t
                touched.add(i)

        while self.comp and self.comp[0][0] <= t:
            _, j, i = heapq.heappop(self.comp)
            self._set(i, busy=self.srv[i].busy - 1)
            self._rescore(i)
            self.active_jobs -= 1
            self._finish(j)
            touched.add(i)

        n = 0
        extra = {}
        while (n < self.K_arr and self.arr_ptr < self.J
               and self.arrival[self.arr_ptr] <= t):
            j = self.arr_ptr
            if extra:
                sc = self.score.copy()
                for i, x in extra.items():
                    if sc[i] < BIG:             # a full queue stays out
                        sc[i] += x
                i = int(np.argmin(sc))
            else:
                i = int(np.argmin(self.score))
            extra[i] = extra.get(i, 0) + 1
            self.job_server[j] = i
            self.ready.append(j)
            self.active_jobs += 1
            self.arr_ptr += 1
            n += 1

        dest = set()
        for _ in range(min(self.K_ready, len(self.ready))):
            j = self.ready.popleft()
            i = int(self.job_server[j])
            s = self.srv[i]
            dest.add(i)
            if len(s.queue) < self.Q:
                s.queue.append(j)
                self.qdepth += 1
                self._rescore(i)
            else:
                self.dropped += 1
                self.active_jobs -= 1
                self._finish(j)
        for i in dest:
            s = self.srv[i]
            if s.state in (PKG_C6, S3, OFF):
                s.wake_at = self._add(t, self.wake_lat[s.state])
                s.wake_count += 1
                self._set(i, state=WAKING)
                heapq.heappush(self.wakes, (s.wake_at, i))
        touched |= dest

        for i in sorted(touched):
            s = self.srv[i]
            if s.state in (ACTIVE, IDLE):
                k = min(self.C - s.busy, len(s.queue))
                if k > 0:
                    for _ in range(k):
                        j = s.queue.popleft()
                        end = self._add(t, self.service[j])
                        heapq.heappush(self.comp, (end, j, i))
                    self.qdepth -= k
                    self._set(i, busy=s.busy + k)
                    self._rescore(i)
                new = ACTIVE if s.busy else IDLE
                if new != s.state:
                    if new == IDLE:
                        s.idle_since = t
                    self._set(i, state=new)
                if s.state == IDLE and self.tau[i] < INF / 2:
                    heapq.heappush(self.timers, (
                        self._add(s.idle_since, self.tau[i]), i,
                        s.idle_since))

        while self.timers and self.timers[0][0] <= t:
            entry = heapq.heappop(self.timers)
            if self._timer_live(entry):
                self._set(entry[1], state=self.sleep_state)
        return True

    def run(self, max_events=None):
        """Run to ``max_events`` events, or until every job has finished
        (the event that finishes the last job is the last one counted)."""
        while max_events is None or self.events < max_events:
            if not self.step():
                break
            if max_events is None and self.n_done == self.J:
                break
        return self

    def result(self) -> dict:
        """The state compared with the program's (bench/compare.py)."""
        for s in self.srv:
            self._accrue(s)
        return {
            "t": self.t,
            "events": self.events,
            "arr_ptr": self.arr_ptr,
            "server": self.job_server,
            "job_finish": self.job_finish,
            "energy": np.asarray([s.energy for s in self.srv]),
            "residency": np.asarray([s.residency for s in self.srv]),
            "wake_count": np.asarray([s.wake_count for s in self.srv]),
            "job_hist": self.job_hist,
            "task_hist": self.task_hist,
            "tail_viol": self.tail_viol,
            "dropped": self.dropped,
            "win": self.win,
        }


def simulate(sim: dict, arrivals, service, tau, max_events=None,
             time_dtype=None) -> dict:
    """The reference's result after ``max_events`` events, or once every
    job has finished."""
    des = FarmDES(sim, arrivals, service, tau, time_dtype)
    return des.run(max_events).result()

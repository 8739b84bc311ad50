"""Farm cells: the program's ``engine.run`` (the module-level jit, as it
is) advancing one large farm by a fixed slice of events.

Set-up builds the state as ``farm.simulate`` does (``build_jobs``,
``init_state``, ``srv_tau``), compiles the two programs it will run,
advances the state by ``warm_events`` to a warm state S_w, and makes one
warm call.  Every timed call then runs from the same S_w to
``warm_events + call_events``, so each retires exactly ``call_events``
events of the same stretch of simulated time: a faster program cannot run
the window out of work or move it to another part of the run.  Both
counts are multiples of the macro-step, so the loop stops on them
exactly."""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from bench import compare, generator, loader, program
from repro.core import engine
from repro.core.jobs import build_jobs, dag_single


class Runner:
    def __init__(self, cell, seed: int):
        conf, traffic = cell.config, cell.traffic
        sim = conf["sim"]
        self.W, self.E = int(traffic["warm_events"]), int(traffic["call_events"])
        K = int(sim["events_per_step"])
        if self.W % K or self.E % K or self.E <= 0:
            raise ValueError(f"warm_events and call_events must be positive "
                             f"multiples of events_per_step={K}")
        self.cell = cell
        self.inputs = _inputs(cell, seed)
        self.arr, self.svc, self.tau = self.inputs
        self.J = len(self.arr)
        cfg = program.sim_config(sim, max_jobs=sim.get("max_jobs", self.J))

        t0 = time.perf_counter()
        jobs = build_jobs(cfg, self.arr, [dag_single(s) for s in self.svc])
        state, tc = engine.init_state(cfg, jobs)
        state = dataclasses.replace(state, farm=dataclasses.replace(
            state.farm, srv_tau=jnp.asarray(self.tau, cfg.time_dtype)))
        state = jax.block_until_ready(state)
        self.build_s = time.perf_counter() - t0

        cfg_w = dataclasses.replace(cfg, max_events=self.W)
        self.cfg = dataclasses.replace(cfg, max_events=self.W + self.E)
        t0 = time.perf_counter()
        engine.run.lower(state, cfg_w, tc).compile()
        self.compiled = engine.run.lower(state, self.cfg, tc).compile()
        self.compile_s = time.perf_counter() - t0

        s_w = jax.block_until_ready(engine.run(state, cfg_w, tc))
        del state
        if int(s_w.events) != self.W or bool(s_w.done):
            raise RuntimeError(f"warm-up stopped at {int(s_w.events)} "
                               f"events (done={bool(s_w.done)}), not "
                               f"{self.W}")
        self.s_w, self.tc = s_w, tc
        self.base = (int(s_w.events), int(s_w.steps))
        self.counts(jax.block_until_ready(self.call()))

    def call(self):
        return engine.run(self.s_w, self.cfg, self.tc)

    def counts(self, out) -> dict:
        return {"events": int(out.events) - self.base[0],
                "steps": int(out.steps) - self.base[1],
                "done": bool(out.done)}

    def failed(self, counts: dict) -> bool:
        """A call that finished the run or retired another event count."""
        return counts["done"] or counts["events"] != self.E

    def outputs(self, out):
        return program.outputs(out, self.J)

    def program_text(self) -> str:
        """The compiled HLO text of the timed calls' program, from
        set-up's compile."""
        return self.compiled.as_text()

    def check(self, outputs) -> dict:
        return compare.gaps(outputs, _reference(self.cell, self.inputs))


def _inputs(cell, seed: int):
    """(arrivals, service, tau) of the cell's traffic for ``seed``."""
    conf = cell.config
    return generator.farm(cell.traffic, conf["sim"], conf["tau_s"], seed)


def _reference(cell, inputs, time_dtype=None) -> dict:
    ref = loader.module("references", cell.config["reference"])
    t = cell.traffic
    return ref.simulate(cell.config["sim"], *inputs,
                        max_events=t["warm_events"] + t["call_events"],
                        time_dtype=time_dtype)


def setup(cell, seed: int) -> Runner:
    return Runner(cell, seed)


def control(cell, seed: int, time_dtype=None, alter=None) -> dict:
    """The reference computed with times in ``time_dtype`` (the
    configuration's own when None) and its result altered by ``alter``,
    put in the program's place and compared as the program is."""
    inputs = _inputs(cell, seed)
    ref = _reference(cell, inputs)
    stand_in = ref if time_dtype is None else _reference(cell, inputs,
                                                         time_dtype)
    return compare.gaps((alter or dict)(stand_in), ref)

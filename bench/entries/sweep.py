"""Sweep cells: the program's ``montecarlo.run_replicas`` called as its
users call it, each call the whole replica sweep run to completion from
the batch that ``montecarlo.batched_state`` built during set-up.  The
slowest replica holds the batch, as in a real sweep, and each call pays
whatever ``run_replicas`` does per call (it builds and traces a new jit).
The first call, in set-up, compiles."""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import compare, generator, loader, program
from repro.core import montecarlo
from repro.core.jobs import dag_single


class Runner:
    def __init__(self, cell, seed: int):
        self.cell = cell
        self.arrs, self.svc, self.taus = _inputs(cell, seed)
        self.R, self.J = self.arrs.shape
        self.cfg = program.sim_config(cell.config["sim"])

        t0 = time.perf_counter()
        self.state_b, self.tc = montecarlo.batched_state(
            self.cfg, self.arrs, [dag_single(s) for s in self.svc],
            taus=self.taus)
        jax.block_until_ready(self.state_b)
        self.build_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        out = jax.block_until_ready(self.call())
        self.first_call_s = time.perf_counter() - t0
        self.compile_s = None            # first call minus a warm call
        self.expected = self.counts(out)["replica_events"]

    def call(self):
        return montecarlo.run_replicas(self.cfg, self.state_b, self.tc)

    def counts(self, out) -> dict:
        ev = np.asarray(out.events)
        st = np.asarray(out.steps)
        return {"events": int(ev.sum()), "steps": int(st.max()),
                "replica_steps": st.tolist(), "replica_events": ev.tolist(),
                "done": bool(np.asarray(out.done).all())}

    def failed(self, counts: dict) -> bool:
        """A call with a replica unfinished or retiring another count."""
        return (not counts["done"]
                or counts["replica_events"] != self.expected)

    def program_text(self) -> str:
        """The compiled HLO text of the program each call builds (read
        back from the compile cache)."""
        return montecarlo.replica_jit(self.cfg, self.tc).lower(
            self.state_b).compile().as_text()

    def outputs(self, out):
        out = jax.device_get(out)
        return [program.outputs(out, self.J, replica=r)
                for r in range(self.R)]

    def check(self, outputs) -> dict:
        """Every replica against the reference; each number's worst."""
        inputs = (self.arrs, self.svc, self.taus)
        return compare.worst([
            compare.gaps(prog, _reference(self.cell, inputs, r))
            for r, prog in enumerate(outputs)])


def _inputs(cell, seed: int):
    """(arrivals (R, J), service (J,), tau (R,)) for ``seed``."""
    conf = cell.config
    mix = dict(cell.traffic, jobs=conf["jobs_per_replica"],
               seeds_per_point=conf["seeds_per_point"])
    arrs, svc, taus, _ = generator.sweep(mix, conf["sim"], seed)
    return arrs, svc, taus


def _reference(cell, inputs, r: int, time_dtype=None) -> dict:
    ref = loader.module("references", cell.config["reference"])
    arrs, svc, taus = inputs
    return ref.simulate(cell.config["sim"], arrs[r], svc, taus[r],
                        time_dtype=time_dtype)


def setup(cell, seed: int) -> Runner:
    return Runner(cell, seed)


def control(cell, seed: int, time_dtype=None, alter=None) -> dict:
    """The reference computed with times in ``time_dtype`` (the
    configuration's own when None) and its result altered by ``alter``,
    put in the program's place on every replica."""
    inputs = _inputs(cell, seed)
    readings = []
    for r in range(len(inputs[0])):
        ref = _reference(cell, inputs, r)
        stand_in = ref if time_dtype is None else _reference(
            cell, inputs, r, time_dtype)
        readings.append(compare.gaps((alter or dict)(stand_in), ref))
    return compare.worst(readings)

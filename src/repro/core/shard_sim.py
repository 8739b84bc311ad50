"""Rack-sharded execution of the event engine: one engine, N devices.

``SimState``'s per-server axes are stored rack-major (server ``i`` lives
in rack ``i // rack_size``), so a contiguous block partition along the
server axis cuts exactly on rack boundaries.  :func:`run_sharded` keeps
those axes sharded across the ``"racks"`` mesh axis *at rest* — each
device holds N/K servers' worth of farm + thermal state — and runs the
whole ``lax.while_loop`` under ``shard_map``.

The macro-step splits into two phases:

  * **thin collective phase** — at the top of each macro-step the rack
    shards are gathered (one tiled ``all_gather`` per sharded leaf, the
    ONLY collectives in the program);
  * **collective-free event core** — the unmodified ``engine.sim_step``
    (including its cheap-event chew loop) runs on the gathered arrays,
    retiring up to ``events_per_step`` events with zero collectives, and
    the updated rack block is sliced back out at the bottom.

Because the gathered arrays and the step computation are *identical* to
the unsharded engine's, the sharded trajectory — every state leaf,
including the trace ring — is **bit-identical** to ``engine.run`` on one
device, for any device count.  A mesh of 1 is literally today's engine
plus a no-op reshard.  (``tests/test_sharding.py`` pins this.)

Replicated-by-construction state (jobs/flows/net/sched/telemetry/trace
and every scalar) is updated identically on all devices: the gathered
inputs are identical, the program is deterministic, and the while-loop
predicate is a replicated scalar, so the devices stay in lockstep and
``check_vma=False`` out-specs can take any copy.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from ..sharding import partition as mesh_lib
from . import engine
from .types import SimConfig

__all__ = ["make_mesh", "n_sharded_leaves", "run_sharded",
           "sharded_step_jaxpr", "validate_sharding"]


def make_mesh(n_shards: int, axis: str = mesh_lib.SIM_AXIS):
    """A 1-D device mesh for rack sharding (first n_shards devices)."""
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(
            f"partition.n_shards={n_shards} but only {len(devs)} device(s) "
            f"are visible; on CPU, launch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards}")
    return jax.make_mesh((n_shards,), (axis,),
                         devices=np.asarray(devs[:n_shards]))


def validate_sharding(cfg: SimConfig, n_shards: int, state=None) -> None:
    """Fail fast on layouts shard_map cannot cut on rack boundaries."""
    if cfg.n_servers % n_shards:
        raise ValueError(
            f"n_servers={cfg.n_servers} is not divisible by "
            f"n_shards={n_shards}; pad the farm first (farm.pad_to_racks)")
    if cfg.thermal.enabled:
        if state is not None and state.thermal.rack_onehot.size:
            raise ValueError(
                "sharded runs need a contiguous equal-size rack grouping "
                "(the i // rack_size default or a block topology); this "
                "state uses the general one-hot grouping")
        if state is not None:
            R = int(state.thermal.t_set.shape[0])
            if R % n_shards:
                raise ValueError(
                    f"{R} racks do not split over {n_shards} shards; pad "
                    f"the farm to a rack multiple of n_shards "
                    f"(farm.pad_to_racks)")


def _gather_leaves(leaves, specs, axis):
    """all_gather every rack-sharded leaf back to its full (N, ...) shape
    — the macro-step's entire collective phase."""
    return [jax.lax.all_gather(x, axis, axis=0, tiled=True)
            if (len(sp) and sp[0] == axis) else x
            for x, sp in zip(leaves, specs)]


def _slice_leaves(leaves, specs, axis, n_shards):
    """Take this device's rack block back out of the full arrays (a local
    dynamic_slice — no communication)."""
    out = []
    idx = None
    for x, sp in zip(leaves, specs):
        if len(sp) and sp[0] == axis:
            if idx is None:
                idx = jax.lax.axis_index(axis)
            blk = x.shape[0] // n_shards
            out.append(jax.lax.dynamic_slice_in_dim(x, idx * blk, blk, 0))
        else:
            out.append(x)
    return out


def _sharded_step_fn(cfg: SimConfig, tc, specs, treedef, axis, n_shards):
    """One macro-step over locally-sharded leaves: gather -> sim_step ->
    re-slice.  Shared by run_sharded's loop body and the jaxpr probe."""
    def step(*local_leaves):
        full = _gather_leaves(list(local_leaves), specs, axis)
        state = jax.tree.unflatten(treedef, full)
        state = engine.sim_step(state, cfg, tc)
        out = jax.tree.leaves(state)
        return tuple(_slice_leaves(out, specs, axis, n_shards))
    return step


@functools.lru_cache(maxsize=32)
def _runner_for(cfg: SimConfig, mesh, axis, treedef, specs, n_state):
    """The jitted shard-mapped run-to-completion loop for one
    (cfg, mesh, pytree layout).  Cached so repeat calls (bench warm runs,
    replica sweeps, simulate(profile=True)) reuse the compiled
    executable instead of retracing a fresh closure each time.

    ``treedef`` flattens the ``(state, tc)`` pair; the trailing
    ``len - n_state`` leaves are the loop-invariant topology constants,
    passed through shard_map replicated."""
    n_shards = int(mesh.shape[axis])
    state_specs = specs[:n_state]
    cond = engine.loop_cond(cfg)

    def loop(*all_leaves):
        # trace-time side effect: lru_cache hits skip this entirely, so a
        # second firing for the same key means the compile cache leaked
        # (pinned by the analysis/retrace sentinel)
        engine._note_trace(
            "shard_sim.loop",
            (cfg, str(mesh), axis, str(treedef), str(specs), n_state))
        tc_leaves = list(all_leaves[n_state:])

        def body(lv):
            full = _gather_leaves(list(lv), state_specs, axis)
            state, tc = jax.tree.unflatten(treedef, full + tc_leaves)
            state = engine.sim_step(state, cfg, tc)
            out = jax.tree.leaves(state)
            return tuple(_slice_leaves(out, state_specs, axis, n_shards))

        def cond_lv(lv):
            state, _ = jax.tree.unflatten(treedef, list(lv) + tc_leaves)
            return cond(state)

        return jax.lax.while_loop(cond_lv, body,
                                  tuple(all_leaves[:n_state]))

    fn = jax.shard_map(loop, mesh=mesh, in_specs=specs,
                   out_specs=state_specs, check_vma=False)
    return jax.jit(fn)


def run_sharded(state, cfg: SimConfig, tc=None, mesh=None):
    """Run to completion like :func:`engine.run`, with the rack-major
    state axes sharded over ``mesh`` (built from ``cfg.partition`` when
    None).  Bit-identical to the single-device engine by construction."""
    axis = cfg.partition.axis
    if mesh is None:
        mesh = make_mesh(cfg.partition.n_shards, axis)
    n_shards = int(mesh.shape[axis])
    validate_sharding(cfg, n_shards, state)
    state_specs = mesh_lib.sim_state_specs(state, cfg, mesh, axis)
    n_state = len(state_specs)
    leaves, treedef = jax.tree.flatten((state, tc))
    specs = state_specs + (P(),) * (len(leaves) - n_state)
    fn = _runner_for(cfg, mesh, axis, treedef, specs, n_state)
    out = fn(*leaves)
    return jax.tree.unflatten(jax.tree.structure(state), list(out))


# ==========================================================================
# shard-efficiency introspection (bench_engine, analysis/)
# ==========================================================================


def sharded_step_jaxpr(state, cfg: SimConfig, tc=None, mesh=None):
    """The jaxpr of ONE shard-mapped macro-step (gather + event core +
    re-slice) — the unit the collective count is quoted per."""
    axis = cfg.partition.axis
    if mesh is None:
        mesh = make_mesh(cfg.partition.n_shards, axis)
    n_shards = int(mesh.shape[axis])
    validate_sharding(cfg, n_shards, state)
    specs = mesh_lib.sim_state_specs(state, cfg, mesh, axis)
    leaves, treedef = jax.tree.flatten(state)
    step = _sharded_step_fn(cfg, tc, specs, treedef, axis, n_shards)
    fn = jax.shard_map(step, mesh=mesh, in_specs=specs, out_specs=specs,
                   check_vma=False)
    return jax.make_jaxpr(fn)(*leaves)


def n_sharded_leaves(state, cfg: SimConfig, mesh=None) -> int:
    """How many state leaves the rack partition actually shards — the
    expected ``all_gather`` count per macro-step (one per sharded leaf;
    counting lives in ``analysis.jaxpr_audit``)."""
    axis = cfg.partition.axis
    if mesh is None:
        mesh = make_mesh(cfg.partition.n_shards, axis)
    specs = mesh_lib.sim_state_specs(state, cfg, mesh, axis)
    return sum(1 for sp in specs if len(sp) and sp[0] == axis)

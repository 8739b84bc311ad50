"""Fused data-center-simulator advance Pallas TPU kernel.

The engine's hot loop (core/engine.sim_step) streams the whole farm state
from HBM several times per event: once for the min-reduction, once for
energy accrual, once for the completion update.  This kernel fuses the
"advance farm to t_next" into a single VMEM pass over server blocks:

  per block of servers (servers over sublanes x lanes, one plane per core):
    busy count -> piecewise power -> energy += P·dt, busy_seconds += busy·dt
    completions (busy_until <= t_next) freed to INF, mask emitted
    next-event candidate: min over the block of surviving busy_until,
    pending wake completions, and idle delay-timer expiries — the farm's
    contribution to the NEXT next_event_time, so the following iteration's
    min-reduction needs no extra pass over the farm arrays

It is the TPU analogue of the paper's event-queue pop + clock advance —
O(state) streaming with everything fused at VPU width, instead of a heap's
pointer chasing (DESIGN.md §3.4).

Oracle: ref.dcsim_advance_reference; swept in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


INF = 1.0e30
LANES = 128


def _kernel(time_ref, ptab_ref, busy_ref, state_ref, energy_ref, bsec_ref,
            wake_ref, isince_ref, tau_ref, thr_ref,
            new_busy_ref, done_ref, new_energy_ref, new_bsec_ref, next_ref,
            *, p_core_active, p_core_idle, n_cores, throttle_power_scale):
    t_next = time_ref[1]
    dt = t_next - time_ref[0]
    cb = busy_ref[...]                                    # (C, br, 128)
    st = state_ref[...]                                   # (br, 128)
    busy = (cb < INF).astype(jnp.float32).sum(axis=0)     # (br, 128)
    awake = st <= 1
    # thermally throttled servers draw scaled active-core power
    # (linear-DVFS approximation, mirrors power.server_power)
    p_act = jnp.where(thr_ref[...] != 0,
                      jnp.float32(p_core_active * throttle_power_scale),
                      jnp.float32(p_core_active))
    p_awake = ptab_ref[0] + busy * p_act \
        + (n_cores - busy) * p_core_idle
    # table[clip(state)] as a select chain over the SMEM table: the chip
    # has no vector gather from a table
    p_state = jnp.full(st.shape, ptab_ref[0], jnp.float32)
    for s in range(1, ptab_ref.shape[0]):
        p_state = jnp.where(st >= s, ptab_ref[s], p_state)
    p = jnp.where(awake, p_awake, p_state)
    new_energy_ref[...] = energy_ref[...] + p * dt
    new_bsec_ref[...] = bsec_ref[...] + busy * dt
    done = cb <= t_next
    done_ref[...] = done.astype(jnp.int32)
    new_busy = jnp.where(done, INF, cb)
    new_busy_ref[...] = new_busy
    # farm candidates for the next event: surviving completions, pending
    # wakeups, and delay-timer expiries of IDLE (state==1) servers
    timer = jnp.where(st == 1, isince_ref[...] + tau_ref[...], INF)
    cand = jnp.minimum(new_busy.min(axis=0),
                       jnp.minimum(wake_ref[...], timer))
    next_ref[...] = cand.min(axis=0, keepdims=True)[None]


def dcsim_advance(core_busy, srv_state, energy, busy_seconds, t, t_next,
                  state_power, p_core_active, p_core_idle,
                  srv_wake_at=None, srv_idle_since=None, srv_tau=None,
                  throttled=None, *, throttle_power_scale=1.0,
                  block_n=4096, interpret=False):
    """Fused farm advance.  core_busy (N, C) f32; srv_state (N,) int32;
    energy/busy_seconds/srv_wake_at/srv_idle_since/srv_tau (N,) f32;
    t/t_next scalars; state_power (SrvState.NUM,) f32 table (index 0 =
    base power of an awake server); throttled (N,) bool/int —
    thermally-throttled servers accrue active-core power scaled by
    ``throttle_power_scale`` (the PR 3 linear-DVFS coupling).

    Inside the kernel servers run along the 128 lanes: per-server vectors
    are laid out (rows, 128) and core_busy (C, rows, 128), cut into blocks
    of ``block_n`` servers (rounded to whole 8-row tiles).  t/t_next and
    the power table sit in SMEM.

    Returns (new_core_busy, done_mask (N, C) bool, energy, busy_seconds,
    next_candidate) where next_candidate is the farm's min next-event time
    after the advance (INF when nothing is pending).
    """
    N, C = core_busy.shape
    if srv_wake_at is None:
        srv_wake_at = jnp.full((N,), INF, jnp.float32)
    if srv_idle_since is None:
        srv_idle_since = jnp.zeros((N,), jnp.float32)
    if srv_tau is None:
        srv_tau = jnp.full((N,), INF, jnp.float32)
    if throttled is None:
        throttled = jnp.zeros((N,), jnp.int32)
    rows = pl.cdiv(N, LANES)
    br = max(8, block_n // LANES // 8 * 8)
    if rows <= br:
        br = rows                     # one block spanning the whole farm
    else:
        rows = pl.cdiv(rows, br) * br
    pad = rows * LANES - N

    def lanes(x, fill, dtype=jnp.float32):
        return jnp.pad(x.astype(dtype), (0, pad),
                       constant_values=fill).reshape(rows, LANES)

    busy_l = jnp.pad(core_busy.astype(jnp.float32), ((0, pad), (0, 0)),
                     constant_values=INF).T.reshape(C, rows, LANES)
    vecs = (lanes(srv_state, 4, jnp.int32),                 # pad: OFF
            lanes(energy, 0), lanes(busy_seconds, 0),
            lanes(srv_wake_at, INF), lanes(srv_idle_since, 0),
            lanes(srv_tau, INF), lanes(throttled, 0, jnp.int32))
    times = jnp.stack([jnp.asarray(t, jnp.float32),
                       jnp.asarray(t_next, jnp.float32)])
    n_blocks = rows // br

    kernel = functools.partial(_kernel, p_core_active=p_core_active,
                               p_core_idle=p_core_idle, n_cores=C,
                               throttle_power_scale=throttle_power_scale)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    cores = pl.BlockSpec((C, br, LANES), lambda i: (0, i, 0))
    row = pl.BlockSpec((br, LANES), lambda i: (i, 0))

    nb, dm, en, bs, nc = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[smem, smem, cores] + [row] * len(vecs),
        out_specs=[cores, cores, row, row,
                   pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((C, rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((C, rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, 1, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(times, state_power.astype(jnp.float32), busy_l, *vecs)

    def servers(x):
        return x.reshape(x.shape[:-2] + (rows * LANES,))[..., :N]

    return (servers(nb).T.astype(core_busy.dtype), servers(dm).T != 0,
            servers(en), servers(bs), nc.min())

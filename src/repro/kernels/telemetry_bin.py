"""Fused telemetry accumulation Pallas TPU kernel.

One VMEM pass per engine step fuses the two latency-histogram scatter-adds
(job + task granularity) with the windowed time-series bucketing
(core/telemetry.py).  Scatter-add is hostile to the TPU's vector unit, so
each block of latencies is binned via a one-hot compare against the bin
iota and reduced at VPU width; the histograms and the window matrix stay
resident in VMEM across the sequential grid (revisited output blocks),
so HBM sees exactly one read and one write of each accumulator.

Oracle: ref.telemetry_accum_reference; swept in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref


def _kernel(widx_ref, wvals_ref, jv_ref, jw_ref, tv_ref, tw_ref,
            jh_in_ref, th_in_ref, win_in_ref,
            jh_ref, th_ref, win_ref, *, lo, hi, n_bins):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        jh_ref[...] = jh_in_ref[...]
        th_ref[...] = th_in_ref[...]
        rows = jax.lax.broadcasted_iota(jnp.int32, win_in_ref.shape, 0)
        win_ref[...] = win_in_ref[...] + jnp.where(
            rows == widx_ref[0], wvals_ref[...][None, :], 0.0)

    def contrib(vals, wts):
        # ref.log_bin keeps kernel and jnp oracle bit-identical
        bins = ref.log_bin(vals, lo, hi, n_bins)
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (vals.shape[0], n_bins), 1)
        onehot = (bins[:, None] == cols).astype(jnp.float32)
        return (onehot * wts[:, None]).sum(axis=0)

    jh_ref[...] += contrib(jv_ref[...], jw_ref[...])
    th_ref[...] += contrib(tv_ref[...], tw_ref[...])


def telemetry_accum(job_vals, job_wts, task_vals, task_wts,
                    job_hist, task_hist, win, widx, wvals,
                    lo, hi, *, block=1024, interpret=False):
    """Fused telemetry update.  job_vals/job_wts (J,) f32; task_vals/
    task_wts (M,) f32; job_hist/task_hist (B,) f32; win (W, K) f32;
    widx () int32 window index; wvals (K,) f32 window increments;
    lo/hi python floats — the log-spaced bin range.

    Returns (job_hist, task_hist, win) with this step's contributions
    accumulated; semantics match ref.telemetry_accum_reference.
    """
    B = job_hist.shape[0]
    lo, hi = float(lo), float(hi)

    def pad_stream(vals, wts, n_blocks):
        n = vals.shape[0]
        pad = n_blocks * block - n
        if pad:
            vals = jnp.pad(vals, (0, pad), constant_values=lo)
            wts = jnp.pad(wts, (0, pad))    # zero weight: no contribution
        return vals.astype(jnp.float32), wts.astype(jnp.float32)

    n_blocks = max(pl.cdiv(job_vals.shape[0], block),
                   pl.cdiv(task_vals.shape[0], block))
    jv, jw = pad_stream(job_vals, job_wts, n_blocks)
    tv, tw = pad_stream(task_vals, task_wts, n_blocks)
    W, K = win.shape

    kernel = functools.partial(_kernel, lo=lo, hi=hi, n_bins=B)
    widx1 = jnp.asarray(widx, jnp.int32).reshape(1)

    jh, th, w = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),            # widx
            pl.BlockSpec((K,), lambda i: (0,)),            # wvals
            pl.BlockSpec((block,), lambda i: (i,)),        # job vals
            pl.BlockSpec((block,), lambda i: (i,)),        # job wts
            pl.BlockSpec((block,), lambda i: (i,)),        # task vals
            pl.BlockSpec((block,), lambda i: (i,)),        # task wts
            pl.BlockSpec((B,), lambda i: (0,)),            # job hist in
            pl.BlockSpec((B,), lambda i: (0,)),            # task hist in
            pl.BlockSpec((W, K), lambda i: (0, 0)),        # win in
        ],
        out_specs=[
            pl.BlockSpec((B,), lambda i: (0,)),
            pl.BlockSpec((B,), lambda i: (0,)),
            pl.BlockSpec((W, K), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B,), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.float32),
            jax.ShapeDtypeStruct((W, K), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(widx1, wvals.astype(jnp.float32), jv, jw, tv, tw,
      job_hist, task_hist, win)
    return jh, th, w

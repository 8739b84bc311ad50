"""Chunked selective-SSM scan Pallas TPU kernel (hymba's Mamba mixer).

The recurrence h[t] = exp(dt·A)⊙h[t-1] + (dt·x)[t]⊗B[t] is elementwise over
the (Dss, N) state — VPU work, not MXU — so the kernel's job is purely a
memory-hierarchy one: tile Dss into VMEM-resident channel blocks, keep the
running state h in VMEM scratch across sequential time chunks (grid
dimension marked "arbitrary"), and stream dt/B/C/x through.  One HBM pass
instead of S tiny scan iterations; the time chunk is unrolled inside the
kernel body over registers.

Grid: (B, Dss/block_d, S/chunk_t) — batch and channel blocks parallel, time
chunks sequential.  State block (block_d, N) f32 lives in scratch; with
block_d=512, N=16 that is 32 KB — negligible, the VMEM budget goes to the
streamed (chunk_t, block_d) inputs.

Oracle: ref.ssm_scan_reference (the engine's lax.scan).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(dt_ref, b_ref, c_ref, x_ref, a_ref, y_ref, h_ref, *, chunk_t):
    tb = pl.program_id(2)

    @pl.when(tb == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)                    # (bd, N)
    h = h_ref[...]                                        # (bd, N)
    ys = []
    for i in range(chunk_t):                              # unrolled in VREGs
        dt_t = dt_ref[0, i].astype(jnp.float32)           # (bd,)
        x_t = x_ref[0, i].astype(jnp.float32)             # (bd,)
        b_t = b_ref[0, i].astype(jnp.float32)             # (N,)
        c_t = c_ref[0, i].astype(jnp.float32)             # (N,)
        da = jnp.exp(dt_t[:, None] * a)                   # (bd, N)
        h = da * h + (dt_t * x_t)[:, None] * b_t[None, :]
        ys.append(jnp.sum(h * c_t[None, :], axis=1))      # (bd,)
    h_ref[...] = h
    y_ref[0] = jnp.stack(ys).astype(y_ref.dtype)          # (chunk_t, bd)


def ssm_scan(dt, Bm, Cm, x, A, *, block_d=256, chunk_t=16, interpret=False):
    """dt/x (B, S, Dss); Bm/Cm (B, S, N); A (Dss, N).
    Returns y (B, S, Dss) = C·h with h the selective-SSM state."""
    B, S, Dss = x.shape
    N = Bm.shape[-1]
    block_d = min(block_d, Dss)
    chunk_t = min(chunk_t, S)
    assert Dss % block_d == 0, (Dss, block_d)
    assert S % chunk_t == 0, (S, chunk_t)
    nd = Dss // block_d
    nt = S // chunk_t

    kernel = functools.partial(_kernel, chunk_t=chunk_t)
    out = pl.pallas_call(
        kernel,
        grid=(B, nd, nt),
        in_specs=[
            pl.BlockSpec((1, chunk_t, block_d),
                         lambda b, d, t: (b, t, d)),       # dt
            pl.BlockSpec((1, chunk_t, N), lambda b, d, t: (b, t, 0)),  # B
            pl.BlockSpec((1, chunk_t, N), lambda b, d, t: (b, t, 0)),  # C
            pl.BlockSpec((1, chunk_t, block_d),
                         lambda b, d, t: (b, t, d)),       # x
            pl.BlockSpec((block_d, N), lambda b, d, t: (d, 0)),        # A
        ],
        out_specs=pl.BlockSpec((1, chunk_t, block_d),
                               lambda b, d, t: (b, t, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, Dss), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dt, Bm, Cm, x, A)
    return out

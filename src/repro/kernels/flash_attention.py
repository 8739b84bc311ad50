"""Flash attention Pallas TPU kernel (prefill / train hot spot).

Classic streaming-softmax formulation: the grid is (batch, q_heads,
q_blocks, kv_blocks) with the kv dimension marked "arbitrary" so each
(b, h, qb) program accumulates over kv blocks in VMEM scratch — running max
m, running sum l, and the (block_q, head_dim) f32 accumulator — and writes
the normalized output at the last kv step.  GQA is handled by the K/V
index_map (kv head = h // group); causal and sliding-window masks and the
gemma2 attention softcap are applied in-kernel.

Block sizes default to (block_q, block_k) = (128, 128): MXU-aligned on the
(8,128)/(128,128) register tiling, and the VMEM working set
q(128×hd) + k/v(128×hd) + acc(128×hd f32) stays well under 16 MB for
hd ≤ 256.

Correctness oracle: ``ref.mha_reference`` (pure jnp, the same math as
models/layers.attend); validated under interpret=True in
tests/test_kernels.py across shape/dtype/window/softcap sweeps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, window, softcap, block_q, block_k, kv_len):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq,)
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(kb == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128, interpret=False):
    """q (B, H, Sq, hd); k/v (B, KV, Skv, hd); H % KV == 0.
    Returns (B, H, Sq, hd) in q.dtype."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    nq = -(-Sq // block_q)
    nk = -(-Skv // block_k)
    pad_q = nq * block_q - Sq
    pad_k = nk * block_k - Skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, kv_len=Skv)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qb, kb: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qb, kb: (b, h // G, kb, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qb, kb: (b, h // G, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qb, kb: (b, h, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * block_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]

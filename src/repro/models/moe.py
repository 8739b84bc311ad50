"""Mixture-of-Experts layer (qwen3-moe, moonshot) — GShard-style routing
with token dropping at a capacity factor, adapted to the TPU mesh.

Layout
------
tokens  (B, S, D)   B sharded over the batch axes ("pod","data"), D replicated
experts (E, D, F)   E sharded over "model"  (expert parallelism == TP axis)

The classic GShard one-hot dispatch/combine einsums materialize a
(B, S, E, C) mask — at our scale that is tens of TB, so they survive only as
a small-shape oracle (``moe_einsum``) used by the tests.  The production path
(``moe_scatter``):

  1. route: top-k experts per token (softmax over the chosen k, f32)
  2. position-in-expert via a *chunked* one-hot running cumsum (bounded
     memory), capacity C = ceil(S·k·cf / E)
  3. inverse index (B, E, C) -> token slot, built with a cheap int32 scatter
  4. dispatch = batched gather (local, zero FLOPs, zero collectives)
  5. slice E onto "model" (free — E was locally replicated)
  6. expert FFN einsums (fully local: E on "model", B on batch axes)
  7. combine under ``shard_map``: every model shard scatter-gathers only its
     own experts' outputs and a single psum over "model" reduces partial
     token outputs — exactly one activation-sized all-reduce per MoE layer,
     the same collective cost as Megatron-style dense TP.

Aux losses: switch-style load-balance loss and router z-loss.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import layers


def capacity(cfg, S: int) -> int:
    import math
    return max(1, math.ceil(S * cfg.top_k * cfg.capacity_factor
                            / cfg.n_experts))


def route(p, x, cfg):
    """Returns (topi (B,S,k) int32, gates (B,S,k) f32, aux_loss f32)."""
    logits = (x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(topv, axis=-1)              # renormalized over k
    # switch load-balance loss: E * mean(f_e * p_e)
    ohot = jax.nn.one_hot(topi[..., 0], cfg.n_experts, dtype=jnp.float32)
    frac = ohot.mean(axis=(0, 1))
    mean_p = probs.mean(axis=(0, 1))
    lb = cfg.n_experts * jnp.sum(frac * mean_p)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return topi.astype(jnp.int32), gates, lb + cfg.router_zloss * z


def _positions_in_expert(topi, cfg, chunk: int = 4096):
    """topi (B, S, k) -> pos (B, S, k): the running index of each (token,
    choice) within its expert, computed with a chunked cumsum so the one-hot
    tensor never exceeds (B, chunk, E)."""
    B, S, k = topi.shape
    E = cfg.n_experts
    ek = topi.reshape(B, S * k)
    T = S * k
    c = min(chunk, T)
    n = -(-T // c)
    pad = n * c - T
    ekp = jnp.pad(ek, ((0, 0), (0, pad)), constant_values=0) if pad else ek
    ekc = ekp.reshape(B, n, c).transpose(1, 0, 2)      # (n, B, c)

    def step(counts, ids):
        oh = jax.nn.one_hot(ids, E, dtype=jnp.int32)   # (B, c, E)
        within = jnp.cumsum(oh, axis=1) - oh + counts[:, None, :]
        pos = jnp.take_along_axis(within, ids[..., None], axis=-1)[..., 0]
        return counts + oh.sum(axis=1), pos

    _, pos = jax.lax.scan(step, jnp.zeros((B, E), jnp.int32), ekc)
    pos = pos.transpose(1, 0, 2).reshape(B, n * c)[:, :T]
    return pos.reshape(B, S, k)


def _dispatch(x, topi, pos, keep, C, cfg):
    """Batched-gather dispatch -> (B, E, C, D); empty slots are zero."""
    B, S, D = x.shape
    E = cfg.n_experts
    # inverse map: (B, E, C) -> source token (sentinel S = zero row)
    slot_e = topi.reshape(B, -1)                                   # (B, S*k)
    slot_c = jnp.where(keep, pos, C).reshape(B, -1)                # overflow->C
    src = jnp.broadcast_to(jnp.arange(S)[:, None], (S, cfg.top_k)
                           ).reshape(1, -1)
    inv = jnp.full((B, E, C + 1), S, jnp.int32)
    b_ix = jnp.broadcast_to(jnp.arange(B)[:, None], slot_e.shape)
    inv = inv.at[b_ix, slot_e, slot_c].set(
        jnp.broadcast_to(src, slot_e.shape), mode="drop")
    inv = inv[:, :, :C]                                            # (B, E, C)
    x_pad = jnp.concatenate([x, jnp.zeros((B, 1, D), x.dtype)], axis=1)
    out = jnp.take_along_axis(
        x_pad, inv.reshape(B, E * C)[..., None], axis=1)
    return out.reshape(B, E, C, D), inv


def _expert_ffn(p, h, cfg):
    """h (B, E, C, D) -> (B, E, C, D); E sharded on "model"."""
    dt = h.dtype
    g = jnp.einsum("becd,edf->becf", h, p["wg"].astype(dt))
    u = jnp.einsum("becd,edf->becf", h, p["wu"].astype(dt))
    a = jax.nn.silu(g) * u
    return jnp.einsum("becf,efd->becd", a, p["wd"].astype(dt))


def _combine_local(expert_out, topi, pos, keep, gates, e_base, E_loc, S):
    """Per-shard combine: sum each token's local-expert outputs."""
    B = expert_out.shape[0]
    D = expert_out.shape[-1]
    out = jnp.zeros((B, S, D), expert_out.dtype)
    for j in range(topi.shape[-1]):                    # static k loop
        e = topi[..., j]                               # (B, S)
        sel = (e >= e_base) & (e < e_base + E_loc) & keep[..., j]
        el = jnp.clip(e - e_base, 0, E_loc - 1)
        cj = jnp.clip(pos[..., j], 0, expert_out.shape[2] - 1)
        flat = el * expert_out.shape[2] + cj           # (B, S)
        eo = expert_out.reshape(B, -1, D)
        vals = jnp.take_along_axis(eo, flat[..., None], axis=1)
        w = (gates[..., j] * sel).astype(expert_out.dtype)
        out = out + vals * w[..., None]
    return out


def moe_scatter(p, x, cfg, mesh=None, mesh_axes=("data", "model")):
    """Production MoE path.  mesh_axes = (batch axes ..., model axis)."""
    B, S, D = x.shape
    E = cfg.n_experts
    C = capacity(cfg, S)
    bax, model_ax = mesh_axes[:-1], mesh_axes[-1]
    bspec = bax[0] if len(bax) == 1 else tuple(bax)

    topi, gates, aux = route(p, x, cfg)
    pos = _positions_in_expert(topi, cfg)
    keep = pos < C
    dropped = jnp.sum(~keep & (gates > 0))

    h, _ = _dispatch(x, topi, pos, keep, C, cfg)        # (B, E, C, D)
    sharded = (mesh is not None and model_ax in mesh.axis_names
               and mesh.shape[model_ax] > 1
               and E % mesh.shape[model_ax] == 0)
    if sharded:
        h = jax.lax.with_sharding_constraint(
            h, P(bspec, model_ax, None, None))
    h = _expert_ffn(p, h, cfg)

    if sharded:
        E_loc = E // mesh.shape[model_ax]

        def combine(eo, ti, po, ke, ga):
            e_base = jax.lax.axis_index(model_ax) * E_loc
            out = _combine_local(eo, ti, po, ke, ga, e_base, E_loc, S)
            return jax.lax.psum(out, model_ax)

        out = jax.shard_map(
            combine, mesh=mesh,
            in_specs=(P(bspec, model_ax, None, None), P(bspec), P(bspec),
                      P(bspec), P(bspec)),
            out_specs=P(bspec), check_vma=False,
        )(h, topi, pos, keep, gates)
    else:                                               # single-device / tests
        out = _combine_local(h, topi, pos, keep, gates, 0, E, S)

    if cfg.n_shared_experts:
        out = out + layers.mlp(p["shared"], x, "silu")
    return out.astype(x.dtype), aux, dropped


# --------------------------------------------------------------------------
# small-shape oracle: classic GShard one-hot einsum dispatch/combine
# --------------------------------------------------------------------------

def moe_einsum(p, x, cfg):
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = capacity(cfg, S)
    topi, gates, aux = route(p, x, cfg)
    pos = _positions_in_expert(topi, cfg)
    keep = pos < C
    oh_e = jax.nn.one_hot(topi, E, dtype=jnp.float32)            # (B,S,k,E)
    oh_c = jax.nn.one_hot(jnp.where(keep, pos, C), C,
                          dtype=jnp.float32)                     # (B,S,k,C)
    disp = jnp.einsum("bske,bskc->bsec", oh_e, oh_c)             # bool-ish
    comb = jnp.einsum("bske,bskc,bsk->bsec", oh_e, oh_c, gates)
    h = jnp.einsum("bsec,bsd->becd", disp.astype(x.dtype), x)
    h = _expert_ffn(p, h, cfg)
    out = jnp.einsum("bsec,becd->bsd", comb.astype(x.dtype), h)
    if cfg.n_shared_experts:
        out = out + layers.mlp(p["shared"], x, "silu")
    dropped = jnp.sum(~keep & (gates > 0))
    return out.astype(x.dtype), aux, dropped


def moe_block(p, x, cfg, mesh=None, mesh_axes=("data", "model")):
    if cfg.moe_impl == "einsum":
        return moe_einsum(p, x, cfg)
    return moe_scatter(p, x, cfg, mesh, mesh_axes)

"""Recursive jaxpr auditor: per-primitive inventory with region provenance,
plus the clock-dtype taint interpreter.

The walker descends through every higher-order primitive (``pjit``,
``while``, ``cond``, ``scan``, ``shard_map``, custom-call wrappers) and
tracks *region provenance*: ``jax.named_scope`` tags recorded on each
equation's name stack are inherited downward into sub-jaxprs, so a rule can
target "the cheap-core body" (``engine._consume_cheap`` runs under
``named_scope("cheap_core")``) separately from "the full step".

Two analyses share the walk:

* :func:`audit` -- an :class:`Inventory` of every equation: primitive name,
  region, and user source location.  Scatter/gather/collective/callback/
  dynamic-slice counts and per-region histograms come from it.
* :func:`clock_audit` -- a forward taint propagation from the declared
  time-valued state leaves.  A downcast of a time value below
  ``cfg.time_dtype`` *outside* a ``named_scope(F32_DOMAIN)`` block marks the
  result DEGRADED; a DEGRADED value reaching a time-valued output leaf is a
  clock-precision leak (the PR 5 ``next_release_time`` bug class), reported
  with the originating downcast's source line.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Sequence

import jax
from jax.extend import core as jax_core
import numpy as np

# Region tag marking intentional exits from the time domain: values
# downcast inside this scope are f32 physics (energy, temperatures,
# telemetry weights), not clocks, and do not carry degraded-clock taint.
F32_DOMAIN = "f32_domain"

SCATTER_PRIMS = frozenset(
    {"scatter", "scatter-add", "scatter-mul", "scatter-min", "scatter-max"}
)
GATHER_PRIMS = frozenset({"gather"})
DYNAMIC_SLICE_PRIMS = frozenset({"dynamic_slice", "dynamic_update_slice"})
COLLECTIVE_PRIMS = frozenset(
    {
        "all_gather",
        "all_gather_invariant",
        "psum",
        "psum2",
        "psum_invariant",
        "pmin",
        "pmax",
        "all_to_all",
        "ppermute",
        "pbroadcast",
        "reduce_scatter",
        "pgather",
        "all_reduce",
    }
)
CALLBACK_PRIMS = frozenset(
    {"pure_callback", "io_callback", "debug_callback", "callback"}
)


@dataclasses.dataclass(frozen=True)
class Site:
    """One equation occurrence: primitive, region provenance, source."""

    prim: str
    region: str  # "/"-joined named-scope components ("" = outer)
    src: str  # user source location "file:line (fn)"

    def in_region(self, region: str) -> bool:
        return region in self.region.split("/")


def _source_of(eqn) -> str:
    try:
        from jax._src import source_info_util

        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return "<unknown>"


def _sub_jaxprs(value) -> Iterator:
    """Yield every (open) jaxpr buried in an eqn param value."""
    if isinstance(value, jax_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jax_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from _sub_jaxprs(item)


def _region_of(eqn, inherited: tuple) -> tuple:
    stack = str(eqn.source_info.name_stack)
    comps = tuple(c for c in stack.split("/") if c)
    return inherited + comps


def iter_eqns(jaxpr, region: tuple = ()) -> Iterator:
    """Yield ``(eqn, region_components)`` over ``jaxpr`` and every
    sub-jaxpr, with named-scope components inherited downward."""
    for eqn in jaxpr.eqns:
        reg = _region_of(eqn, region)
        yield eqn, reg
        for param in eqn.params.values():
            for sub in _sub_jaxprs(param):
                yield from iter_eqns(sub, reg)


@dataclasses.dataclass
class Inventory:
    """Flat per-primitive inventory of one traced program."""

    sites: list
    n_eqns: int

    def count(self, prims, region: Optional[str] = None) -> int:
        if isinstance(prims, str):
            prims = {prims}
        return sum(
            1
            for s in self.sites
            if s.prim in prims and (region is None or s.in_region(region))
        )

    def sites_of(self, prims, region: Optional[str] = None) -> list:
        if isinstance(prims, str):
            prims = {prims}
        return [
            s
            for s in self.sites
            if s.prim in prims and (region is None or s.in_region(region))
        ]

    def histogram(self) -> dict:
        """``{region: {prim: count}}`` with the full region path as key."""
        out: dict = {}
        for s in self.sites:
            reg = out.setdefault(s.region, {})
            reg[s.prim] = reg.get(s.prim, 0) + 1
        return {r: dict(sorted(v.items())) for r, v in sorted(out.items())}

    def summary(self) -> dict:
        return {
            "eqns": self.n_eqns,
            "scatter": self.count(SCATTER_PRIMS),
            "scatter_cheap_core": self.count(SCATTER_PRIMS, "cheap_core"),
            "gather": self.count(GATHER_PRIMS),
            "dynamic_slice": self.count(DYNAMIC_SLICE_PRIMS),
            "collectives": {
                p: self.count(p)
                for p in sorted(COLLECTIVE_PRIMS)
                if self.count(p)
            },
            "callbacks": self.count(CALLBACK_PRIMS),
        }


def audit(closed_jaxpr) -> Inventory:
    """Walk a (closed) jaxpr into a flat :class:`Inventory`."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    sites = [
        Site(prim=eqn.primitive.name, region="/".join(reg), src=_source_of(eqn))
        for eqn, reg in iter_eqns(jaxpr)
    ]
    return Inventory(sites=sites, n_eqns=len(sites))


# ==========================================================================
# clock-dtype taint propagation
# ==========================================================================

# taint lattice: NONE < CLEAN (a time value) < DEGRADED (a time value that
# went through a sub-time_dtype float outside F32_DOMAIN)
NONE, CLEAN, DEGRADED = 0, 1, 2

# state leaves that carry absolute simulation times (suffix-matched on
# jax.tree_util.keystr paths of SimState)
TIME_LEAVES = (
    ".t",
    ".farm.core_busy_until",
    ".farm.srv_wake_at",
    ".farm.srv_idle_since",
    ".farm.srv_tau",
    ".jobs.arrival",
    ".jobs.task_end",
    ".jobs.start_at",
    ".jobs.finish",
    ".jobs.job_finish",
    ".jobs.admit_at",
    ".jobs.deadline",
    ".flows.done_at",
    ".flows.extra",
    ".net.port_idle_since",
    ".thermal.ctrl_next",
)


def time_leaf_mask(tree) -> list:
    """Per-leaf bool: is this flattened leaf a declared clock array?"""
    leaves_with_path, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [
        any(jax.tree_util.keystr(path).endswith(s) for s in TIME_LEAVES)
        for path, _ in leaves_with_path
    ]


def time_leaf_names(tree) -> list:
    leaves_with_path, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [jax.tree_util.keystr(path) for path, _ in leaves_with_path]


def _is_float(aval) -> bool:
    return hasattr(aval, "dtype") and np.issubdtype(aval.dtype, np.floating)


def _float_bits(aval) -> int:
    return np.dtype(aval.dtype).itemsize * 8


def _join(a: tuple, b: tuple) -> tuple:
    return a if a[0] >= b[0] else b


_NO_TAINT = (NONE, None)


class _TaintEnv:
    """Var -> (level, origin site) with Literal inputs always NONE."""

    def __init__(self):
        self._env: dict = {}

    def read(self, var) -> tuple:
        if isinstance(var, jax_core.Literal):
            return _NO_TAINT
        return self._env.get(var, _NO_TAINT)

    def write(self, var, taint: tuple) -> None:
        if taint[0] != NONE:
            self._env[var] = taint


@dataclasses.dataclass
class ClockReport:
    """Result of :func:`clock_audit`."""

    time_dtype: str
    # {leaf name: dtype} for declared time leaves, inputs and outputs
    in_census: dict
    out_census: dict
    # [(leaf name, downcast site)] time outputs reconstructed from a value
    # that lost precision outside F32_DOMAIN
    degraded_leaves: list
    # every downcast site that created degraded taint (for diagnostics)
    downcast_sites: list

    @property
    def census_violations(self) -> list:
        bad = []
        for census, tag in ((self.in_census, "input"), (self.out_census, "output")):
            for name, dtype in census.items():
                if dtype != self.time_dtype:
                    bad.append((name, tag, dtype))
        return bad


def clock_audit(closed_jaxpr, state_template, time_dtype) -> ClockReport:
    """Propagate clock taint through ``closed_jaxpr`` (traced from a
    ``state -> state`` step over ``state_template``'s pytree layout)."""
    time_dtype = np.dtype(time_dtype)
    tbits = time_dtype.itemsize * 8
    jaxpr = closed_jaxpr.jaxpr
    mask = time_leaf_mask(state_template)
    names = time_leaf_names(state_template)
    n_leaves = len(mask)
    if len(jaxpr.invars) < n_leaves or len(jaxpr.outvars) < n_leaves:
        raise ValueError(
            f"jaxpr arity ({len(jaxpr.invars)} in / {len(jaxpr.outvars)} out)"
            f" smaller than the state template's {n_leaves} leaves"
        )

    downcasts: list = []

    def run(jx, in_taints: Sequence, region: tuple) -> list:
        env = _TaintEnv()
        for var, taint in zip(jx.invars, in_taints):
            env.write(var, taint)

        for eqn in jx.eqns:
            reg = _region_of(eqn, region)
            ins = [env.read(v) for v in eqn.invars]
            outs = _apply(eqn, ins, reg)
            for var, taint in zip(eqn.outvars, outs):
                env.write(var, taint)
        return [env.read(v) for v in jx.outvars]

    def _default(eqn, ins) -> list:
        joined = _NO_TAINT
        for t in ins:
            joined = _join(joined, t)
        return [joined if _is_float(v.aval) else _NO_TAINT for v in eqn.outvars]

    def _apply(eqn, ins, region) -> list:
        name = eqn.primitive.name
        if name == "convert_element_type":
            (taint,) = ins
            out = eqn.outvars[0]
            if not _is_float(out.aval):
                return [_NO_TAINT]
            if taint[0] == NONE:
                return [_NO_TAINT]
            if F32_DOMAIN in region:
                # declared exit into the f32 physics domain: the result is
                # no longer a clock
                return [_NO_TAINT]
            if _float_bits(out.aval) < tbits:
                site = _source_of(eqn)
                downcasts.append(site)
                return [(DEGRADED, site)]
            return [taint]
        if name == "while":
            return _run_while(eqn, ins, region)
        if name == "scan":
            return _run_scan(eqn, ins, region)
        if name == "cond":
            return _run_cond(eqn, ins, region)
        sub = [j for p in eqn.params.values() for j in _sub_jaxprs(p)]
        if sub:
            if len(sub) == 1 and len(sub[0].invars) == len(eqn.invars):
                # pjit / shard_map / closed_call / custom-call wrappers:
                # positional pass-through
                outs = run(sub[0], ins, _region_of(eqn, region))
                if len(outs) == len(eqn.outvars):
                    return outs
            # unknown higher-order primitive: conservative join-all
            return _default(eqn, ins)
        return _default(eqn, ins)

    def _run_while(eqn, ins, region):
        reg = _region_of(eqn, region)
        nc = eqn.params["cond_nconsts"]
        nb = eqn.params["body_nconsts"]
        body = eqn.params["body_jaxpr"].jaxpr
        body_consts = ins[nc : nc + nb]
        carry = list(ins[nc + nb :])
        for _ in range(8):  # lattice height bounds convergence well below
            outs = run(body, list(body_consts) + carry, reg)
            new = [_join(c, o) for c, o in zip(carry, outs)]
            if new == carry:
                break
            carry = new
        return carry

    def _run_scan(eqn, ins, region):
        reg = _region_of(eqn, region)
        nc = eqn.params["num_consts"]
        ncarry = eqn.params["num_carry"]
        body = eqn.params["jaxpr"].jaxpr
        consts = list(ins[:nc])
        carry = list(ins[nc : nc + ncarry])
        xs = list(ins[nc + ncarry :])
        ys = [_NO_TAINT] * (len(eqn.outvars) - ncarry)
        for _ in range(8):
            outs = run(body, consts + carry + xs, reg)
            new = [_join(c, o) for c, o in zip(carry, outs[:ncarry])]
            ys = [_join(y, o) for y, o in zip(ys, outs[ncarry:])]
            if new == carry:
                break
            carry = new
        return carry + ys

    def _run_cond(eqn, ins, region):
        reg = _region_of(eqn, region)
        outs = [_NO_TAINT] * len(eqn.outvars)
        for branch in eqn.params["branches"]:
            bouts = run(branch.jaxpr, ins[1:], reg)
            outs = [_join(a, b) for a, b in zip(outs, bouts)]
        return outs

    in_taints = [_NO_TAINT] * len(jaxpr.invars)
    for i, is_time in enumerate(mask):
        if is_time:
            in_taints[i] = (CLEAN, None)
    out_taints = run(jaxpr, in_taints, ())

    in_census = {
        names[i]: str(np.dtype(jaxpr.invars[i].aval.dtype))
        for i in range(n_leaves)
        if mask[i]
    }
    out_census = {
        names[i]: str(np.dtype(jaxpr.outvars[i].aval.dtype))
        for i in range(n_leaves)
        if mask[i]
    }
    degraded = [
        (names[i], out_taints[i][1] or "<unknown>")
        for i in range(n_leaves)
        if mask[i] and out_taints[i][0] == DEGRADED
    ]
    return ClockReport(
        time_dtype=str(time_dtype),
        in_census=in_census,
        out_census=out_census,
        degraded_leaves=degraded,
        downcast_sites=sorted(set(downcasts)),
    )

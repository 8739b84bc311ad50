"""Static per-equation cost model: bytes touched, FLOPs, arithmetic
intensity, and the state-footprint (HBM budget) table.

This is a *jaxpr-level* estimate, not a compiled-module measurement: loop
bodies are counted once (static structure, same convention as
``roofline.analysis.raw_stats``), fusion is ignored, and bytes are the sum
of input+output aval sizes per equation.  That makes the numbers an upper
bound on memory traffic and a structural fingerprint — good for "did this
PR double the bytes the cheap core touches", not for wall-clock prediction
(the benchmarks guard that).

``state_footprint`` sizes the full ``SimState`` pytree via
``jax.eval_shape`` without materialising it, so the 65536-server farm's HBM
budget is a printed table rather than a guess.
"""

from __future__ import annotations

import dataclasses

import jax
from jax.extend import core as jax_core
import numpy as np

from .jaxpr_audit import iter_eqns

# same hardware model as roofline/analysis.py (TPU v5e)
from ..roofline.analysis import HBM_BW, PEAK_FLOPS  # noqa: F401

HBM_PER_CHIP = 16e9  # bytes, v5e


def _aval_bytes(aval) -> int:
    if not hasattr(aval, "dtype") or not hasattr(aval, "shape"):
        return 0
    n = 1
    for d in aval.shape:
        try:
            n *= int(d)
        except TypeError:  # symbolic dim
            return 0
    return n * np.dtype(aval.dtype).itemsize


def _out_size(eqn) -> int:
    return sum(
        int(np.prod(v.aval.shape)) if hasattr(v.aval, "shape") else 0
        for v in eqn.outvars
    )


_ELEMENTWISE_FLOP_WEIGHT = {
    "exp": 8,
    "log": 8,
    "sin": 8,
    "cos": 8,
    "tanh": 8,
    "erf": 8,
    "rsqrt": 4,
    "sqrt": 4,
    "div": 4,
    "pow": 8,
    "integer_pow": 2,
}

_REDUCTIONS = frozenset(
    {
        "reduce_sum",
        "reduce_max",
        "reduce_min",
        "reduce_prod",
        "reduce_and",
        "reduce_or",
        "argmax",
        "argmin",
        "cumsum",
        "cummax",
        "cummin",
        "cumlogsumexp",
    }
)

_ZERO_FLOP = frozenset(
    {
        "broadcast_in_dim",
        "reshape",
        "transpose",
        "squeeze",
        "slice",
        "concatenate",
        "convert_element_type",
        "copy",
        "rev",
        "iota",
        "gather",
        "scatter",
        "dynamic_slice",
        "dynamic_update_slice",
        "pad",
        "bitcast_convert_type",
        "stop_gradient",
        "select_n",
    }
)


def eqn_cost(eqn) -> tuple:
    """(bytes, flops) estimate for one equation (sub-jaxprs excluded —
    the walker visits their eqns separately)."""
    name = eqn.primitive.name
    has_sub = any(
        isinstance(p, (jax_core.ClosedJaxpr, jax_core.Jaxpr))
        or (
            isinstance(p, (tuple, list))
            and any(isinstance(q, (jax_core.ClosedJaxpr, jax_core.Jaxpr)) for q in p)
        )
        for p in eqn.params.values()
    )
    if has_sub:
        return 0, 0  # charged to the inner eqns
    bytes_ = sum(
        _aval_bytes(v.aval) for v in eqn.invars if not isinstance(v, jax_core.Literal)
    ) + sum(_aval_bytes(v.aval) for v in eqn.outvars)
    if name in _ZERO_FLOP:
        return bytes_, 0
    if name == "dot_general":
        dims = eqn.params["dimension_numbers"][0][0]
        lhs = eqn.invars[0].aval
        contracted = 1
        for d in dims:
            contracted *= int(lhs.shape[d])
        return bytes_, 2 * _out_size(eqn) * contracted
    if name in _REDUCTIONS:
        insz = sum(
            int(np.prod(v.aval.shape))
            for v in eqn.invars
            if hasattr(v.aval, "shape") and not isinstance(v, jax_core.Literal)
        )
        return bytes_, insz
    if name == "sort":
        insz = max(
            (
                int(np.prod(v.aval.shape))
                for v in eqn.invars
                if hasattr(v.aval, "shape")
            ),
            default=0,
        )
        return bytes_, insz * max(int(np.log2(max(insz, 2))), 1)
    weight = _ELEMENTWISE_FLOP_WEIGHT.get(name, 1)
    return bytes_, weight * _out_size(eqn)


@dataclasses.dataclass
class CostReport:
    """Rolled-up static cost of one traced program."""

    total_bytes: int
    total_flops: int
    by_region: dict  # {region: {"bytes": int, "flops": int, "eqns": int}}

    @property
    def arithmetic_intensity(self) -> float:
        return self.total_flops / max(self.total_bytes, 1)

    def to_json(self) -> dict:
        return {
            "bytes": self.total_bytes,
            "flops": self.total_flops,
            "arithmetic_intensity": round(self.arithmetic_intensity, 4),
            "by_region": self.by_region,
        }


def cost_of(closed_jaxpr) -> CostReport:
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    by_region: dict = {}
    total_b = total_f = 0
    for eqn, reg in iter_eqns(jaxpr):
        b, f = eqn_cost(eqn)
        key = "/".join(reg)
        slot = by_region.setdefault(key, {"bytes": 0, "flops": 0, "eqns": 0})
        slot["bytes"] += b
        slot["flops"] += f
        slot["eqns"] += 1
        total_b += b
        total_f += f
    return CostReport(
        total_bytes=total_b,
        total_flops=total_f,
        by_region=dict(sorted(by_region.items())),
    )


# ==========================================================================
# state footprint / HBM budget
# ==========================================================================


def state_footprint(state_fn, *args) -> dict:
    """Size the pytree returned by ``state_fn(*args)`` via ``eval_shape``
    (nothing is materialised).  Returns ``{"total_bytes", "by_field"}``
    with ``by_field`` grouped on the first path component."""
    shapes = jax.eval_shape(state_fn, *args)
    leaves, _ = jax.tree_util.tree_flatten_with_path(shapes)
    by_field: dict = {}
    total = 0
    for path, leaf in leaves:
        b = _aval_bytes(leaf)
        key = jax.tree_util.keystr(path[:1]) or "<root>"
        by_field[key] = by_field.get(key, 0) + b
        total += b
    return {"total_bytes": total, "by_field": dict(sorted(by_field.items()))}


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:8.2f} {unit}"
        n /= 1024
    return f"{n:.2f} GiB"


def footprint_table(footprints: dict, hbm_per_chip: float = HBM_PER_CHIP) -> str:
    """Render ``{label: footprint_dict}`` as the HBM-budget table."""
    lines = [
        f"{'config':<28} {'state bytes':>14} {'% of HBM/chip':>14}",
        "-" * 58,
    ]
    for label, fp in footprints.items():
        total = fp["total_bytes"]
        lines.append(
            f"{label:<28} {_fmt_bytes(total):>14} {100 * total / hbm_per_chip:13.2f}%"
        )
    return "\n".join(lines)


def field_table(fp: dict) -> str:
    lines = [f"{'field':<24} {'bytes':>14}", "-" * 40]
    for field, b in sorted(fp["by_field"].items(), key=lambda kv: -kv[1]):
        lines.append(f"{field:<24} {_fmt_bytes(b):>14}")
    lines.append("-" * 40)
    lines.append(f"{'total':<24} {_fmt_bytes(fp['total_bytes']):>14}")
    return "\n".join(lines)

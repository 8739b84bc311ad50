"""The static auditor itself (src/repro/analysis/).

Planted-violation fixtures: synthetic jaxprs that each break exactly one
pinned contract (scatter in a forbidden region, stray collective, host
callback, degraded clock, baseline drift) must trip the matching rule with
the offending source location.  Plus a green run of the full rule set on a
real engine config, and the retrace sentinel plumbing.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis import jaxpr_audit, retrace, rules
from repro.analysis.jaxpr_audit import (
    CALLBACK_PRIMS, COLLECTIVE_PRIMS, SCATTER_PRIMS, audit, clock_audit)
from repro.core.types import pytree_dataclass

THIS_FILE = "test_analysis.py"


def _one(violations, rule_name):
    """Exactly one violation, from the named rule, located in this file."""
    assert len(violations) == 1, violations
    v = violations[0]
    assert v.rule == rule_name
    located = [s for s in v.sites if THIS_FILE in s]
    assert located, (v.message, v.sites)
    return v


# ==========================================================================
# planted violations
# ==========================================================================

def test_planted_scatter_in_forbidden_region():
    def step(x, idx):
        with jax.named_scope("cheap_core"):
            return x.at[idx].set(0.0)

    jx = jax.make_jaxpr(step)(jnp.zeros(8), jnp.array([1]))
    inv = audit(jx)
    rule = rules.ForbidPrimitive(
        name="cheap-core-scatter-free", prims=SCATTER_PRIMS,
        region="cheap_core")
    v = _one(rule.check("fixture", inv, None), "cheap-core-scatter-free")
    assert "scatter" in v.message
    # the same scatter OUTSIDE the region does not fire
    jx2 = jax.make_jaxpr(lambda x, i: x.at[i].set(0.0))(
        jnp.zeros(8), jnp.array([1]))
    assert rule.check("fixture", audit(jx2), None) == []


def test_planted_stray_psum():
    mesh = jax.make_mesh((1,), ("racks",))

    def step(x):
        return jax.lax.psum(x, "racks")

    fn = jax.shard_map(step, mesh=mesh, in_specs=P("racks"), out_specs=P())
    jx = jax.make_jaxpr(fn)(jnp.zeros(4))
    inv = audit(jx)
    rule = rules.ForbidPrimitive(
        name="no-other-collectives",
        prims=COLLECTIVE_PRIMS - {"all_gather"})
    v = _one(rule.check("fixture", inv, None), "no-other-collectives")
    assert "psum" in v.message


def test_planted_host_callback():
    def step(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2,
            jax.ShapeDtypeStruct((4,), jnp.float32), x)

    jx = jax.make_jaxpr(step)(jnp.zeros(4, jnp.float32))
    inv = audit(jx)
    rule = rules.ForbidPrimitive(
        name="no-host-callbacks", prims=CALLBACK_PRIMS)
    _one(rule.check("fixture", inv, None), "no-host-callbacks")


@pytree_dataclass
class TinyState:
    t: jnp.ndarray      # declared clock leaf (keystr suffix ".t")
    x: jnp.ndarray


def test_planted_degraded_clock():
    tmpl = TinyState(t=jnp.zeros((), jnp.float32),
                     x=jnp.zeros(4, jnp.float32))

    def step(s):
        # clock round-trips through f16: precision silently lost
        bad = s.t.astype(jnp.float16).astype(jnp.float32)
        return TinyState(t=bad + 1.0, x=s.x * 2.0)

    report = clock_audit(jax.make_jaxpr(step)(tmpl), tmpl, jnp.float32)
    violations = rules.DtypePolicy().check_clock("fixture", report)
    v = _one(violations, "clock-dtype-policy")
    assert ".t" in v.message and "downcast" in v.message

    # the identical downcast inside a declared f32_domain scope is an
    # intentional physics exit — no violation
    def step_tagged(s):
        with jax.named_scope(jaxpr_audit.F32_DOMAIN):
            phys = s.t.astype(jnp.float16).astype(jnp.float32)
        return TinyState(t=s.t + 1.0, x=s.x + phys)

    report2 = clock_audit(
        jax.make_jaxpr(step_tagged)(tmpl), tmpl, jnp.float32)
    assert rules.DtypePolicy().check_clock("fixture", report2) == []
    assert report2.degraded_leaves == []


def test_planted_clock_census_violation():
    tmpl = TinyState(t=jnp.zeros((), jnp.float32),
                     x=jnp.zeros(4, jnp.float32))

    def step(s):
        return TinyState(t=s.t.astype(jnp.float16), x=s.x)

    report = clock_audit(jax.make_jaxpr(step)(tmpl), tmpl, jnp.float32)
    bad = rules.DtypePolicy().check_clock("fixture", report)
    # fires as BOTH a census violation and a detected downcast
    assert bad and any("has dtype float16" in v.message for v in bad)
    assert all(v.rule == "clock-dtype-policy" for v in bad)


def test_planted_baseline_drift():
    def v1(x):
        return x * 2.0

    def v2(x):
        return jnp.exp(x) * 2.0  # structural drift: a new primitive

    inv1 = audit(jax.make_jaxpr(v1)(jnp.zeros(4)))
    inv2 = audit(jax.make_jaxpr(v2)(jnp.zeros(4)))
    entry = rules.baseline_entry_from(inv1)
    rule = rules.NoNewPrimitives()
    assert rule.check("fixture", inv1, entry) == []
    v = _one(rule.check("fixture", inv2, entry), "no-new-primitives")
    assert "exp" in v.message
    # an explicit waiver silences exactly that drift
    entry["waivers"] = [{"config": "fixture", "prim": "exp",
                         "reason": "test waiver"}]
    assert rule.check("fixture", inv2, entry) == []
    # missing baseline is itself a violation (forces --update)
    missing = rule.check("fixture", inv2, None)
    assert missing and "run --update" in missing[0].message


def test_exact_count_reports_mismatch_with_sites():
    def step(x, i):
        y = x.at[i].set(1.0)
        return y.at[i].add(2.0)

    inv = audit(jax.make_jaxpr(step)(jnp.zeros(8), jnp.array([1])))
    rule = rules.ExactCount(
        name="one-all-gather-per-sharded-leaf", prims=SCATTER_PRIMS,
        expect=1)
    v = _one(rule.check("fixture", inv, None),
             "one-all-gather-per-sharded-leaf")
    assert "expected exactly 1" in v.message


# ==========================================================================
# walker mechanics
# ==========================================================================

def test_region_provenance_inherits_into_sub_jaxprs():
    def f(x):
        def hot(v):
            with jax.named_scope("cheap_core"):
                return v.at[0].set(v[1] * 3.0)

        return jax.lax.cond(x[0] > 0, hot, lambda v: v, x)

    inv = audit(jax.make_jaxpr(f)(jnp.zeros(4)))
    hits = inv.sites_of(SCATTER_PRIMS, "cheap_core")
    assert hits, inv.histogram()
    assert inv.count(SCATTER_PRIMS, "cheap_core") == \
        inv.count(SCATTER_PRIMS)


def test_clock_taint_through_while_carry():
    tmpl = TinyState(t=jnp.zeros((), jnp.float32),
                     x=jnp.zeros((), jnp.float32))

    def step(s):
        # degradation enters the carry on iteration 1 and must still be
        # seen at the output (fixpoint propagation)
        def body(c):
            t, k = c
            t = jnp.where(k == 1,
                          t.astype(jnp.float16).astype(jnp.float32), t)
            return t, k + 1

        t, _ = jax.lax.while_loop(lambda c: c[1] < 3, body,
                                  (s.t, jnp.int32(0)))
        return TinyState(t=t, x=s.x)

    report = clock_audit(jax.make_jaxpr(step)(tmpl), tmpl, jnp.float32)
    assert [leaf for leaf, _ in report.degraded_leaves] == [".t"]


# ==========================================================================
# retrace sentinel plumbing
# ==========================================================================

def test_retrace_guard_counts_only_inside_guard():
    retrace.note_trace("tag", ("outside",))  # guard off: ignored
    with retrace.retrace_guard() as retraced:
        retrace.note_trace("engine.run", ("k1",))
        retrace.note_trace("engine.run", ("k1",))
        retrace.note_trace("engine.run", ("k2",))
        hits = retraced()
    assert len(hits) == 1 and hits[0]["traces"] == 2
    assert "k1" in hits[0]["key"]
    # guard exited: counting off again
    retrace.note_trace("tag", ("after",))
    with retrace.retrace_guard() as retraced:
        assert retraced() == []


def test_jit_counts_see_each_replica_retrace_and_no_cached_run():
    """watch() counts top-level traces: every run_replicas call builds a
    new jit and traces it; a repeated engine.run hits the jit cache."""
    from repro.core import engine, montecarlo
    from repro.core.jobs import build_jobs, dag_single
    from repro.core.types import SimConfig, SleepPolicy

    cfg = SimConfig(n_servers=2, n_cores=1, max_jobs=4, tasks_per_job=1,
                    sleep_policy=SleepPolicy.ALWAYS_ON, max_events=200)
    arrs = np.asarray([[0.0, 0.1, 0.2, 0.3], [0.05, 0.1, 0.4, 0.5]])
    specs = [dag_single(0.05)] * 4
    retrace.watch()
    state_b, tc = montecarlo.batched_state(cfg, arrs, specs)
    before = retrace.jit_counts()
    for _ in range(2):
        jax.block_until_ready(montecarlo.run_replicas(cfg, state_b, tc))
    d = retrace.jit_delta(before, retrace.jit_counts())
    assert d["trace"]["count"] >= 2 and d["trace"]["seconds"] > 0
    assert d["trace"]["by_fun"].get("run", 0) >= 2
    assert d["compile"]["count"] >= 2

    state, tc = engine.init_state(cfg, build_jobs(cfg, arrs[0], specs))
    jax.block_until_ready(engine.run(state, cfg, tc))
    before = retrace.jit_counts()
    jax.block_until_ready(engine.run(state, cfg, tc))
    d = retrace.jit_delta(before, retrace.jit_counts())
    assert d["trace"]["count"] == 0 and d["compile"]["count"] == 0


# ==========================================================================
# the real engine, green end to end
# ==========================================================================

def test_real_engine_config_passes_full_rule_set():
    """One real config through the exact rule set the CI simlint job
    applies: zero violations against a baseline pinned from itself, and
    the committed repo baseline stays in sync when the jax version
    matches."""
    import json
    import os

    from repro.analysis import matrix, simlint

    case = matrix.build_case("policy_load_balance")
    inv = audit(case.closed_jaxpr)
    report = clock_audit(case.closed_jaxpr, case.state_template,
                         case.time_dtype)
    entry = rules.baseline_entry_from(inv)
    violations = []
    for rule in simlint._rules_for(case, entry, advisory=False):
        violations.extend(rule.check(case.name, inv, entry))
    violations.extend(rules.DtypePolicy().check_clock(case.name, report))
    assert violations == [], "\n".join(v.render() for v in violations)
    # the scatter-free contract is a real budget, not vacuous
    assert inv.count(SCATTER_PRIMS, "cheap_core") > 0
    assert inv.count(COLLECTIVE_PRIMS) == 0
    assert inv.count(CALLBACK_PRIMS) == 0

    path = os.path.join(os.path.dirname(__file__), "..",
                        "ANALYSIS_BASELINE.json")
    committed = rules.load_baseline(path)
    assert "policy_load_balance" in committed["cases"]
    if committed["jax"] == jax.__version__:
        pinned = committed["cases"]["policy_load_balance"]
        assert rules.NoNewPrimitives().check(
            "policy_load_balance", inv, pinned) == [], (
            "committed ANALYSIS_BASELINE.json is stale — rerun "
            "PYTHONPATH=src python -m repro.analysis.simlint "
            "--update ANALYSIS_BASELINE.json")
    assert isinstance(json.dumps(entry), str)  # entry is JSON-clean

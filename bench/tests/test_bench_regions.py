"""Device time per engine region and the program's host spans: the
reduction on a hand-made HLO text and trace, the mapping on a real CPU
compile, and the spans of a real profiled ``run_replicas`` call."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import regions, trace_reduce, tracing

MS = 1_000_000      # ns

HLO = """\
HloModule jit_run, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %sine.1 = f32[8]{0} sine(%param_0), metadata={op_name="jit(run)/while/body/cheap_core/advance/sin" stack_frame_id=1}
  ROOT %add.2 = f32[8]{0} add(%sine.1, %sine.1), metadata={op_name="jit(run)/while/body/cheap_core/power/add" stack_frame_id=2}
}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(run)/vmap()/while/body/vmap(full_step)/telemetry/mul"}
}

%body (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(run)/while/body/cheap_core/power/add" stack_frame_id=2}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1
  ROOT %copy.3 = f32[8]{0} copy(%fusion.2)
}

%cond (q: f32[8]) -> pred[] {
  %q = f32[8]{0} parameter(0)
  ROOT %constant.9 = pred[] constant(false)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %while.4 = f32[8]{0} while(%x), condition=%cond, body=%body, metadata={op_name="jit(run)/while"}
}
"""


def test_region_of_takes_the_innermost_region():
    assert regions.region_of(
        "jit(run)/while/body/cheap_core/advance/telemetry/add") == \
        "telemetry"
    assert regions.region_of(
        "jit(run)/vmap()/while/body/vmap(drain_start)/sort") == \
        "drain_start"
    assert regions.region_of("jit(run)/while/body/cheap_core/add") == \
        regions.OTHER


def test_hlo_regions_maps_device_ops_and_flags_mixed_fusions():
    module, table = regions.hlo_regions(HLO)
    assert module == "jit_run"
    # a fusion by its own metadata; mixed when its body names two regions
    assert table["fusion.1"] == ("power", True)
    # an unnamed fusion takes the one region its body names
    assert table["fusion.2"] == ("telemetry", False)
    assert table["copy.3"] == (regions.OTHER, False)
    assert table["while.4"] == (regions.OTHER, False)
    # fused instructions never run as ops of their own
    assert "sine.1" not in table and "multiply.3" not in table


def test_region_seconds_other_and_mixed_share():
    _, table = regions.hlo_regions(HLO)
    ops = {"/device:TPU:0": [
        ("%while.4 = f32[8]{0} while(%x)", 0, 10 * MS),
        ("%fusion.1 = f32[8]{0} fusion(%p)", 1 * MS, 3 * MS),
        ("fusion.2", 4 * MS, 2 * MS),
        ("copy.3", 6 * MS, 1 * MS),
        ("fusion.1", 8 * MS, 4 * MS),          # runs past the window
    ]}
    r = regions.reduce(ops, table, (0, 10 * MS))
    sec = r["seconds"]
    assert sec["power"] == pytest.approx(5e-3)
    assert sec["telemetry"] == pytest.approx(2e-3)
    assert sec[regions.OTHER] == pytest.approx(1e-3)
    assert sec["advance"] == 0.0 and set(sec) == \
        set(regions.REGIONS) | {regions.OTHER}
    assert r["busy_s"] == pytest.approx(8e-3)
    assert r["leaf_s"] == pytest.approx(sum(sec.values()))
    assert r["mixed_s"] == pytest.approx(5e-3)
    assert r["ops"][0] == ["fusion.1", pytest.approx(5e-3), "power", True]
    # the harness's own reduction of the same ops is untouched by regions
    tr = {"device": ops, "host": [("call", 0, 10 * MS),
                                  ("dispatch", 0, 1 * MS),
                                  ("wait", 1 * MS, 9 * MS)]}
    assert trace_reduce.reduce(tr)["busy_s"] == pytest.approx(r["busy_s"])


def test_real_compile_maps_each_instruction_to_its_scope():
    def f(x):
        with jax.named_scope("advance"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("power"):
            z = y @ x
        return z

    x = jnp.ones((16, 16))
    text = jax.jit(f).lower(x).compile().as_text()
    module, table = regions.hlo_regions(text)
    assert module.startswith("jit_f")
    found = {r for r, _ in table.values()}
    assert {"advance", "power"} <= found
    # every device op that names a scope maps to that scope
    for line in text.splitlines():
        head = line.split(" = ", 1)[0].split()
        if not head or head[-1].lstrip("%") not in table:
            continue
        region, _ = table[head[-1].lstrip("%")]
        for scope in ("advance", "power"):
            if f'/{scope}/' in line.split("metadata=")[-1]:
                assert region == scope, line


def test_run_replicas_spans_nest_inside_the_dispatch_span(tmp_path):
    from repro.core import montecarlo
    from repro.core.jobs import dag_single
    from repro.core.types import SimConfig, SleepPolicy

    cfg = SimConfig(n_servers=2, n_cores=1, max_jobs=4, tasks_per_job=1,
                    sleep_policy=SleepPolicy.ALWAYS_ON, max_events=200)
    arrs = np.asarray([[0.0, 0.1, 0.2, 0.3], [0.05, 0.1, 0.4, 0.5]])
    state_b, tc = montecarlo.batched_state(
        cfg, arrs, [dag_single(0.05)] * 4)
    out, path = tracing.capture(
        lambda: montecarlo.run_replicas(cfg, state_b, tc),
        tmp_path / "trace")
    assert bool(np.asarray(out.done).all())
    # the harness sees its own three spans, as before
    host = tracing.extract(path)["host"]
    assert [n for n, _, _ in host] == ["call", "dispatch", "wait"]
    (_, c0, cd), (_, d0, dd), _ = host
    spans = regions.extract(path)["program"]
    assert [n for n, _, _ in spans] == list(regions.PROGRAM_SPANS)
    end = d0
    for _, s, d in spans:
        assert end <= s and s + d <= d0 + dd      # in order, in dispatch
        end = s + d
    secs = regions.span_seconds(spans, (c0, c0 + cd))
    assert list(secs) == list(regions.PROGRAM_SPANS)
    assert all(v > 0 for v in secs.values())

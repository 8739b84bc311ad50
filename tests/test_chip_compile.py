"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed next to jax, so a chip that is described
(``topologies.get_topology_desc``) compiles what a real one would, and
refuses what a real one would refuse: block shapes off the (8, 128) tiling,
integer indexing of vectors, programs that do not fit.  Nothing runs, so
these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels
from repro.core import engine, telemetry
from repro.core.jobs import build_jobs, dag_single
from repro.core.types import SimConfig, TelemetryConfig
from repro.kernels.dcsim_step import dcsim_advance
from repro.kernels.telemetry_bin import telemetry_accum


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without the chip: keep it out of the cache
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_on)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


@pytest.mark.parametrize("n", [512, 20480])
def test_dcsim_advance_compiles(one_chip, n):
    c = 4

    def advance(cb, st, en, bs, t, tn, tab, wake, since, tau, thr):
        return dcsim_advance(cb, st, en, bs, t, tn, tab, 13.0, 2.0, wake,
                             since, tau, thr, throttle_power_scale=0.6)

    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in [
        ((n, c), f32), ((n,), jnp.int32), ((n,), f32), ((n,), f32),
        ((), f32), ((), f32), ((6,), f32), ((n,), f32), ((n,), f32),
        ((n,), f32), ((n,), jnp.bool_)]]
    hlo = jax.jit(advance).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_telemetry_accum_compiles(one_chip):
    """At the widths the 20480-server, 100,000-job farm bins with."""
    tcfg = TelemetryConfig()
    J = 100_000
    B, K = tcfg.n_bins, telemetry.WIN_COLS

    def accum(jv, jw, tv, tw, jh, th, win, widx, wvals):
        return telemetry_accum(jv, jw, tv, tw, jh, th, win, widx, wvals,
                               tcfg.lat_lo, tcfg.lat_hi)

    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in [
        ((J,), f32), ((J,), f32), ((J,), f32), ((J,), f32), ((B,), f32),
        ((B,), f32), ((1, K), f32), ((), jnp.int32), ((K,), f32)]]
    hlo = jax.jit(accum).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def _engine_args(n_servers, n_jobs, use_kernel, sharding):
    cfg = SimConfig(n_servers=n_servers, n_cores=4, max_jobs=n_jobs,
                    use_kernel=use_kernel,
                    telemetry=TelemetryConfig(use_kernel=use_kernel))
    arr = np.cumsum(np.full(n_jobs, 1e-3))
    jt = build_jobs(cfg, arr, [dag_single(0.1)] * n_jobs)
    state, tc = engine.init_state(cfg, jt)
    return _shapes(state, sharding), cfg, _shapes(tc, sharding)


def test_engine_run_compiles(one_chip):
    """The whole run-to-completion loop, jnp path, 20480 servers."""
    state, cfg, tc = _engine_args(20480, 600, False, one_chip)
    compiled = engine.run.lower(state, cfg, tc).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_engine_run_with_kernels_compiles(one_chip, monkeypatch):
    """``use_kernel`` on both kernels puts them in the compiled engine.
    The engine asks the backend whether to interpret; here the backend is
    the CPU, so the test tells it that it compiles."""
    monkeypatch.setattr(repro.kernels, "interpret_mode", lambda: False)
    state, cfg, tc = _engine_args(4096, 600, True, one_chip)
    hlo = engine.run.lower(state, cfg, tc).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 2


def test_interpret_mode_is_cpu_only(monkeypatch):
    assert repro.kernels.interpret_mode() is True     # tier-1 runs on cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert repro.kernels.interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        repro.kernels.interpret_mode()


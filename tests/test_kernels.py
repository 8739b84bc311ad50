"""Per-kernel allclose validation against the pure-jnp oracles in
kernels/ref.py, swept over shapes/dtypes (interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    # optional dev dependency (pyproject [dev]); without it the
    # property-based sweeps fall back to fixed parametrized examples
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                       # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.dcsim_step import dcsim_advance, INF
from repro.kernels.telemetry_bin import telemetry_accum


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,Sq,Skv,hd,causal,window,cap", [
    (2, 4, 2, 256, 256, 64, True, 0, 0.0),
    (1, 4, 4, 128, 128, 128, True, 0, 50.0),     # softcap (gemma2)
    (2, 2, 1, 256, 256, 64, True, 64, 0.0),      # sliding window
    (1, 8, 2, 384, 384, 64, True, 0, 0.0),       # non-multiple of block
    (1, 2, 2, 128, 256, 32, False, 0, 0.0),      # cross attention
])
def test_flash_attention_matches_ref(B, H, KV, Sq, Skv, hd, causal, window,
                                     cap, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, KV, Skv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, KV, Skv, hd), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          interpret=True)
    exp = ref.mha_reference(q, k, v, causal=causal, window=window,
                            softcap=cap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.float32(out), np.float32(exp), atol=tol,
                               rtol=tol)


def test_flash_attention_matches_model_attend():
    """The kernel and the model's streaming attend agree (same oracle)."""
    from repro.models.layers import attend
    ks = jax.random.split(jax.random.key(1), 3)
    B, H, KV, S, hd = 2, 4, 2, 192, 64
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    a = attend(q, k, v, causal=True, chunk=64)
    f = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=True,
                        interpret=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.float32(a), np.float32(f), atol=2e-5,
                               rtol=2e-5)


# --------------------------------------------------------------------------
# ssm scan
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Dss,N,block_d,chunk_t", [
    (2, 64, 256, 16, 128, 16),
    (1, 128, 512, 8, 256, 32),
    (3, 32, 128, 16, 128, 8),
])
def test_ssm_scan_matches_ref(B, S, Dss, N, block_d, chunk_t, dtype):
    ks = jax.random.split(jax.random.key(2), 4)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, Dss))) * 0.1
    Bm = jax.random.normal(ks[1], (B, S, N))
    Cm = jax.random.normal(ks[2], (B, S, N))
    x = jax.random.normal(ks[3], (B, S, Dss))
    A = -jnp.exp(jax.random.normal(jax.random.key(5), (Dss, N)) * 0.3)
    dt, Bm, Cm, x = (a.astype(dtype) for a in (dt, Bm, Cm, x))
    y = ssm_scan(dt, Bm, Cm, x, A, block_d=block_d, chunk_t=chunk_t,
                 interpret=True)
    y_ref, _ = ref.ssm_scan_reference(dt, Bm, Cm, x, A)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.float32(y), np.float32(y_ref), atol=tol,
                               rtol=tol)


# --------------------------------------------------------------------------
# dcsim advance
# --------------------------------------------------------------------------

def _check_dcsim_advance(n, c, seed, block_n=4096):
    rng = np.random.default_rng(seed)
    t = np.float32(rng.uniform(0, 10))
    t_next = np.float32(t + rng.uniform(0, 1))
    busy = np.where(rng.random((n, c)) < 0.5,
                    rng.uniform(t, t + 2, (n, c)).astype(np.float32),
                    np.float32(INF))
    state = rng.integers(0, 6, n).astype(np.int32)
    energy = rng.uniform(0, 100, n).astype(np.float32)
    bsec = rng.uniform(0, 10, n).astype(np.float32)
    wake = np.where(state == 5, rng.uniform(t, t + 3, n),
                    np.float32(INF)).astype(np.float32)
    isince = rng.uniform(0, t, n).astype(np.float32)
    tau = np.where(rng.random(n) < 0.5, rng.uniform(0.1, 2.0, n),
                   np.float32(INF)).astype(np.float32)
    ptab = jnp.asarray([65.0, 65.0, 15.0, 9.0, 0.0, 145.0], jnp.float32)
    # thermally throttled servers accrue scaled active-core power
    throttled = (rng.random(n) < 0.3).astype(np.int32)
    scale = 0.6

    got = dcsim_advance(jnp.asarray(busy), jnp.asarray(state),
                        jnp.asarray(energy), jnp.asarray(bsec),
                        t, t_next, ptab, 13.0, 2.0,
                        jnp.asarray(wake), jnp.asarray(isince),
                        jnp.asarray(tau), jnp.asarray(throttled),
                        throttle_power_scale=scale, block_n=block_n,
                        interpret=True)
    exp = ref.dcsim_advance_reference(
        jnp.asarray(busy), jnp.asarray(state), jnp.asarray(energy),
        jnp.asarray(bsec), jnp.asarray(t), jnp.asarray(t_next), ptab,
        13.0, 2.0, jnp.asarray(wake), jnp.asarray(isince),
        jnp.asarray(tau), jnp.asarray(throttled),
        throttle_power_scale=scale)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(np.float32(g), np.float32(e),
                                   rtol=1e-5, atol=1e-5)


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(n=st.integers(3, 300), c=st.integers(1, 4),
           seed=st.integers(0, 999))
    def test_dcsim_advance_matches_ref(n, c, seed):
        _check_dcsim_advance(n, c, seed)
else:
    @pytest.mark.parametrize("n,c,seed", [
        (3, 1, 0), (17, 2, 5), (120, 3, 7), (256, 4, 11), (300, 1, 42),
    ])
    def test_dcsim_advance_matches_ref(n, c, seed):
        _check_dcsim_advance(n, c, seed)


def test_dcsim_advance_multi_block():
    """A farm cut into several lane-dense blocks, the last one padded."""
    _check_dcsim_advance(2500, 4, 3, block_n=1024)


# --------------------------------------------------------------------------
# telemetry accumulation
# --------------------------------------------------------------------------

@pytest.mark.parametrize("J,M,B,W,block", [
    (64, 64, 32, 16, 64),        # single block
    (200, 700, 64, 32, 256),     # uneven streams, padding
    (1024, 100, 128, 8, 512),    # job stream longer than task stream
])
def test_telemetry_accum_matches_ref(J, M, B, W, block):
    K = 12
    rng = np.random.default_rng(B + J)
    jv = jnp.asarray(rng.uniform(1e-6, 50.0, J), jnp.float32)
    jw = jnp.asarray(rng.random(J) < 0.4, jnp.float32)
    tv = jnp.asarray(rng.uniform(1e-6, 50.0, M), jnp.float32)
    tw = jnp.asarray(rng.random(M) < 0.6, jnp.float32)
    jh = jnp.asarray(rng.uniform(0, 5, B), jnp.float32)
    th = jnp.asarray(rng.uniform(0, 5, B), jnp.float32)
    win = jnp.asarray(rng.uniform(0, 1, (W, K)), jnp.float32)
    widx = jnp.asarray(rng.integers(0, W), jnp.int32)
    wvals = jnp.asarray(rng.uniform(0, 1, K), jnp.float32)
    lo, hi = 1e-5, 1e3

    got = telemetry_accum(jv, jw, tv, tw, jh, th, win, widx, wvals,
                          lo, hi, block=block, interpret=True)
    exp = ref.telemetry_accum_reference(jv, jw, tv, tw, jh, th, win,
                                        widx, wvals, lo, hi)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(np.float32(g), np.float32(e),
                                   rtol=1e-5, atol=1e-5)


def test_telemetry_hist_mass_and_range():
    """Every unit of weight lands in exactly one bin; out-of-range values
    clamp into the edge bins."""
    B, K, W = 16, 12, 4
    vals = jnp.asarray([1e-9, 1e-5, 0.5, 1e3, 1e7], jnp.float32)
    wts = jnp.ones_like(vals)
    z = jnp.zeros((1,), jnp.float32)
    jh, th, _ = ref.telemetry_accum_reference(
        vals, wts, z, z, jnp.zeros((B,), jnp.float32),
        jnp.zeros((B,), jnp.float32), jnp.zeros((W, K), jnp.float32),
        jnp.asarray(0, jnp.int32), jnp.zeros((K,), jnp.float32),
        1e-5, 1e3)
    assert float(jh.sum()) == pytest.approx(5.0)
    assert float(jh[0]) >= 2.0          # 1e-9 and 1e-5 clamp to bin 0
    assert float(jh[-1]) >= 2.0         # 1e3 and 1e7 clamp to bin B-1

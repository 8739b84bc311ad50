"""Pallas TPU kernels for the perf-critical hot spots, each with a pure-jnp
oracle (ref.py):

  flash_attention  prefill/train attention (causal, sliding-window,
                   softcap, GQA) — streaming softmax, VMEM-resident scores
  ssm_scan         selective-SSM recurrence (hymba) — state in VMEM
                   scratch across sequential time chunks
  dcsim_step       the simulator's fused farm-advance (min + energy +
                   completion) — the TPU analogue of the event-queue pop
  telemetry_bin    fused telemetry accumulation (latency-histogram binning
                   + time-series window bucketing in one VMEM pass)
"""
import jax

from . import ref, telemetry_bin  # noqa: F401


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode on this backend: yes on
    ``cpu`` (where the tests run), no on ``tpu`` (compiled by Mosaic).  Any
    other backend raises rather than quietly interpreting."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on tpu or interpreted on cpu; the "
        f"{backend!r} backend is neither")

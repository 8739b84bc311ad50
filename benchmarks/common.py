"""Shared helpers for the paper-table benchmarks."""
from __future__ import annotations

import sys
import time

import numpy as np

sys.path.insert(0, "src")  # allow `python -m benchmarks.run` from repo root

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import farm as farm_mod          # noqa: E402
from repro.core import workload                  # noqa: E402
from repro.core.jobs import dag_single           # noqa: E402
from repro.core.types import SimConfig           # noqa: E402

enable_compile_cache()

# paper workload models (§IV-B): web search ~5ms, web serving ~120ms
WEB_SEARCH_SVC = 0.005
WEB_SERVING_SVC = 0.120


def make_jobs(rng, n_jobs, mean_svc):
    return [dag_single(rng.exponential(mean_svc)) for _ in range(n_jobs)]


def wiki_arrivals(n_jobs, rho, cfg, mean_svc, seed=0):
    lam = workload.utilization_to_rate(rho, mean_svc, cfg.n_servers,
                                       cfg.n_cores)
    return workload.wiki_like_trace(n_jobs, lam, period=60.0, swing=0.5,
                                    seed=seed)


def poisson_arrivals_for(n_jobs, rho, cfg, mean_svc, seed=0):
    lam = workload.utilization_to_rate(rho, mean_svc, cfg.n_servers,
                                       cfg.n_cores)
    return workload.poisson_arrivals(lam, n_jobs, seed=seed)


def timed(fn, *a, **kw):
    t0 = time.time()
    out = fn(*a, **kw)
    return out, time.time() - t0


def row(name, us_per_call, derived=""):
    print(f"{name},{us_per_call:.1f},{derived}")

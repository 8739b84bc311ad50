"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<name>.json`` and draws its arrivals and service times
from ``--seed``.

Arrivals are a Poisson process at the rate that puts the farm's cores at
the stated utilization, rho = lambda * mean_service / (servers * cores)
(HolDCSim §III-D); service times are exponential with the stated mean
(web serving, §IV-B: 120 ms).  A mix is either one farm (``utilization``
under ``arrivals``) or a replica sweep (``grid`` of ``tau_s`` x
``utilization``, ``seeds_per_point`` replicas per point, each with its own
arrival stream; one service draw is shared by every replica, because a
replica batch takes one job list).

A farm mix may fix its draw and leave ``--seed`` the order alone
(``order``: ``draw_seed``, ``block``): the gaps between arrivals and the
service times are drawn once from ``draw_seed``, and each seed shuffles
them within consecutive blocks of ``block`` jobs.  Every seed then offers
the same sizes and arrivals, block by block, in another order, so a
fixed slice of events holds about the same work whatever the seed.
"""
from __future__ import annotations

import numpy as np

# seed streams: one per purpose, so adding one never shifts another
_ARRIVALS, _SERVICE, _ORDER = 1, 2, 3


def rng(seed: int, *keys: int) -> np.random.Generator:
    """Independent stream ``keys`` of ``seed`` (any whole number)."""
    return np.random.default_rng([int(seed) % 2**63, *keys])


def arrival_rate(rho: float, mean_service: float, n_servers: int,
                 n_cores: int) -> float:
    return rho * n_servers * n_cores / mean_service


def poisson_arrivals(lam: float, n_jobs: int, gen) -> np.ndarray:
    return np.cumsum(gen.exponential(1.0 / lam, size=n_jobs))


def service_times(traffic: dict, n_jobs: int, seed: int) -> np.ndarray:
    svc = traffic["service"]
    if svc["dist"] != "exponential":
        raise ValueError(f"unknown service distribution {svc['dist']!r}")
    return rng(seed, _SERVICE).exponential(svc["mean_s"], n_jobs)


def block_shuffle(values: np.ndarray, block: int, gen) -> np.ndarray:
    """``values`` with each consecutive run of ``block`` shuffled."""
    values = np.asarray(values)
    n = len(values)
    return values[np.lexsort((gen.random(n), np.arange(n) // block))]


def _check_arrivals(traffic: dict) -> None:
    if traffic["arrivals"]["process"] != "poisson":
        raise ValueError(
            f"unknown arrival process {traffic['arrivals']['process']!r}")


def farm(traffic: dict, sim: dict, tau_s: float, seed: int):
    """One farm: (arrivals (J,), service (J,), tau (N,)) in seconds."""
    _check_arrivals(traffic)
    J = int(traffic["jobs"])
    lam = arrival_rate(traffic["arrivals"]["utilization"],
                       traffic["service"]["mean_s"], sim["n_servers"],
                       sim["n_cores"])
    tau = np.full(sim["n_servers"], tau_s)
    order = traffic.get("order")
    if order is None:
        arr = poisson_arrivals(lam, J, rng(seed, _ARRIVALS, 0))
        return arr, service_times(traffic, J, seed), tau
    draw, block = int(order["draw_seed"]), int(order["block"])
    gaps = rng(draw, _ARRIVALS, 0).exponential(1.0 / lam, J)
    svc = service_times(traffic, J, draw)
    gaps = block_shuffle(gaps, block, rng(seed, _ORDER, 0))
    svc = block_shuffle(svc, block, rng(seed, _ORDER, 1))
    return np.cumsum(gaps), svc, tau


def sweep(traffic: dict, sim: dict, seed: int):
    """A replica sweep: (arrivals (R, J), service (J,), tau (R,),
    utilization (R,)), replicas ordered point-major: for each tau, for
    each utilization, ``seeds_per_point`` arrival streams."""
    _check_arrivals(traffic)
    J = int(traffic["jobs"])
    k = int(traffic["seeds_per_point"])
    grid = traffic["grid"]
    mean = traffic["service"]["mean_s"]
    arrs, taus, rhos = [], [], []
    for tau in grid["tau_s"]:
        for rho in grid["utilization"]:
            lam = arrival_rate(rho, mean, sim["n_servers"], sim["n_cores"])
            for _ in range(k):
                r = len(arrs)
                arrs.append(poisson_arrivals(lam, J,
                                             rng(seed, _ARRIVALS, r)))
                taus.append(tau)
                rhos.append(rho)
    return (np.stack(arrs), service_times(traffic, J, seed),
            np.asarray(taus), np.asarray(rhos))

"""The comparison that decides ``correct``: the program's state after the
timed calls against the plain reference's, number by number, each against
its own limit (``bench/limits/<cell>.json``, whose names are the numbers
judged).

Every number a farm's check computes (``gaps``, ``NUMBERS``) is a gap
that reads 0 where the two agree exactly:

- ``sim_time_gap_s``: simulated time reached, seconds;
- ``placement_mismatch``: jobs placed on another server;
- ``finish_gap_s``: widest gap of a job's finish time, seconds (a job
  finished on one side only reads about 1e30);
- ``energy_gap``: widest relative gap of a server's energy;
- ``residency_gap``: widest gap of a server's seconds in a power state,
  as a share of the simulated time;
- ``wake_mismatch``: servers whose wake count differs;
- ``hist_misbinned``: bin moves that turn one side's job and task
  latency histograms into the other's (each pair's earth mover's
  distance in bins, summed): a latency one bin off counts 1 per
  histogram, and every finished job one bin off counts its whole count;
- ``window_gap``: widest gap of a telemetry window cell (occupancy,
  jobs in flight, awake servers, queue depth, power, servers per state),
  as a share of the larger of the two sides' column totals;
- ``counter_mismatch``: events, jobs admitted, drops and tail-latency
  violations that differ, summed.
"""
from __future__ import annotations

import numpy as np

INF = 1.0e30
NUMBERS = ("sim_time_gap_s", "placement_mismatch", "finish_gap_s",
           "energy_gap", "residency_gap", "wake_mismatch", "hist_misbinned",
           "window_gap", "counter_mismatch")


def gaps(prog: dict, ref: dict) -> dict:
    """Each compared number for one farm (see the module docstring)."""
    t = max(ref["t"], 1e-30)
    fp = np.where(prog["job_finish"] < INF / 2, prog["job_finish"], INF)
    fr = np.where(ref["job_finish"] < INF / 2, ref["job_finish"], INF)
    cols = ref["win"].shape[1]
    wp, wr = prog["win"][:, :cols], ref["win"]
    col_tot = np.maximum(np.maximum(np.abs(wr).sum(axis=0),
                                    np.abs(wp).sum(axis=0)), 1e-30)
    return {
        "sim_time_gap_s": abs(prog["t"] - ref["t"]),
        "placement_mismatch": int((prog["server"] != ref["server"]).sum()),
        "finish_gap_s": float(np.abs(fp - fr).max(initial=0.0)),
        "energy_gap": float((np.abs(prog["energy"] - ref["energy"])
                             / np.maximum(ref["energy"], 1e-30)).max()),
        "residency_gap": float(np.abs(prog["residency"]
                                      - ref["residency"]).max() / t),
        "wake_mismatch": int((prog["wake_count"]
                              != ref["wake_count"]).sum()),
        "hist_misbinned": bin_moves(prog["job_hist"], ref["job_hist"])
        + bin_moves(prog["task_hist"], ref["task_hist"]),
        "window_gap": float((np.abs(wp - wr) / col_tot).max()),
        "counter_mismatch": int(
            abs(prog["events"] - ref["events"])
            + abs(prog["arr_ptr"] - ref["arr_ptr"])
            + abs(prog["dropped"] - ref["dropped"])
            + abs(prog["tail_viol"] - ref["tail_viol"])),
    }


def bin_moves(a, b) -> float:
    """Earth mover's distance of two histograms over their bins, in
    bins: the counts that cross each bin edge, summed over the edges."""
    return float(np.abs(np.cumsum(np.asarray(a, float)
                                  - np.asarray(b, float))).sum())


def worst(readings: list) -> dict:
    """Each number's largest reading over several farms (a sweep's
    checked replicas)."""
    return {k: max(r[k] for r in readings) for k in readings[0]}


def judge(numbers: dict, limits: dict) -> tuple:
    """(all within limits, {name: {"value", "limit"}}) for every name of
    ``limits`` (the cell's ``bench/limits/<cell>.json``), in its order.
    A number with no limit, or a limit with no number, is an error: what
    the check computes is all judged, and all that is judged is
    computed."""
    unjudged = [k for k in numbers if k not in limits]
    missing = [k for k in limits if k not in numbers]
    if unjudged or missing:
        raise KeyError(f"compared numbers without a limit: {unjudged}; "
                       f"limits without a number: {missing}")
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(numbers[k] <= limits[k] for k in limits)
    return ok, checks

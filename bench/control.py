"""Readings of a cell's control and of its histogram fault, each put in
the program's place and compared as the program is:

- ``control``: the plain reference with its times in bfloat16, the
  precision below the float32 that the configurations state;
- ``hist_shift``: the reference as it is, but with every finished job's
  latency binned one bin higher in both telemetry histograms, so that the
  same jobs finish at the same times and only the binning is wrong.

The limits in ``bench/limits/<cell>.json`` lie below the smallest of
these readings where they fail.

    python bench/control.py --workload <cell> --seeds 1 2 3

Prints one JSON line per seed with each compared number.  Host-only: it
runs no program and needs no chip.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def hist_shift(result: dict) -> dict:
    """``result`` with each latency histogram moved up one bin (the top
    bin keeps what it held)."""
    out = dict(result)
    for key in ("job_hist", "task_hist"):
        h = np.asarray(result[key], float)
        moved = np.zeros_like(h)
        moved[1:] = h[:-1]
        moved[-1] += h[-1]
        out[key] = moved
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import loader
    cell = loader.cell(args.workload)
    entry = loader.entry(cell)
    for seed in args.seeds:
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "control": entry.control(cell, seed, "bfloat16"),
            "hist_shift": entry.control(cell, seed, alter=hist_shift)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

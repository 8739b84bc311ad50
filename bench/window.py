"""The measured window: whole calls of the program's entry, back to back,
each ended by ``block_until_ready``, until ``seconds`` have passed at a
call boundary.  The rate is every event of those calls over the time from
the first call's start to the last call's end: no call is split, no call
is left out, and the host's work between calls is in the time."""
from __future__ import annotations

import dataclasses
import time

import jax


@dataclasses.dataclass
class Call:
    start: float             # host clock at dispatch (s)
    end: float               # host clock once the result is ready (s)
    counts: dict             # the entry's counters for this call


def measure(call, counts, seconds: float):
    """(the calls, the last call's output)."""
    calls = []
    t0 = time.perf_counter()
    while True:
        c0 = time.perf_counter()
        out = jax.block_until_ready(call())
        c1 = time.perf_counter()
        calls.append(Call(c0, c1, counts(out)))
        if c1 - t0 >= seconds:
            return calls, out
        del out


def wall_s(calls: list) -> float:
    return calls[-1].end - calls[0].start


def total(calls: list, key: str) -> float:
    return sum(c.counts[key] for c in calls)


def rate(calls: list, key: str = "events") -> float:
    """``key`` summed over every call, per second of the window."""
    return total(calls, key) / wall_s(calls)

"""Wall milliseconds per loop iteration of a replica sweep over the
window: the window's time over its calls' iterations, each call's being
its slowest replica's macro-steps, since the batch loops until every
replica is done."""
from bench import window

LAYER = "event loop"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "replica_events_per_s"


def read(run):
    calls = run["calls"]
    return window.wall_s(calls) * 1e3 / window.total(calls, "steps")

"""Wall milliseconds per macro-step of a farm over the window: the
window's time over the loop iterations its calls ran."""
from bench import window

LAYER = "event loop"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "events_per_s"


def read(run):
    calls = run["calls"]
    return window.wall_s(calls) * 1e3 / window.total(calls, "steps")

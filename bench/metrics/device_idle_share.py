"""Share of one traced farm call in which no operation ran on the
device: 1 - (union of the device's op intervals) / (the call's host
span)."""
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "events_per_s"


def read(run):
    tr = run["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

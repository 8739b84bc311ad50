"""Device milliseconds per macro-step of a traced farm call in the
engine's ``completions`` region (``core/engine.py``): the leaf time of
its device ops, mapped to regions through the compiled program's HLO
text (``bench/regions.py``)."""
LAYER = "engine regions"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "events_per_s"


def read(run):
    regions = run["regions"]
    return regions["completions"] if regions else None

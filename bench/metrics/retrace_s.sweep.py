"""Host seconds of a traced replica sweep call in which the program
builds its jit anew: the program spans ``run_replicas.trace``,
``.lower`` and ``.compile`` (``core/montecarlo.py``) inside the
harness's ``call`` span, on the profiler's clock."""
from bench.regions import RETRACE_SPANS

LAYER = "replica batch"
UNIT = "s"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "replica_events_per_s"


def read(run):
    spans = run["spans"]
    if not spans or not any(n in spans for n in RETRACE_SPANS):
        return None
    return sum(spans.get(n, 0.0) for n in RETRACE_SPANS)

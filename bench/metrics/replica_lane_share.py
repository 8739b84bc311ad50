"""Share of the replica batch's vmapped loop iterations that do work:
the replicas' own macro-steps over replicas times the slowest replica's,
from the program's ``state.steps``."""
LAYER = "replica batch"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "replica_events_per_s"


def read(run):
    c = run["calls"][-1].counts
    if "replica_steps" not in c:
        return None
    steps = c["replica_steps"]
    return 100.0 * sum(steps) / (len(steps) * max(steps))

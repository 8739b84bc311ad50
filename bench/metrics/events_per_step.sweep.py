"""Events retired per macro-step in a replica sweep, from the program's
counters (``state.events`` and ``state.steps``), summed over replicas."""
LAYER = "event loop"
UNIT = "events/step"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "replica_events_per_s"


def read(run):
    c = run["calls"][-1].counts
    return c["events"] / sum(c["replica_steps"])

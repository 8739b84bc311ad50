"""Events retired per macro-step of a farm, from the program's counters
(``state.events`` and ``state.steps``)."""
LAYER = "event loop"
UNIT = "events/step"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "events_per_s"


def read(run):
    c = run["calls"][-1].counts
    return c["events"] / c["steps"]

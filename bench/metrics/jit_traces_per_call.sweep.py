"""Top-level jaxpr traces per call of a replica sweep's window, from the
program's jit counters (``analysis.retrace.watch``, on in a traced
run): at least 1 while ``run_replicas`` builds a jit per call."""
LAYER = "replica batch"
UNIT = "traces/call"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "replica_events_per_s"


def read(run):
    jit = run["jit_window"]
    if jit is None:
        return None
    return jit["trace"]["count"] / len(run["calls"])

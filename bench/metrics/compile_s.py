"""Host seconds to compile the timed entry: around ``.lower().compile()``
of each program a farm cell runs, or a sweep's first call minus its first
warm call (``run_replicas`` builds its jit inside the call)."""
LAYER = "host set-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run["compile_s"]

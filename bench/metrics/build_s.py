"""Host seconds to build the program's state from the traffic: the job
table (``jobs.build_jobs``, a Python loop per job), ``engine.init_state``
and, in a sweep, the replica batch (``montecarlo.batched_state``)."""
LAYER = "host set-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run["build_s"]

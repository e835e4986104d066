"""Calls of the event-loop programs per unit of work, counted from the
host's launch events in the trace."""
NAME = "launches_per_unit"
UNIT = "launches"
LAYER = "runner and dispatch"
MOVES = "sim_req_per_s"
LOOPS = ("_sweep_metrics", "_cluster_metrics")


def read(t):
    n = t.launches(LOOPS)
    return n / t.units if n else None

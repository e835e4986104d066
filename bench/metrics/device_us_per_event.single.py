"""Device time of the single-node event loop per simulated event: the
device's busy time in the traced unit over the unit's loop steps
(`n_events`), in a unit whose only loop program is `_sweep_metrics`.
The other programs a unit runs are conversions of microseconds."""
NAME = "device_us_per_event.single"
UNIT = "us/event"
LAYER = "single-node event loop"
MOVES = "sim_req_per_s"


def read(t):
    if (not t.launches(("_sweep_metrics",)) or t.launches(
            ("_cluster_metrics",)) or not t.steps or not t.busy_s):
        return None
    return t.busy_s / t.steps * 1e6

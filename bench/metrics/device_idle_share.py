"""Share of the traced unit in which no program ran on the device:
1 - the union of the device's busy intervals over the unit's span."""
NAME = "device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "sim_req_per_s"


def read(t):
    if not t.window_s or not t.modules:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)

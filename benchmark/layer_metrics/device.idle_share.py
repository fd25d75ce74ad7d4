"""Layer metric ``device.idle_share``: the share of the traced requests' time (submit
start to result synced) in which no operation ran on the device."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    return None if run.trace is None else run.trace["idle_share"]

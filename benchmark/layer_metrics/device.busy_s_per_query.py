"""Layer metric ``device.busy_s_per_query``: seconds a request kept the device busy:
the union of the device operations inside the traced requests' intervals,
over their number."""

LAYER = "operators"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    if run.trace is None or not run.trace["requests"]:
        return None
    return run.trace["busy_s"] / run.trace["requests"]

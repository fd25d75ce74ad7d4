"""Layer metric ``region.device_s_per_query``: device time of the fused regions a
request: the device's modules named ``jit_region_<plan>`` inside the traced
requests, over their number. Stands beside ``device.busy_s_per_query``,
which is this plus ``dispatch.pad_device_s_per_query``."""

LAYER = "operators"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    reduced = span_reduce.device(run)
    if reduced is None or not reduced["region_modules"]:
        return None
    return reduced["region_s"] / reduced["requests"]

"""Layer metric ``dispatch.pad_device_s_per_query``: device time a request outside
its fused region: the device's modules of every other name inside the
traced requests (the eager ops of the padded copy and its masks, and the
trim of the result), over their number. Not reported where no module is
named ``jit_region_*``: the two cannot be told apart there."""

LAYER = "dispatch"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    reduced = span_reduce.device(run)
    if reduced is None or not reduced["region_modules"]:
        return None
    return reduced["other_s"] / reduced["requests"]

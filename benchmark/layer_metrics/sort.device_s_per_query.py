"""Layer metric ``sort.device_s_per_query``: device time a request under the scope
of the plan's ``Sort`` nodes: the ordered result."""

LAYER = "operators"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import scope_reduce

    return scope_reduce.kind_seconds_per_query(run, scope_reduce.SORTS)

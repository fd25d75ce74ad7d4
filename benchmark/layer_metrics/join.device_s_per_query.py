"""Layer metric ``join.device_s_per_query``: device time a request under the scopes
of the plan's join nodes (``Join``, ``DensePkJoin``: ``pk1``, ``pk2`` in
planned q3), from the trace's operations inside the traced requests."""

LAYER = "operators"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import scope_reduce

    return scope_reduce.kind_seconds_per_query(run, scope_reduce.JOINS)

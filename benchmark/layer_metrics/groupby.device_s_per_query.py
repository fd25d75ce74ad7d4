"""Layer metric ``groupby.device_s_per_query``: device time a request under the
scopes of the plan's ``GroupBy`` nodes: the sort path that planned q3 and
general q1 share (sort, boundary searches, prefix sums, gathers)."""

LAYER = "operators"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import scope_reduce

    return scope_reduce.kind_seconds_per_query(run, scope_reduce.GROUPBYS)

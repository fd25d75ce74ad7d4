"""Layer metric ``filter.device_s_per_query``: device time a request under the
scopes of the plan's ``Filter`` nodes (``where`` in planned q13): the
predicate, from the trace's operations inside the traced requests: the
``NOT LIKE`` over the comment column's bytes. Not listed for q6's cell: XLA
fuses its numeric predicate into a fusion named after the sum's node, and
what stays under the filter's scope is the two counts alone (0.0004 s of a
0.013 s region, my chip run, PR 38): it would read wrongly there."""

LAYER = "operators"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import scope_reduce

    return scope_reduce.kind_seconds_per_query(run, ("Filter",))

"""Layer metric ``groupby.groups_per_query``: groups a request's sort-path groupbys
found (counter ``groupby.groups`` over requests): the cardinality the
sort path and the result's sort work on."""

LAYER = "operators"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    return span_reduce.counter_per_request(run, "groupby.groups")

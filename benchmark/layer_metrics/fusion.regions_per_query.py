"""Layer metric ``fusion.regions_per_query``: fused regions dispatched a request
(``fusion.regions`` over requests): 1 for a plan that is one region."""

LAYER = "fusion"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    return span_reduce.counter_per_request(run, "fusion.regions")

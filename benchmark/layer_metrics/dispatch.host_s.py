"""Layer metric ``dispatch.host_s``: host time a request spends in dispatch: its sum of
the spans ``dispatch.pad`` (enqueueing the padded copy), ``dispatch.execute``
(enqueueing the executable; closes at the async return) and
``dispatch.compile`` (none in a warm window), median over the requests."""

LAYER = "dispatch"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    return span_reduce.median_of_spans(run, "dispatch.pad",
                                       "dispatch.execute", "dispatch.compile")

"""Layer metric ``admission.wait_s``: the true admission wait of a request: the span
``admission.queue`` (from the client's enqueue, after its fingerprint, to
the worker's pickup) plus ``admission.wait`` (the limiter's reserve),
median over the window's requests. Stands beside ``admission.queue_wait_s``,
whose clock starts before the fingerprint."""

LAYER = "admission"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    return span_reduce.median_of_spans(run, "admission.queue",
                                       "admission.wait")

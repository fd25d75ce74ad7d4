"""Layer metric ``admission.queue_wait_s``: the median time a request waited between
submit and admission (``QueryTicket.queue_wait_s``)."""

LAYER = "admission"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    import statistics

    waits = [r.queue_wait_s for r in run.requests
             if r.queue_wait_s is not None]
    return statistics.median(waits) if waits else None

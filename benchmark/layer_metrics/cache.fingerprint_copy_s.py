"""Layer metric ``cache.fingerprint_copy_s``: a request's sum of the spans
``cache.fingerprint.copy`` (one a buffer: ``np.asarray`` of the device
array, the copy to the host), median over the window's requests."""

LAYER = "result cache"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    return span_reduce.median_of_spans(run, "cache.fingerprint.copy")

"""Layer metric ``cache.fingerprint_hash_s``: a request's sum of the spans
``cache.fingerprint.hash`` (one a buffer: ``tobytes`` and the sha256
update on the host), median over the window's requests."""

LAYER = "result cache"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    return span_reduce.median_of_spans(run, "cache.fingerprint.hash")

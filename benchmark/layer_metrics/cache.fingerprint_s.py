"""Layer metric ``cache.fingerprint_s``: the median over the window's requests of the span
``cache.fingerprint``: the content fingerprint of the bound tables on the
submitting thread, the device-to-host copy and the sha256 together. Stands
beside ``session.submit_s``, which is this plus the rest of ``submit``."""

LAYER = "result cache"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    return span_reduce.median_of_spans(run, "cache.fingerprint")

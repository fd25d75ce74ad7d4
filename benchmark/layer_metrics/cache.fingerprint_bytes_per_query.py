"""Layer metric ``cache.fingerprint_bytes_per_query``: bytes brought to the host and
hashed a request (``cache.fingerprint_bytes`` over requests). A fresh batch
reads the configuration's table bytes; a repeated table 0."""

LAYER = "result cache"
UNIT = "bytes"
SOURCE = "program_counter"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    return span_reduce.counter_per_request(run, "cache.fingerprint_bytes")

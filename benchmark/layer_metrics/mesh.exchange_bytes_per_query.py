"""Layer metric ``mesh.exchange_bytes_per_query``: bytes a request's
shuffles put on the interconnect (counter ``shuffle.bytes``, moved once a
request by ``QueryServer._account_meta`` from the result's meta: what the
``all_to_all`` of every groupby lowered over a mesh carries between
chips), over the window's requests."""

LAYER = "mesh"
UNIT = "bytes"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    return span_reduce.counter_per_request(run, "shuffle.bytes")

"""Layer metric ``dispatch.padded_copy_bytes_per_query``: bytes of the data leaves
copied to bucket-sized buffers a request (``dispatch.padded_copy_bytes``
over requests): the whole padded copy, beside
``dispatch.padded_bytes_per_query``, which is its waste. The validity
masks, a byte a row a column and written anew at any size, are not in it."""

LAYER = "dispatch"
UNIT = "bytes"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    return span_reduce.counter_per_request(run, "dispatch.padded_copy_bytes")

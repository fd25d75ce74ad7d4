"""Layer metric ``scan.decoded_bytes_per_query``: bytes a request's scan staged to the
device (counter ``scan.decoded_bytes``: the decoded columns' buffers, a
validity buffer only where a group holds a null), over the window's
requests. A whole SF1 split of the seven q1 columns reads 228,046,170."""

LAYER = "scan"
UNIT = "bytes"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    return span_reduce.counter_per_request(run, "scan.decoded_bytes")

"""Layer metric ``scan.file_bytes_per_query``: compressed bytes of the column chunks
a request's scan read (counter ``scan.file_bytes``: the footer's
``total_compressed_size`` of the chunks the projection and the split keep;
the pruned columns' chunks are never touched), over the window's requests."""

LAYER = "scan"
UNIT = "bytes"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    return span_reduce.counter_per_request(run, "scan.file_bytes")

"""Layer metric ``scan.decode_s``: the span ``scan.decode``, from the first column chunk's
decode handed to the shared pool to the last's result taken (the chunks
decode side by side on the pool's threads, so this is wall time, not
their sum: that is ``scan.decode_thread_s``), median over the requests."""

LAYER = "scan"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import scan_reduce

    return scan_reduce.median_of(run, "scan.decode")

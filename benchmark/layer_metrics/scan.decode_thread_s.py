"""Layer metric ``scan.decode_thread_s``: a request's sum of its ``scan.decode.chunk``
spans (one a column chunk, on the decode pool's threads), median over the
requests. Over ``scan.decode_s`` it says how many threads really worked."""

LAYER = "scan"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import scan_reduce

    return scan_reduce.median_of(run, "scan.decode.chunk")

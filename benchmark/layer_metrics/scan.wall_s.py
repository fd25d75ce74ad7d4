"""Layer metric ``scan.wall_s``: a request's time from its split's footer read to its
staged table ready: the span ``scan`` (decode and staging, which overlap:
it is no sum of its parts) and the span ``scan.footer``, which precedes
admission on the submitting thread; median over the window's requests.
Beside ``query_p50_s`` it is the scan's share of a request."""

LAYER = "scan"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import scan_reduce

    return scan_reduce.median_of(run, "scan", "scan.footer")

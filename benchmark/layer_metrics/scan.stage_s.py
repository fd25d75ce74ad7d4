"""Layer metric ``scan.stage_s``: a request's sum of its ``scan.stage`` spans (one a row
group: the host-to-device copies of its columns and their writes into the
table's buffers, enqueued while the pool decodes the next group; and the
last, the wait until the table is ready), median over the requests."""

LAYER = "scan"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import scan_reduce

    return scan_reduce.median_of(run, "scan.stage")

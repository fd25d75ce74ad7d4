"""Layer metric ``scan.footer_s``: the span ``scan.footer`` (the file's envelope checked,
the footer read, pruned to the read schema by name and filtered to the
split's byte range; no page read), median over the window's requests."""

LAYER = "scan"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import scan_reduce

    return scan_reduce.median_of(run, "scan.footer")

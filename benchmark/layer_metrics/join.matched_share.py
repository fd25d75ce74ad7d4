"""Layer metric ``join.matched_share``: of the rows that probed a join in the
window, the share that found a match: counter ``join.matched_rows`` over
``join.probe_rows`` (the server counts both once a request from the
result's meta). Planned q3 reads about 6%: most probes are filtered rows."""

LAYER = "operators"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "higher"


def read(run):
    probed = run.counters.get("join.probe_rows", 0)
    if not probed:   # no join in the mix, or a program that does not count
        return None
    return 100.0 * run.counters.get("join.matched_rows", 0) / probed

"""Layer metric ``filter.kept_share``: of the real rows the plan's predicates saw
in the window, the share they kept: counter ``filter.rows_kept`` over
``filter.rows_in`` (the server counts both once a request from the result's
meta). Planned q13 keeps the orders its ``NOT LIKE`` does not match, over
98%; q6 keeps under 2%."""

LAYER = "operators"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "higher"


def read(run):
    seen = run.counters.get("filter.rows_in", 0)
    if not seen:   # no Filter in the mix, or a program that does not count
        return None
    return 100.0 * run.counters.get("filter.rows_kept", 0) / seen

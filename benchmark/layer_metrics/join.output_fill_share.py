"""Layer metric ``join.output_fill_share``: of the rows the window's joins
that lay rows out had room for, the share that held a real row: counter
``join.matched_rows`` over ``join.capacity_rows`` (the server counts both
once a request from the result's meta: a join's ``total`` where its probe
side holds a scan's rows, and its resolved ``out_rows``). Everything
above such a join (the gathers by its maps, the projection over them)
runs at the capacity's rows, so this is how much of what they write is
real. Not reported on a program whose joins state no capacity."""

LAYER = "operators"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "higher"


def fill_share(counters: dict):
    """Percent of the joins' stated capacity that real rows filled; None
    where no join stated one."""
    room = counters.get("join.capacity_rows", 0)
    if not room:
        return None
    return 100.0 * counters.get("join.matched_rows", 0) / room


def read(run):
    return fill_share(run.counters)

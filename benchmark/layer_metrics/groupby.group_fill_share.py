"""Layer metric ``groupby.group_fill_share``: of the groups the window's
bounded sort-path groupbys had room for, the share they found: counter
``groupby.groups`` over ``groupby.capacity_groups`` (the server counts both
once a request from the result's meta: a groupby's ``num_groups`` and its
resolved ``max_groups``). A bounded groupby's look-ups at the bound's rows
and every node above it run at the bound, so this is how much of what
they carry is real: 6% in planned q3, whose bound is |orders| + 1 for the
orders a date and a segment keep, near 100% in q18, whose every order has
lineitems. Not reported on a program whose groupbys state no capacity."""

LAYER = "operators"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "higher"


def fill_share(counters: dict):
    """Percent of the groupbys' stated bounds that groups filled; None
    where no groupby stated one."""
    room = counters.get("groupby.capacity_groups", 0)
    if not room:
        return None
    return 100.0 * counters.get("groupby.groups", 0) / room


def read(run):
    return fill_share(run.counters)

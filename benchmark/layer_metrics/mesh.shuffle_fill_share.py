"""Layer metric ``mesh.shuffle_fill_share``: of the slots the receive
buffers of a join's exchanges over a mesh have, the share that a real row
filled: counter ``shuffle.rows`` over ``shuffle.capacity_rows`` (the server
counts both once a request from the result's meta: the rows that entered
the two ``hash_shuffle``s of a join lowered over a mesh, and the slots of
their receive buffers, both summed over the chips). The join of what
landed sorts every slot, filled or not, so this is how much of what it
sorts is real. Not reported where no join was exchanged, nor on a program
that does not count the slots."""

LAYER = "mesh"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "higher"


def fill_share(counters: dict):
    """Percent of the exchanges' slots that real rows filled; None where
    no exchange stated its slots."""
    slots = counters.get("shuffle.capacity_rows", 0)
    if not slots:
        return None
    return 100.0 * counters.get("shuffle.rows", 0) / slots


def read(run):
    return fill_share(run.counters)

"""Layer metric ``dispatch.compiles_in_window``: executables compiled inside the
window (``dispatch.compile``); the warm-up exists so that this reads 0."""

LAYER = "dispatch"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "query_p95_s"
BETTER = "lower"


def read(run):
    return run.counters.get("dispatch.compile", 0)

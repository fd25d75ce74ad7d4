"""Layer metric ``dispatch.padded_bytes_per_query``: bytes of padding added a request to
reach the shape bucket (``dispatch.padded_waste_bytes`` over requests). It
counts the waste, not the whole padded copy."""

LAYER = "dispatch"
UNIT = "bytes"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    if not run.requests:
        return None
    return run.counters.get("dispatch.padded_waste_bytes", 0) / len(
        run.requests)

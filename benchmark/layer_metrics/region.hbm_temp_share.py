"""Layer metric ``region.hbm_temp_share``: of what the request's largest
executable needs (``region.hbm_need_bytes``), the share that is XLA's
temporaries: ``temp_bytes`` over ``need_bytes`` of that same
``dispatch.execute`` span, median over the window's held requests. It is
the part ``peak_bytes_in_use`` misses between two readings and the
server's learned estimate (input + result bytes) cannot see."""

LAYER = "operators"
UNIT = "%"
SOURCE = "program_span"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import compile_reduce

    return compile_reduce.median_of_needs(
        run, lambda e: 100.0 * e["temp"] / e["need"])

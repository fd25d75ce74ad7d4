"""Layer metric ``region.hbm_need_bytes``: the HBM the request's largest
executable needs by XLA's own buffer assignment: the largest ``need_bytes``
(arguments + outputs + temporaries less aliased) among a request's
``dispatch.execute`` spans, median over the window's held requests. Over
a mesh it is one chip's share. ``None`` for a program whose spans do not
say."""

LAYER = "operators"
UNIT = "bytes"
SOURCE = "program_span"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import compile_reduce

    return compile_reduce.median_of_needs(run, lambda e: e["need"])

"""Layer metric ``request.untraced_share``: the self time of a request's two root
spans (``submit.<plan>`` and ``query.<plan>``: their duration less what
their children cover) over their duration, median over the window's
requests: what the program's spans do not explain."""

LAYER = "client / session"
UNIT = "%"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    return span_reduce.untraced_share(run)

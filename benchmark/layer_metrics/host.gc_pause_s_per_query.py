"""Layer metric ``host.gc_pause_s_per_query``: seconds a request the host's
garbage collector held every thread of the process (counter
``host.gc_pause_ns`` across the window over its requests). ``None`` for a
program without the counter."""

LAYER = "client / session"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "query_p95_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    ns = span_reduce.counter_per_request(run, "host.gc_pause_ns")
    return None if ns is None else ns / 1e9

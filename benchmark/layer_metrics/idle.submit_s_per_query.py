"""Layer metric ``idle.submit_s_per_query``: the device's idle time a traced request
inside the client's root ``submit.<plan>`` (the footer, the digest's
enqueue and the wait for it, the look-up, the enqueue) before a worker has
the request.
One of the six phases ``benchmark/idle_reduce.py`` gives every idle piece to;
the six sum to the cell's idle time a request. ``None`` for a program without
the client's root ``query.result.<plan>``."""

LAYER = "result cache"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import idle_reduce

    return idle_reduce.phase(run, "submit")

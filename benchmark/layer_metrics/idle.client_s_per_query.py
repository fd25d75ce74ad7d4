"""Layer metric ``idle.client_s_per_query``: the device's idle time a traced request
inside ``bench.request`` and outside every root of the request: the caller's
own time around ``submit`` and ``result()``, and the sync.
One of the six phases ``benchmark/idle_reduce.py`` gives every idle piece to;
the six sum to the cell's idle time a request. ``None`` for a program without
the client's root ``query.result.<plan>``."""

LAYER = "client / session"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import idle_reduce

    return idle_reduce.phase(run, "client")

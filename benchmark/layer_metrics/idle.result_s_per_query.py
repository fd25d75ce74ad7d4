"""Layer metric ``idle.result_s_per_query``: the device's idle time a traced request
under the worker's ``server.record_actual``, ``cache.put``,
``server.account_meta``, ``ticket.resolve`` and the root's own time after
them, up to the resolve.
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

    return idle_reduce.phase(run, "result")

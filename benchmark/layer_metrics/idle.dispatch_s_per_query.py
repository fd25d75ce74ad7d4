"""Layer metric ``idle.dispatch_s_per_query``: the device's idle time a traced request
under the worker's ``rung.*`` / ``region.<plan>`` and their children
``dispatch.pad``, ``.execute``, ``.compile``: the host enqueueing the pad and
the region.
One of the six phases ``benchmark/idle_reduce.py`` gives every idle piece to;
the six sum to the cell's idle time a request. ``None`` for a program without
the client's root ``query.result.<plan>``."""

LAYER = "dispatch"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import idle_reduce

    return idle_reduce.phase(run, "dispatch")

"""Layer metric ``idle.handoff_s_per_query``: the device's idle time a traced request
in the two thread hand-offs: between the end of ``submit.<plan>`` and the
start of ``query.<plan>`` (the worker's pickup), and between the end of
``ticket.resolve`` and the end of ``query.result.<plan>`` (the client's
wake-up while the worker closes its root).
One of the six phases ``benchmark/idle_reduce.py`` gives every idle piece to;
the six sum to the cell's idle time a request. ``None`` for a program without
the client's root ``query.result.<plan>``."""

LAYER = "admission"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import idle_reduce

    return idle_reduce.phase(run, "handoff")

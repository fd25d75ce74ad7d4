"""Layer metric ``request.wake_s``: the client's wake-up: the span ``ticket.wake``,
from the moment the worker resolved the ticket to the return of
``ticket.result()`` on the client's thread, median over the window's
requests. ``None`` for a program that records no ``ticket.wake``."""

LAYER = "client / session"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    import statistics

    from benchmark import span_reduce

    requests = span_reduce.window_requests(run)
    woke = [[r["t1"] - r["t0"] for r in req["spans"]
             if r["op"] == "ticket.wake"] for req in requests or ()]
    if not any(woke):
        return None
    return statistics.median(sum(w) for w in woke)

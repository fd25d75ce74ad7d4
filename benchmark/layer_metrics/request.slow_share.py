"""Layer metric ``request.slow_share``: the share of the window's requests the
server found slow (counter ``server.slow_requests``: more than twice the
median of its plan signature's last 64 and at least 0.010 s over it) and
whose three trees it kept. ``None`` for a program without the counter."""

LAYER = "client / session"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "query_p95_s"
BETTER = "lower"


def read(run):
    from benchmark import span_reduce

    share = span_reduce.counter_per_request(run, "server.slow_requests")
    return None if share is None else 100.0 * share

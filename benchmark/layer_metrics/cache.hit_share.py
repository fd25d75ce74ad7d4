"""Layer metric ``cache.hit_share``: the share of the window's requests served from
the result cache (``cache.hit`` over requests). Fresh batches read 0."""

LAYER = "result cache"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "query_p50_s"
BETTER = "higher"


def read(run):
    if not run.requests:
        return None
    return 100.0 * run.counters.get("cache.hit", 0) / len(run.requests)
